"""Device-idle time per serving tick while the host was innermost in a
bookkeeping span (``harness.admit``, ``.decode_phase``, ``.release``,
``.observe``) in an open mix, ms, read from the profiler trace."""
from lib import program_spans

NAMES = ("harness.admit", "harness.decode_phase", "harness.release",
         "harness.observe")


def read(run):
    return program_spans.idle_per_tick_ms(run, NAMES, closed=False)
