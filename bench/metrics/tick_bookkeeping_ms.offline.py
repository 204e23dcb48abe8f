"""Host time per serving tick in the program's bookkeeping spans
(``harness.admit``, ``.decode_phase``, ``.release``, ``.observe``) in a
closed (offline) mix, ms."""
from lib import program_spans

NAMES = ("harness.admit", "harness.decode_phase", "harness.release",
         "harness.observe")


def read(run):
    return program_spans.per_tick_ms(run, NAMES, closed=True)
