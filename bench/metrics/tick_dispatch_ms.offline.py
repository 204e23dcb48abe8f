"""Host time per serving tick in the program's dispatch spans
(``harness.prefill_dispatch``, ``harness.decode_dispatch``) in a closed
(offline) mix, ms: input copies and the launches."""
from lib import program_spans

NAMES = ("harness.prefill_dispatch", "harness.decode_dispatch")


def read(run):
    return program_spans.per_tick_ms(run, NAMES, closed=True)
