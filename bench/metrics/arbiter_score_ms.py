"""Host time per fleet round in the program's ``arbiter.score`` spans,
ms: the batched frontier scoring launch and its readback."""
from lib import program_spans


def read(run):
    return program_spans.per_round_ms(run, ("arbiter.score",))
