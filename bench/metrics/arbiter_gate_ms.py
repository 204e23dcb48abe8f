"""Host time per fleet round in the program's ``arbiter.gate`` span,
ms: the batched drift gate with its window flushes, the stacks, the
launch and its readback."""
from lib import program_spans


def read(run):
    return program_spans.per_round_ms(run, ("arbiter.gate",))
