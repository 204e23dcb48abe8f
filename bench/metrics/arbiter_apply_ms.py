"""Host time per fleet round in the program's ``arbiter.apply`` spans,
ms: each scored frontier's decision, its migration cost and, when
approved, the allocator's reconfigure."""
from lib import program_spans


def read(run):
    return program_spans.per_round_ms(run, ("arbiter.apply",))
