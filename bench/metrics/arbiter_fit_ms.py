"""Host time per fleet round in the program's ``controller.fit`` spans,
ms: the policy fits of the tenants whose drift passed the gates."""
from lib import program_spans


def read(run):
    return program_spans.per_round_ms(run, ("controller.fit",))
