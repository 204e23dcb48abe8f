"""Host time per fleet round in the program's ``sync:`` spans under
``arbiter.tick``, ms: the labelled readbacks where the arbiter waits on
the device."""
from lib import program_spans


def read(run):
    return program_spans.sync_under_tick_ms(run)
