"""Device time of one prefill program in the rate cell, ms."""
from lib.prefill import prefill_ms


def read(run):
    if getattr(run.cell, "closed", True):
        return None
    return prefill_ms(run)
