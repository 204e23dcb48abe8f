"""Device time of one prefill program in the offline cell, ms."""
from lib.prefill import prefill_ms


def read(run):
    if not getattr(run.cell, "closed", False):
        return None
    return prefill_ms(run)
