"""Mean host time of the arbiter_tick stage of a fleet round, ms."""
import numpy as np


def read(run):
    if run.config["kind"] != "fleet":
        return None
    d = run.spans.durations_ms("arbiter_tick")
    return float(np.mean(d)) if d else None
