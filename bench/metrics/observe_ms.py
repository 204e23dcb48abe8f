"""Mean host time of the observe stage of a fleet round, ms."""
import numpy as np


def read(run):
    if run.config["kind"] != "fleet":
        return None
    d = run.spans.durations_ms("observe")
    return float(np.mean(d)) if d else None
