"""Mean device time of one decode-attention kernel launch, ms."""
import numpy as np

from lib import trace as trace_lib

KERNEL = "slab_decode_attention"


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    d = trace_lib.op_durations(run.trace, KERNEL)
    return float(np.mean(d)) * 1e3 if d else None
