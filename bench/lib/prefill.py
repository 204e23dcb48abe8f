"""Shared by the prefill readers: mean device time of the programs that
write prompts into the pool (a loop over the batch and no decode
attention), ms."""
import numpy as np

from lib import trace as trace_lib

DECODE_KERNEL = "slab_decode_attention"


def prefill_ms(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    runs = [sec for sec, names in trace_lib.module_runs(run.trace)
            if any(n.startswith("while") for n in names)
            and not any(n.startswith(DECODE_KERNEL) for n in names)]
    return float(np.mean(runs)) * 1e3 if runs else None
