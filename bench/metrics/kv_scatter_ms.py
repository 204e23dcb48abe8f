"""Device time of the KV scatter kernels (new-token append and
class-overflow chunk copies) per decode step, ms."""
from lib import trace as trace_lib

KERNELS = ("kv_append", "kv_chunk_copy")


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    cell = run.cell
    steps = len(cell.decode_lanes) - cell.base_dispatch
    total = sum(sum(trace_lib.op_durations(run.trace, k)) for k in KERNELS)
    if steps <= 0 or total == 0:
        return None
    return 1e3 * total / steps
