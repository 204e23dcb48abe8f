"""Decode attention's share of its roofline, %: the least time the
chip needs for the work the window's calls require (live KV rows read,
queries and outputs, matmul FLOPs; lib/work.py), over the kernel's
measured device time. Counted from live lengths, not from the tiles the
kernel reads."""
from lib import trace as trace_lib
from lib import work

KERNEL = "slab_decode_attention"


def read(run):
    if run.trace is None or not run.trace["devices"] or not run.peaks:
        return None
    cell = run.cell
    times = trace_lib.op_durations(run.trace, KERNEL)
    live = cell.decode_live_tokens[cell.base_dispatch:]
    lanes = cell.decode_lanes[cell.base_dispatch:]
    if not times or not live:
        return None
    least = [work.least_seconds(work.decode_attention(t, n, run.config),
                                run.peaks) for t, n in zip(live, lanes)]
    # mean over calls on both sides: the i-th launch in the trace is the
    # i-th dispatch, but a mean stays sound if the profiler drops events
    return 100.0 * (sum(least) / len(least)) / (sum(times) / len(times))
