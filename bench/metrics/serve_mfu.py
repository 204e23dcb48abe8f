"""The whole serving step's share of the chip's peak, %: the least time
the chip needs for all the work the window requires (decode attention
over live lengths, the new tokens' KV rows, the prompts' KV rows;
lib/work.py), each piece bounded by peak FLOP/s or peak HBM bandwidth,
over the traced window's length."""
from lib import trace as trace_lib
from lib import work


def read(run):
    if run.trace is None or not run.trace["devices"] or not run.peaks:
        return None
    cell = run.cell
    b = cell.base_dispatch
    live, lanes = cell.decode_live_tokens[b:], cell.decode_lanes[b:]
    if not live:
        return None
    cfg, peaks = run.config, run.peaks
    least = sum(work.least_seconds(work.decode_attention(t, n, cfg), peaks)
                for t, n in zip(live, lanes))
    least += work.least_seconds(work.kv_rows_written(sum(lanes), cfg), peaks)
    prompts = sum(cell.prefill_prompt_tokens[cell.base_prefill:])
    least += work.least_seconds(work.kv_rows_written(prompts, cfg), peaks)
    return 100.0 * least / trace_lib.window_seconds(run.trace)
