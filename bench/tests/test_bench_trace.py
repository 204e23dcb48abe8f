"""The reduction from a profiler trace to per-layer numbers, on a small
synthetic trace with known answers and on an excerpt of a trace
recorded on a TPU v5e (``fixtures/``)."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from lib import trace as T  # noqa: E402
from lib import work  # noqa: E402
from lib.harness import read_per_layer  # noqa: E402

NS = 1e-9

SYNTH = {
    "window": [0, 100],
    "devices": {"/device:TPU:0": {
        "ops": [["slab_decode_attention_pallas.1", 10, 20],
                ["kv_append_pallas.2", 20, 20],
                ["while.13", 60, 10]],
        "modules": [["jit_run(1)", 5, 40], ["jit_run(2)", 58, 14]]}},
    "host": [["window", 0, 100], ["tick", 0, 50], ["tick", 55, 45],
             ["final_sync", 90, 10]],
}


def test_busy_is_the_union_of_op_intervals():
    assert T.merged_busy(SYNTH["devices"]["/device:TPU:0"]["ops"], 0, 100) \
        == [(10, 40), (60, 70)]
    assert T.busy_seconds(SYNTH) == pytest.approx(40 * NS)
    assert T.window_seconds(SYNTH) == pytest.approx(100 * NS)


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = T.idle_gaps(SYNTH)
    # [0,10) and [70,100) lie in ticks (the final sync covers 90-100 but
    # the gap's midpoint, 85, is in the tick); [40,60) in no span
    assert gaps == pytest.approx({"tick": 40 * NS, "between_spans": 20 * NS})
    assert sum(gaps.values()) + T.busy_seconds(SYNTH) == \
        pytest.approx(T.window_seconds(SYNTH))


def test_ops_are_clipped_to_the_window_and_grouped_by_module():
    clipped = dict(SYNTH, window=[15, 65])
    secs = T.op_seconds(clipped)
    assert secs["slab_decode_attention_pallas.1"] == pytest.approx(15 * NS)
    assert secs["while.13"] == pytest.approx(5 * NS)
    runs = T.module_runs(SYNTH)
    assert runs == [(pytest.approx(40 * NS),
                     ["slab_decode_attention_pallas.1", "kv_append_pallas.2"]),
                    (pytest.approx(14 * NS), ["while.13"])]
    assert T.op_durations(SYNTH, "slab_decode") == [pytest.approx(20 * NS)]


def test_breakdown_lists_at_most_ten_of_each():
    many = {"window": [0, 1000], "host": [["window", 0, 1000]],
            "devices": {"/device:TPU:0": {
                "ops": [[f"op{i}", 10 * i, 5] for i in range(30)],
                "modules": []}}}
    b = T.breakdown(many)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 1


def _serving_run(trace, live, lanes, prompts):
    cell = SimpleNamespace(closed=True, tick_ms=[14.0, 16.0],
                           decode_live_tokens=[0] + live,
                           decode_lanes=[1] + lanes, base_dispatch=1,
                           prefill_prompt_tokens=[1] + prompts,
                           base_prefill=1)
    cfg = {"kind": "serving", "num_key_value_heads": 8, "head_dim": 128,
           "num_attention_heads": 8}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return SimpleNamespace(trace=trace, cell=cell, config=cfg, peaks=peaks,
                           spans=None)


def test_serving_readers_on_the_synthetic_trace():
    run = _serving_run(SYNTH, live=[64_000], lanes=[64], prompts=[1000])
    assert read_per_layer("decode_attn_ms", run) == pytest.approx(20e-6)
    assert read_per_layer("kv_scatter_ms", run) == pytest.approx(20e-6)
    assert read_per_layer("prefill_ms.offline", run) == \
        pytest.approx(14e-6)
    assert read_per_layer("prefill_ms.rate", run) is None
    assert read_per_layer("tick_host_ms.offline", run) == 15.0
    assert read_per_layer("idle_share.offline", run) == pytest.approx(60.0)
    least = work.least_seconds(work.decode_attention(64_000, 64, run.config),
                               run.peaks)
    assert read_per_layer("decode_attn_roofline", run) == \
        pytest.approx(100 * least / (20 * NS))


def test_roofline_counts_live_lengths_not_tiles():
    cfg = {"num_key_value_heads": 8, "head_dim": 128,
           "num_attention_heads": 8}
    w = work.decode_attention(1000, 2, cfg)
    assert w["bytes"] == 1000 * 8 * 128 * 4 * 2 + 2 * 8 * 128 * 4 * 2
    assert w["flops"] == 1000 * 8 * 128 * 4


FIXTURES = sorted((HERE / "fixtures").glob("excerpt-*.json"))


@pytest.mark.parametrize("path", FIXTURES, ids=[p.stem for p in FIXTURES])
def test_recorded_excerpt_reduces_within_bounds(path):
    tr = json.loads(path.read_text())
    busy, win = T.busy_seconds(tr), T.window_seconds(tr)
    assert 0 < busy <= win
    gaps = T.idle_gaps(tr)
    assert sum(gaps.values()) + busy == pytest.approx(win, rel=1e-6)
    b = T.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10
    secs = T.op_seconds(tr)
    assert max(secs.values()) <= win * len(tr["devices"])


def test_recorded_serving_excerpt_names_its_layers():
    # 0.25 s of the offline-chat window on a TPU v5e: decode attention
    # leads, prefill programs are the module runs with a while loop
    tr = json.loads((HERE / "fixtures" / "excerpt-offline-chat.json")
                    .read_text())
    top = T.breakdown(tr)["device_ops"][0][0]
    assert top.startswith("slab_decode_attention")
    run = _serving_run(tr, live=[1], lanes=[1], prompts=[1])
    prefill = read_per_layer("prefill_ms.offline", run)
    assert 5.0 < prefill < 50.0
    assert read_per_layer("decode_attn_ms", run) > 1.0
    assert T.busy_seconds(tr) / T.window_seconds(tr) > 0.9
