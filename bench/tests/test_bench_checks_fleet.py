"""The fleet cells' correctness check, driven end to end on the CPU at a
small size with the look for a chip skipped: a sound run comes out
correct, and a run whose timed path is broken underneath (frontier
scores altered, a sketch update that leaves the state unchanged, half
of an observe window dropped) comes out not correct. The control
(waste summed in bfloat16) fails the limit."""
import io
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import tinydoc  # noqa: E402
from lib.harness import main  # noqa: E402

PHASED = "memcached-fleet-1k.phased-drift"


@pytest.fixture(scope="module")
def doc(tmp_path_factory):
    return tinydoc.build(tmp_path_factory.mktemp("doc"))


def run(doc, workload, seed=21, control=False, trace=0):
    out, err = io.StringIO(), io.StringIO()
    rc = main(["--workload", workload, "--seed", str(seed),
               "--seconds", "1.5", "--trace", str(trace)],
              require_accelerator=False, doc_root=doc, use_cache=False,
              control=control, out=out, err=err)
    assert rc == 0, err.getvalue()[-3000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture
def fresh_flush(monkeypatch):
    import repro.core.observe as obs
    monkeypatch.setattr(obs, "_WINDOW_FLUSH", {})
    return obs


@pytest.mark.parametrize("workload", [PHASED])
def test_sound_run_is_correct(doc, workload):
    res = run(doc, workload)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["alloc_ops_per_s"]["value"] > 0
    assert 0 < res["metrics"]["hole_fraction"]["value"] < 1
    assert list(res)[-1] == "checks"


def test_traced_run_reads_its_host_layers(doc):
    res = run(doc, PHASED, trace=1)
    assert res["correct"] is True
    for name in ("op_host_us", "observe_ms", "arbiter_tick_ms",
                 "refits_per_kop"):
        assert name in res["metrics"], res["metrics"]
    assert "window_s" in res["device"] and "breakdown" in res


def test_altered_frontier_scores_are_caught(doc, monkeypatch):
    import repro.kernels.ops as ops
    batch, solo = ops.waste_eval_fleet, ops.waste_eval
    monkeypatch.setattr(ops, "waste_eval_fleet",
                        lambda *a, **k: batch(*a, **k) * 1.001)
    monkeypatch.setattr(ops, "waste_eval",
                        lambda *a, **k: solo(*a, **k) * 1.001)
    res = run(doc, PHASED)
    assert res["correct"] is False
    assert res["checks"]["frontier_rel_err"]["value"] > 5e-4


def test_sketch_update_that_leaves_the_state_unchanged_is_caught(
        doc, fresh_flush, monkeypatch):
    obs = fresh_flush
    orig = obs._window_flush_fn

    def frozen(*a, **k):
        fn = orig(*a, **k)
        return lambda state, *rest: (state, fn(state, *rest)[1])

    monkeypatch.setattr(obs, "_window_flush_fn", frozen)
    res = run(doc, PHASED)
    assert res["correct"] is False
    assert res["checks"]["sketch_count_err"]["value"] > 0


def test_half_an_observe_window_dropped_is_caught(doc, fresh_flush,
                                                  monkeypatch):
    obs = fresh_flush
    orig = obs._window_flush_fn

    def half(*a, **k):
        fn = orig(*a, **k)

        def run_half(state, sizes, weights, lengths, *rest):
            import numpy as np
            lengths = np.asarray(lengths) // 2
            return fn(state, sizes, weights, lengths, *rest)
        return run_half

    monkeypatch.setattr(obs, "_window_flush_fn", half)
    res = run(doc, PHASED)
    assert res["correct"] is False


def test_control_fails_the_limit(doc):
    # the control's scores go through the cell's own comparison
    res = run(doc, PHASED, control=True)
    assert res["correct"] is False
    check = res["checks"]["frontier_rel_err"]
    assert check["value"] > check["limit"]
    assert all(c["value"] <= c["limit"] for name, c in res["checks"].items()
               if name != "frontier_rel_err")


def test_a_round_counts_only_served_ops(doc):
    # ten sizes in ten slab classes against a tenant's six pages: the
    # classes that get no page refuse their sets (memcached's out of
    # memory reply), and the round leaves them out of its served ops
    from kinds.fleet import Fleet
    from lib import traffic
    from lib.common import Spans
    cfg = json.loads((doc / "fleet.json").read_text())
    fleet = Fleet(cfg)
    sizes = [100, 200, 400, 800, 1600, 3200, 4800, 6400, 8000, 12000]
    ops = [(traffic.SET, 0, f"k{i}", sizes[i % 10]) for i in range(40)]
    ops.append((traffic.DELETE, 0, "k0", 0))
    before = sum(a.n_rejected for a in fleet.allocs)
    served = fleet.round(ops, Spans())
    refused = sum(a.n_rejected for a in fleet.allocs) - before
    assert refused > 0
    assert served == len(ops) - refused
