"""The traffic generator gives every seed the same work: the same
multiset of lengths and gaps in each block (and of sets, deletes and
sizes in each fleet round), in another order and with other ids."""
import json
import sys
from collections import Counter
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lib import traffic  # noqa: E402

SEEDS = (3100000001, 4400000002, 2**33 + 7)


def load(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def blocks(seq, k):
    return [seq[i:i + k] for i in range(0, len(seq) - k + 1, k)]


@pytest.mark.parametrize("mix", ["offline-chat", "chat-rate"])
def test_serving_blocks_hold_the_same_pairs_for_every_seed(mix):
    m = load(mix)
    k = int(m["block"])
    runs = [traffic.serving_requests(m, s) for s in SEEDS]
    per_seed = [[Counter((r["prompt_len"], r["output_len"]) for r in b)
                 for b in blocks(reqs, k)] for reqs in runs]
    assert all(p == per_seed[0] for p in per_seed)
    table = Counter(traffic.serving_table(m))
    assert all(b == table for b in per_seed[0])
    orders = [[(r["prompt_len"], r["output_len"]) for r in reqs]
              for reqs in runs]
    assert orders[0] != orders[1] and orders[1] != orders[2]
    rids = [reqs[0]["rid"] for reqs in runs]
    assert len(set(rids)) == len(rids)
    assert all(r["rid"] < 2**23 for reqs in runs for r in reqs)


def test_serving_lengths_fit_the_top_class():
    cfg = json.loads((BENCH / "configs" / "ouro-2.6b-kv-f32.json")
                     .read_text())
    for mix in ("offline-chat", "chat-rate"):
        for p, o in traffic.serving_table(load(mix)):
            assert 16 <= p and 1 <= o
            assert p + o <= cfg["serving"]["max_class"]


def test_poisson_gaps_hold_the_same_multiset_per_block():
    m = load("chat-rate")
    k = int(m["block"])
    gaps = []
    for s in SEEDS:
        due = [r["due_s"] for r in traffic.serving_requests(m, s)]
        g = [b - a for a, b in zip([0.0] + due, due)]
        gaps.append(g)
    per_seed = [[sorted(round(x, 9) for x in b) for b in blocks(g, k)]
                for g in gaps]
    assert all(p == per_seed[0] for p in per_seed)
    assert gaps[0] != gaps[1]
    mean = sum(gaps[0][:k]) / k
    assert mean == pytest.approx(1.0 / m["arrival"]["rate_per_s"], rel=0.05)


def test_closed_mix_queues_everything_at_once():
    reqs = traffic.serving_requests(load("offline-chat"), SEEDS[0])
    assert {r["due_s"] for r in reqs} == {0.0}


def fleet_mix(mix):
    """A fleet mix: ``phased-drift`` as committed, or ``steady``, the
    same rounds with stationary sizes (no trough borrows)."""
    if mix == "steady":
        return dict(load("phased-drift"), trough_mix=0.0)
    return load(mix)


@pytest.mark.parametrize("mix", ["phased-drift", "steady"])
def test_fleet_rounds_hold_the_same_work_for_every_seed(mix):
    cfg = json.loads((BENCH / "configs" / "memcached-fleet-1k.json")
                     .read_text())
    cfg = dict(cfg, tenants=40)
    m = fleet_mix(mix)
    gens = [traffic.FleetTraffic(cfg, m, s) for s in SEEDS]
    for r in range(6):
        rounds = [g.round_ops(r) for g in gens]
        work = [Counter((op, t, size) for op, t, _, size in ops)
                for ops in rounds]
        assert all(w == work[0] for w in work)
        assert rounds[0] != rounds[1]
        keys = [{k for _, _, k, _ in ops} for ops in rounds]
        assert not keys[0] & keys[1]
        # each tenant's own sequence of ops and sizes is the same
        for t in range(40):
            seqs = [[(op, size) for op, tt, _, size in ops if tt == t]
                    for ops in rounds]
            assert all(q == seqs[0] for q in seqs)
    assert any(op == traffic.DELETE for op, _, _, _ in rounds[0])


def test_fleet_drift_only_in_phased_mix():
    cfg = json.loads((BENCH / "configs" / "memcached-fleet-1k.json")
                     .read_text())
    cfg = dict(cfg, tenants=8)
    own = {}
    for mix in ("phased-drift", "steady"):
        g = traffic.FleetTraffic(cfg, fleet_mix(mix), SEEDS[0])
        sizes = Counter()
        for r in range(8):
            for op, t, _, size in g.round_ops(r):
                if op == traffic.SET and t % 4 == 0:
                    sizes[size > 800] += 1
        own[mix] = sizes
    assert own["steady"][True] == 0          # tenant 0 stays near 518 B
    assert own["phased-drift"][True] > 0     # its troughs borrow 1,210 B


def test_rounds_are_generated_in_order():
    cfg = json.loads((BENCH / "configs" / "memcached-fleet-1k.json")
                     .read_text())
    g = traffic.FleetTraffic(dict(cfg, tenants=4), fleet_mix("steady"), 1)
    with pytest.raises(ValueError):
        g.round_ops(1)


def test_stratified_table_is_the_inverse_cdf_at_midpoints():
    vals = traffic.stratified({"dist": "exponential", "mean": 2.0}, 4)
    import math
    want = [-2.0 * math.log1p(-(i + 0.5) / 4) for i in range(4)]
    assert list(vals) == pytest.approx(want)
    with pytest.raises(ValueError):
        traffic.seed_rng(-1, "x")
