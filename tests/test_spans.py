"""The always-on span recorder: nesting, ends on raise, the ring's wrap
and its dropped count, per-name totals, ``deliberate_sync``'s
``sync:<label>`` spans, and span counts that equal the dispatch
counters on a small CPU fleet and a small CPU serving harness."""
from __future__ import annotations

import numpy as np
import pytest

from repro.analysis import spans
from repro.analysis.spans import Recorder

EVERYTHING = (0, 1 << 62)


def _count(name: str) -> int:
    return spans.totals().get(name, (0, 0))[0]


def _counts(*names):
    return {n: _count(n) for n in names}


def test_nesting_records_the_enclosing_span():
    rec = Recorder(capacity=16)
    with rec.span("outer"):
        with rec.span("inner"):
            pass
        with rec.span("inner"):
            with rec.span("leaf"):
                pass
    with rec.span("outer"):
        pass
    got = rec.records(*EVERYTHING)
    assert [(r[0], r[1], r[4]) for r in got] == [
        (0, "outer", -1), (1, "inner", 0), (2, "inner", 0), (3, "leaf", 2),
        (4, "outer", -1)]
    for seq, _, start, end, parent in got:
        assert start <= end
        if parent >= 0:                  # a child lies inside its parent
            p = got[parent]
            assert p[2] <= start and end <= p[3]


def test_the_end_is_recorded_when_the_body_raises():
    rec = Recorder(capacity=8)
    with pytest.raises(KeyError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise KeyError("x")
    got = rec.records(*EVERYTHING)
    assert [r[1] for r in got] == ["outer", "inner"]
    assert all(r[3] >= r[2] > 0 for r in got)
    with rec.span("after"):              # the stack unwound to the top
        pass
    assert rec.records(*EVERYTHING)[-1][4] == -1
    assert rec.totals()["inner"][0] == 1


def test_ring_wrap_counts_what_it_dropped():
    rec = Recorder(capacity=4)
    for _ in range(3):
        with rec.span("a"):
            pass
    assert rec.dropped == 0
    first = rec.records(*EVERYTHING)[0][2]
    assert rec.holds_since(0)
    for _ in range(3):
        with rec.span("b"):
            pass
    assert rec.dropped == 2
    got = rec.records(*EVERYTHING)
    assert [r[0] for r in got] == [2, 3, 4, 5]       # the newest four
    assert [r[1] for r in got] == ["a", "b", "b", "b"]
    assert not rec.holds_since(first)                # seq 0, 1 are gone
    assert rec.holds_since(got[0][2] + 1)
    # the totals never wrap
    assert rec.totals()["a"][0] == 3 and rec.totals()["b"][0] == 3


def test_an_overwritten_open_span_still_counts_in_totals():
    rec = Recorder(capacity=2)
    with rec.span("long"):
        for _ in range(4):
            with rec.span("short"):
                pass
    assert [r[1] for r in rec.records(*EVERYTHING)] == ["short", "short"]
    assert rec.totals()["long"][0] == 1
    assert rec.dropped == 3


def test_totals_and_records_of_an_interval():
    rec = Recorder(capacity=8)
    with rec.span("x"):
        pass
    mid = rec.records(*EVERYTHING)[0][3]
    with rec.span("y"):
        pass
    with rec.span("x"):
        pass
    tot = rec.totals()
    assert tot["x"][0] == 2 and tot["y"][0] == 1
    got = rec.records(*EVERYTHING)
    assert tot["x"][1] >= sum(r[3] - r[2] for r in got if r[1] == "x")
    later = rec.records(mid, 1 << 62)
    assert [r[1] for r in later] == ["y", "x"]
    with pytest.raises(ValueError):
        Recorder(capacity=6)


@pytest.mark.parametrize("armed", [False, True])
def test_deliberate_sync_records_its_label(armed):
    from repro.analysis import guards
    name = f"sync:test.spans-{armed}"
    before = _count(name)
    if armed:
        jnp = pytest.importorskip("jax.numpy")
        with guards.no_implicit_transfers():
            with guards.deliberate_sync(f"test.spans-{armed}"):
                assert float(jnp.ones(())) == 1.0
    else:
        with guards.deliberate_sync(f"test.spans-{armed}"):
            pass
    assert _count(name) == before + 1


def test_fleet_span_counts_equal_the_arbiter_counters():
    from repro.core import ControllerConfig, PagePool, TenantArbiter
    from repro.core.distribution import (PAPER_WORKLOADS,
                                         sample_lognormal_sizes)
    from repro.memcached import SlabAllocator
    page, n = 1 << 14, 8
    pool = PagePool(2 * n, page_size=page)
    cfg = ControllerConfig(page_size=page, check_every=100,
                           min_items_between_refits=100, device=True)
    arb = TenantArbiter(pool, controller_config=cfg, arbitrate_every=400,
                        fleet=True, fleet_capacity=n)
    for i in range(n):
        arb.register(f"t{i}", SlabAllocator((128, 512, 2048, 8192),
                                            page_size=page, page_pool=pool,
                                            tenant=f"t{i}"))
    pool.equal_partition(floor=1)
    names = ("sync:arbiter.fleet-drift-gate", "arbiter.score",
             "arbiter.apply", "arbiter.tick", "arbiter.gate",
             "arbiter.arbitrate", "controller.frontier", "controller.fit")
    before = _counts(*names)
    rng = np.random.default_rng(5)
    ticks = 0
    while ticks < 40 and (ticks < 6 or sum(
            t.controller.n_refits for t in arb.tenants.values()) == 0):
        for i in range(n):
            w = PAPER_WORKLOADS[i % len(PAPER_WORKLOADS)]
            mu = w.mu * (1.8 if (ticks // 2) % 2 else 1.0)
            arb.observe(f"t{i}", sample_lognormal_sizes(
                rng, 60, mu, w.sigma, max_size=page))
        arb.tick(60 * n)
        ticks += 1
    got = {k: _count(k) - v for k, v in before.items()}
    assert sum(t.controller.n_refits for t in arb.tenants.values()) > 0
    assert arb.n_gate_launches > 0 and arb.n_frontiers_scored > 0
    assert got["sync:arbiter.fleet-drift-gate"] == arb.n_gate_launches
    assert got["arbiter.score"] == arb.n_score_launches
    assert got["arbiter.apply"] == arb.n_frontiers_scored
    assert got["arbiter.tick"] == ticks
    # one gate per tick with a tenant due
    assert arb.n_gate_launches <= got["arbiter.gate"] <= ticks
    assert got["controller.fit"] == got["controller.frontier"] > 0
    assert got["arbiter.arbitrate"] > 0


def test_harness_span_counts_equal_the_dispatch_counters():
    from repro.serving import (KVSlabPool, OfflineHarness, Request,
                               lognormal_request_workload)
    rng = np.random.default_rng(2)
    wl = lognormal_request_workload(rng, 24, prompt_mean=96.0,
                                    prompt_std=64.0, output_mean=8.0,
                                    output_std=4.0, arrival_rate=4.0)
    h = OfflineHarness(KVSlabPool(16384, (128, 256, 512, 1024)),
                       max_batch=8, mode="batched")
    names = ("harness.tick", "harness.decode_dispatch",
             "harness.prefill_dispatch", "harness.admit",
             "harness.decode_phase", "harness.release", "harness.observe")
    before = _counts(*names)
    res = h.run([Request(rid=r.rid, prompt_len=r.prompt_len,
                         output_len=r.output_len, arrival=r.arrival)
                 for r in wl])
    got = {k: _count(k) - v for k, v in before.items()}
    assert res.n_decode_dispatches > 0 and res.n_prefill_dispatches > 0
    assert got["harness.decode_dispatch"] == res.n_decode_dispatches
    assert got["harness.prefill_dispatch"] == res.n_prefill_dispatches
    assert got["harness.tick"] == res.ticks
    for phase in ("harness.admit", "harness.decode_phase",
                  "harness.release", "harness.observe"):
        assert got[phase] == res.ticks
