"""Differential tests: ``TenantArbiter(fleet=True)`` vs the legacy loop.

The fleet refactor's contract is *bit-identity*, not closeness: on host
sketches, every ``TransferDecision`` field (floats included), every
refit verdict, every quota and stats counter must equal the legacy
per-tenant Python loop's output on the same op stream. The suite drives
1–8 tenant twins through phased multi-tenant traffic (forecast on and
off), through join/leave churn mid-stream, and through the
observe/tick serving mode with device sketches (where the batched gate
replaces per-tenant launches — decisions must still agree), plus unit
tests for the stacked-state plumbing itself: row alloc/free/reuse
zeroing, capacity growth, ``FleetSketchView`` aliasing, the batched
drift gate vs the scalar distance, the gate's fused fold of every due
tenant's observe window vs solo sketches, and ``acf_period_batch`` vs
the scalar forecaster.

When ``hypothesis`` is installed, a fuzz layer searches random tenant
counts / seeds / pool shapes for parity violations; the deterministic
parametrized cases below run everywhere (CI has no hypothesis).
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.analysis.guards import no_implicit_transfers
from repro.core import (ControllerConfig, FleetState, PagePool,
                        TenantArbiter)
from repro.core.distribution import (PAPER_WORKLOADS,
                                     sample_lognormal_sizes)
from repro.core.forecast import DemandForecaster, acf_period_batch
from repro.core.observe import DeviceSizeSketch, histogram_distance_device
from repro.memcached import SlabAllocator, multitenant_phased_ops

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:                                    # pragma: no cover
    hypothesis = None

PAGE = 1 << 14
CLASSES = (128, 512, 2048, 8192)


# ---------------------------------------------------------------------------
# twin harness
# ---------------------------------------------------------------------------

def _build(n_tenants, *, fleet, total_pages=None, forecast=True,
           device=False, check_every=150, arbitrate_every=400,
           fleet_capacity=4):
    pool = PagePool(total_pages or 2 * n_tenants, page_size=PAGE)
    fc = DemandForecaster(ring=10, min_confidence=0.05) if forecast \
        else None
    cfg = ControllerConfig(page_size=PAGE, check_every=check_every,
                           min_items_between_refits=2 * check_every,
                           device=device)
    arb = TenantArbiter(pool, controller_config=cfg,
                        arbitrate_every=arbitrate_every, forecast=fc,
                        fleet=fleet, fleet_capacity=fleet_capacity)
    for i in range(n_tenants):
        name = f"t{i}"
        arb.register(name, SlabAllocator(CLASSES, page_size=PAGE,
                                         page_pool=pool, tenant=name))
    pool.equal_partition(floor=1)
    return arb


def _ops(n_tenants, n_sets, seed):
    if n_tenants == 1:
        rng = np.random.default_rng(seed)
        sizes = rng.integers(100, 7000, size=n_sets)
        return [(0, "set", f"k{i}", int(s)) for i, s in enumerate(sizes)]
    workloads = [PAPER_WORKLOADS[i % len(PAPER_WORKLOADS)]
                 for i in range(n_tenants)]
    return [(op.tenant, op.op, op.key, op.size)
            for op in multitenant_phased_ops(workloads, n_sets=n_sets,
                                             trough_mix=0.5, seed=seed)]


def _feed(arb, ops, events=()):
    """Replay ops; ``events`` is {op_index: callable(arb)} for mid-
    stream churn (join/leave) — fired at the same index in both twins."""
    events = dict(events)
    for i, (tn, op, key, size) in enumerate(ops):
        if i in events:
            events[i](arb)
        name = f"t{tn}"
        if name not in arb.tenants:
            continue                       # removed mid-stream
        if op == "set":
            arb.set(name, key, size)
        elif op == "delete":
            arb.delete(name, key)
        else:
            arb.get(name, key)
    arb.arbitrate()


def _transfer_sig(arb):
    return [(d.approved, d.reason, d.donor, d.recipient, d.benefit,
             d.cost, d.forecast_penalty, d.evicted_items,
             d.evicted_bytes, d.at_op) for d in arb.decisions]


def _refit_sig(arb, *, exact_drift=True):
    return [(n, d.approved, d.reason,
             d.drift if exact_drift else round(float(d.drift), 6),
             tuple(np.asarray(d.chunks).tolist())
             if d.chunks is not None else None)
            for n in sorted(arb.tenants)
            for d in arb.tenants[n].controller.decisions]


def _assert_twins_equal(legacy, fleet, *, exact_drift=True):
    assert _transfer_sig(legacy) == _transfer_sig(fleet)
    assert _refit_sig(legacy, exact_drift=exact_drift) \
        == _refit_sig(fleet, exact_drift=exact_drift)
    assert legacy.stats() == fleet.stats()
    assert legacy.n_transfers == fleet.n_transfers
    assert legacy.n_bounced == fleet.n_bounced
    for name in legacy.tenants:
        assert legacy.pool.quota(name) == fleet.pool.quota(name)
        assert legacy.pool.owned(name) == fleet.pool.owned(name)
    assert legacy.pool.conserved and fleet.pool.conserved


def _twin_run(n_tenants, seed, **kw):
    ops = _ops(n_tenants, kw.pop("n_sets", 1200), seed)
    events = kw.pop("events", None)
    legacy = _build(n_tenants, fleet=False, **kw)
    fleet = _build(n_tenants, fleet=True, **kw)
    _feed(legacy, ops, events(legacy) if events else ())
    _feed(fleet, ops, events(fleet) if events else ())
    return legacy, fleet


# ---------------------------------------------------------------------------
# differential parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_tenants,seed", [
    (2, 0), (3, 7), (4, 13), (5, 3), (8, 42)])
def test_host_parity(n_tenants, seed):
    """Host-path fleets decide bit-identically to the legacy loop —
    transfers, refits, quotas, stats — across 2..8 tenants."""
    legacy, fleet = _twin_run(n_tenants, seed)
    _assert_twins_equal(legacy, fleet)
    assert fleet.n_transfers > 0, "stream built no pressure; test is vacuous"


@pytest.mark.parametrize("n_tenants,seed", [(3, 1), (6, 11)])
def test_host_parity_reactive(n_tenants, seed):
    """Same with the forecaster off (no surcharge stage at all)."""
    legacy, fleet = _twin_run(n_tenants, seed, forecast=False)
    _assert_twins_equal(legacy, fleet)


def test_forecast_penalty_exercised():
    """The parity claim must cover rounds where the surcharge is
    nonzero — otherwise the batched ACF stage is untested."""
    legacy, fleet = _twin_run(4, 13, n_sets=3000, arbitrate_every=150)
    _assert_twins_equal(legacy, fleet)
    assert any(d.forecast_penalty > 0 for d in fleet.decisions), \
        "no decision carried a forecast surcharge; shrink the ring"


def test_device_set_path_parity():
    """Device sketches, set-driven: the per-set refit pipeline uses the
    solo gate in both modes — decisions stay bit-identical."""
    legacy, fleet = _twin_run(3, 5, device=True, check_every=120)
    _assert_twins_equal(legacy, fleet)


def test_tick_driven_batched_gate_parity():
    """Serving mode (observe + tick): the fleet batches due tenants'
    drift gates into one launch per tick. Verdicts must agree with
    legacy's per-tenant launches (drift compared to 1e-6 — different
    launch shapes may round the last ulp differently), and the launch
    count must be O(ticks), not O(tenants)."""
    n, ticks = 6, 8
    legacy = _build(n, fleet=False, device=True, check_every=100,
                    arbitrate_every=10**9)
    fleet = _build(n, fleet=True, device=True, check_every=100,
                   arbitrate_every=10**9)
    for arb in (legacy, fleet):
        rng = np.random.default_rng(3)
        # batched gate launches run under the transfer sanitizer: the
        # only legal syncs are the deliberate_sync-declared gate reads
        with no_implicit_transfers():
            for r in range(ticks):
                for i in range(n):
                    w = PAPER_WORKLOADS[i % len(PAPER_WORKLOADS)]
                    mu = w.mu * (1.7 if (r // 2) % 2 else 1.0)
                    arb.observe(f"t{i}", sample_lognormal_sizes(
                        rng, 60, mu, w.sigma, max_size=PAGE))
                arb.tick(1)
    assert _refit_sig(legacy, exact_drift=False) \
        == _refit_sig(fleet, exact_drift=False)
    assert legacy.n_gate_launches == 0
    assert 1 <= fleet.n_gate_launches <= ticks
    assert fleet.n_score_launches <= ticks


def test_single_tenant_degenerate():
    """One tenant: nobody can donate to anybody. Both modes record the
    same no-eligible-donor verdicts and never crash."""
    legacy, fleet = _twin_run(1, 9, total_pages=2, n_sets=600,
                              arbitrate_every=200)
    _assert_twins_equal(legacy, fleet)
    assert all(d.reason == "no-eligible-donor" for d in fleet.decisions)
    assert len(fleet.decisions) > 0


def test_join_leave_mid_stream():
    """A tenant joins and another leaves at fixed op indices in both
    twins; parity holds through the churn, the pool stays conserved,
    and the leaver's fleet row is freed for the joiner that follows."""
    def events(arb):
        def join(a, name):
            a.register(name, SlabAllocator(CLASSES, page_size=PAGE,
                                           page_pool=a.pool, tenant=name),
                       quota=1, floor_pages=0)

        return {300: lambda a: join(a, "t4"),
                700: lambda a: a.remove("t1"),
                900: lambda a: join(a, "t5")}

    legacy, fleet = _twin_run(4, 21, events=events, n_sets=1400)
    assert "t1" not in legacy.tenants and "t1" not in fleet.tenants
    _assert_twins_equal(legacy, fleet)
    f = fleet.fleet
    assert "t1" not in f.row_of
    # t5 joined after t1 left: the LIFO free-list must have reused the row
    assert f.row_of["t5"] == 1
    assert f.n_active == len(fleet.tenants)


def test_remove_drains_pages_and_conserves():
    arb = _build(3, fleet=True, fleet_capacity=2)   # forces one grow
    for i in range(40):
        arb.set("t1", f"k{i}", 4000)
    assert arb.pool.owned("t1") > 0
    arb.remove("t1")
    assert arb.pool.conserved
    assert "t1" not in arb.pool.tenants()
    assert "t1" not in arb.fleet.row_of


# ---------------------------------------------------------------------------
# stacked-state plumbing
# ---------------------------------------------------------------------------

def test_row_alloc_free_reuse_zeroing():
    f = FleetState(capacity=2,
                   forecaster=DemandForecaster(ring=8))
    ra = f.alloc_row("a")
    rb = f.alloc_row("b")
    f.owned[ra] = 5
    f.quota[ra] = 7
    f.pressure[ra] = 3.5
    f.record_demand(np.array([ra]), np.array([100.0]))
    f.free_row("a")
    assert not f.active[ra]
    assert f.owned[ra] == 0 and f.quota[ra] == -1
    assert f.pressure[ra] == 0.0 and f.ring_len[ra] == 0
    assert float(np.abs(f.demand_ring[ra]).sum()) == 0.0
    rc = f.alloc_row("c")
    assert rc == ra                      # LIFO reuse
    assert f.name_of == ["c", "b"]
    assert f.row_of == {"c": rc, "b": rb}
    with pytest.raises(ValueError):
        f.alloc_row("c")


def test_grow_preserves_state():
    f = FleetState(capacity=1)
    r0 = f.alloc_row("a")
    f.owned[r0] = 9
    f.ensure_sketch(16)
    f.sketch = f.sketch.at[r0, 3].set(2.0)
    for name in "bcd":
        f.alloc_row(name)
    assert f.capacity >= 4
    assert f.owned[r0] == 9
    assert f.quota[f.row_of["d"]] == -1     # grown rows carry the sentinel
    assert float(f.sketch[r0, 3]) == 2.0
    assert f.sketch.shape[0] == f.capacity


def test_fleet_sketch_view_aliases_fleet_row():
    f = FleetState(capacity=3)
    cfg = ControllerConfig(page_size=PAGE, device=True, check_every=50)
    row = f.alloc_row("a")
    view = f.sketch_view(row, cfg)
    solo = DeviceSizeSketch(half_life=view.half_life,
                            num_buckets=view.num_buckets,
                            bucket_width=view.bucket_width,
                            window=True)
    rng = np.random.default_rng(0)
    sizes = rng.integers(64, PAGE, size=500)
    view.observe_many(sizes)
    solo.observe_many(sizes)
    view.flush_window()
    solo.flush_window()
    np.testing.assert_array_equal(np.asarray(view.weights_device),
                                  np.asarray(solo.weights_device))
    # the view's weights ARE the fleet row
    np.testing.assert_array_equal(np.asarray(view.weights_device),
                                  np.asarray(f.sketch[row]))
    assert float(np.abs(np.asarray(f.sketch[(row + 1) % 3])).sum()) == 0.0


@pytest.mark.parametrize("metric", ["l1", "emd"])
def test_drift_gate_fleet_matches_scalar(metric):
    from repro.kernels.fleet_gate import drift_gate_fleet
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    refs = jnp.asarray(rng.random((5, 64), dtype=np.float32))
    live = jnp.asarray(rng.random((5, 64), dtype=np.float32))
    batched = np.asarray(drift_gate_fleet(refs, live, metric=metric))
    solo = np.array([float(histogram_distance_device(refs[i], live[i],
                                                     metric=metric))
                     for i in range(5)])
    np.testing.assert_allclose(batched, solo, rtol=1e-6, atol=1e-7)


def test_drift_gate_fleet_rejects_bad_input():
    from repro.kernels.fleet_gate import drift_gate_fleet
    import jax.numpy as jnp
    a = jnp.zeros((2, 8))
    with pytest.raises(ValueError):
        drift_gate_fleet(a, jnp.zeros((3, 8)), metric="l1")
    with pytest.raises(ValueError):
        drift_gate_fleet(a, a, metric="cosine")


def test_acf_period_batch_matches_scalar():
    """A batch of N rows must return the N scalar answers bitwise —
    the property the fleet's forecast stage parity rests on."""
    rng = np.random.default_rng(4)
    lengths = np.array([4, 7, 10, 10, 10, 3, 10], dtype=np.int64)
    ring = int(lengths.max())
    series = np.zeros((len(lengths), ring))
    fc = DemandForecaster(ring=ring, min_confidence=0.05)
    for i, ln in enumerate(lengths):
        periodic = 100.0 * np.sin(2 * np.pi * np.arange(ln) / 5.0)
        series[i, :ln] = periodic + rng.normal(0, 5.0, ln)
    lags, confs = acf_period_batch(series, lengths,
                                   min_cycles=fc.min_cycles,
                                   min_confidence=fc.min_confidence)
    for i, ln in enumerate(lengths):
        fc._rings.clear() if hasattr(fc, "_rings") else None
        scalar = DemandForecaster(ring=ring, min_confidence=0.05)
        for v in series[i, :ln]:
            scalar.record_window("x", demand_bytes=float(v))
        lag, conf = scalar.period("x")
        if lag is None:
            assert lags[i] == -1
        else:
            assert lags[i] == lag
            assert confs[i] == conf       # bitwise, not approx


def test_fleet_demand_growth_matches_scalar():
    fc = DemandForecaster(ring=8, min_confidence=0.05)
    f = FleetState(capacity=4, forecaster=fc)
    rows = np.array([f.alloc_row(n) for n in ("a", "b", "c")])
    rng = np.random.default_rng(2)
    for w in range(8):
        vals = 1000.0 * (1.5 + np.sin(2 * np.pi * w / 4.0
                                      + np.arange(3))) \
            + rng.normal(0, 10.0, 3)
        f.record_demand(rows, vals)
        for i, n in enumerate(("a", "b", "c")):
            fc.record_window(n, demand_bytes=float(vals[i]))
    growth, conf = f.demand_growth(rows, horizon=1)
    for i, n in enumerate(("a", "b", "c")):
        g, c = fc.demand_growth(n, 1)
        assert growth[i] == g and conf[i] == c


# ---------------------------------------------------------------------------
# the gate's fused window fold
# ---------------------------------------------------------------------------

FOLD_EVERY = 64


def _fold_fleet(n_ready, half_life):
    """A device-sketch fleet with ``n_ready`` tenants holding adopted
    references, two tenants with windows too short to come due, and one
    freed row. No check can refit (threshold above any distance)."""
    pool = PagePool(4 * (n_ready + 3), page_size=PAGE)
    cfg = ControllerConfig(page_size=PAGE, check_every=FOLD_EVERY,
                           device=True, half_life=half_life,
                           drift_threshold=2.0)
    arb = TenantArbiter(pool, controller_config=cfg,
                        arbitrate_every=10**9, fleet=True,
                        fleet_capacity=4)
    names = [f"t{i}" for i in range(n_ready + 3)]
    for name in names:
        arb.register(name, SlabAllocator(CLASSES, page_size=PAGE,
                                         page_pool=pool, tenant=name))
    arb.remove(names[-1])
    ready, idle = names[:n_ready], names[n_ready:-1]
    rng = np.random.default_rng(n_ready)
    first = {n: rng.integers(1, 6000, size=FOLD_EVERY) for n in ready}
    for name in ready:          # first check adopts the reference
        arb.observe(name, first[name])
    arb.tick(1)
    assert all(arb.tenants[n].controller.reference is not None
               for n in ready)
    return arb, ready, idle, rng, first


def _fold_batches(rng, i):
    """Ragged batches of one ready tenant's window: sizes past the grid
    (clamped into the top bucket), size 0 and a negative size (dropped)
    included; odd tenants carry integer weights."""
    batches = [rng.integers(-1, 3 * PAGE, size=int(n))
               for n in rng.integers(1, 50, size=1 + i % 4)]
    batches[0][0] = -5
    batches.append(rng.integers(1, 4000, size=FOLD_EVERY))
    weights = [None if i % 2 == 0 else np.full(len(b), 2.0)
               for b in batches]
    return batches, weights


@pytest.mark.parametrize("half_life", [float("inf"), None],
                         ids=["undecayed", "decayed"])
@pytest.mark.parametrize("n_ready", [2, 7, 33])
def test_gate_folds_every_due_window_in_one_launch(n_ready, half_life):
    """The gate folds every ready tenant's buffered window into its
    fleet row and gates the folded rows in one launch: each row equals
    a solo sketch fed the same batches (bit for bit undecayed, 1e-6
    decayed), rows not due, freed rows and never-used rows keep their
    bits, the drift vector is the distance over the folded rows, and
    no tenant pays a dispatch of its own."""
    import jax.numpy as jnp
    from repro.kernels.fleet_gate import drift_gate_fleet
    arb, ready, idle, rng, first = _fold_fleet(n_ready, half_life)
    f = arb.fleet
    solos = {}
    for i, name in enumerate(ready):
        view = arb.tenants[name].controller.sketch
        solo = DeviceSizeSketch(half_life=view.half_life,
                                num_buckets=view.num_buckets,
                                bucket_width=view.bucket_width,
                                window=True)
        solo.observe_many(first[name])
        solo.flush_window()
        batches, weights = _fold_batches(rng, i)
        for b, w in zip(batches, weights):
            arb.observe(name, b, w)
            solo.observe_many(b, w)
        solo.flush_window()
        solos[name] = solo
    for name in idle:
        arb.observe(name, rng.integers(1, 6000, size=FOLD_EVERY // 2))
    before = np.asarray(f.sketch)
    refs = [arb.tenants[n].controller.reference for n in ready]
    dispatches = {n: arb.tenants[n].controller.sketch.n_dispatches
                  for n in arb.tenants}
    launches, folded = arb.n_gate_launches, arb.n_gate_rows_folded
    with no_implicit_transfers():
        arb.tick(1)
    after = np.asarray(f.sketch)
    assert arb.n_gate_launches == launches + 1
    assert arb.n_gate_rows_folded == folded + n_ready
    assert {n: arb.tenants[n].controller.sketch.n_dispatches
            for n in arb.tenants} == dispatches
    rows = [f.row_of[n] for n in ready]
    for name, row in zip(ready, rows):
        want = np.asarray(solos[name].weights_device)
        if half_life == float("inf"):
            np.testing.assert_array_equal(after[row], want)
        else:
            np.testing.assert_allclose(after[row], want, rtol=1e-6)
    untouched = [r for r in range(f.capacity) if r not in rows]
    assert len(untouched) >= 3           # idle, freed and never-used rows
    np.testing.assert_array_equal(after[untouched], before[untouched])
    for name in idle:                    # their windows stay buffered
        assert arb.tenants[name].controller.sketch._pending
    got = np.array([arb.tenants[n].controller.last_drift for n in ready])
    want = np.asarray(drift_gate_fleet(jnp.stack(refs),
                                       jnp.asarray(after[rows])))
    np.testing.assert_array_equal(got, want.astype(np.float64))


def test_take_window_folds_device_batches_itself():
    """A window holding a device-resident batch is folded by the view's
    own launch, and the gate then folds nothing for that row."""
    import jax.numpy as jnp
    f = FleetState(capacity=2)
    cfg = ControllerConfig(page_size=PAGE, device=True, check_every=50)
    view = f.sketch_view(f.alloc_row("a"), cfg)
    view.observe_many(np.arange(1, 40))
    view.observe_many(jnp.arange(40, 90))
    assert view.take_window() is None
    assert view.n_dispatches == 1 and not view._pending
    assert float(jnp.sum(view.weights_device > 0)) == 89
    view.observe_many(np.arange(1, 10))
    sizes, weights, decay_total = view.take_window()
    np.testing.assert_array_equal(sizes, np.arange(1, 10))
    assert weights.shape == (9,) and 0 < decay_total < 1
    assert view.take_window() is None


def test_streaming_size_sketch_removed():
    with pytest.raises(ImportError, match="DecayedSizeHistogram"):
        from repro.core.observe import StreamingSizeSketch  # noqa: F401


# ---------------------------------------------------------------------------
# hypothesis fuzz layer (runs only where hypothesis is installed)
# ---------------------------------------------------------------------------

if hypothesis is not None:
    @hypothesis.given(n_tenants=st.integers(2, 8),
                      seed=st.integers(0, 10**6),
                      forecast=st.booleans())
    @hypothesis.settings(max_examples=15, deadline=None)
    def test_fuzz_host_parity(n_tenants, seed, forecast):
        legacy, fleet = _twin_run(n_tenants, seed, forecast=forecast,
                                  n_sets=500)
        _assert_twins_equal(legacy, fleet)
