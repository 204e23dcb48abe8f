"""Observation-layer tests: the host sketch's prune/weight fixes, the
device-resident sketch + Pallas sketch_update kernel (interpret mode on
CPU), the on-device drift metric, and host/device controller parity."""
import numpy as np
import pytest

from repro.analysis.guards import no_implicit_transfers
from repro.core import (ControllerConfig, DecayedSizeHistogram,
                        DeviceSizeSketch, SlabController, SlabPolicy,
                        histogram_distance, histogram_distance_device,
                        schedule_with_default_tail, size_histogram)
from repro.core.distribution import PAPER_WORKLOADS
from repro.memcached import phase_shift_traffic


# -- host sketch regressions -------------------------------------------------

def test_prune_recomputes_total_from_kept_bins():
    """Regression: _prune used to drop bins without subtracting their
    weight from _total, permanently overstating effective_count."""
    h = DecayedSizeHistogram(half_life=50.0, max_bins=32)
    for s in range(1, 200):          # many distinct sizes -> many prunes
        h.observe(s)
    support, weights = h.snapshot_weights()
    assert h.effective_count == pytest.approx(weights.sum(), rel=1e-9)


def test_prune_total_stays_consistent_under_repeated_pressure():
    rng = np.random.default_rng(0)
    h = DecayedSizeHistogram(half_life=200.0, max_bins=64)
    for chunk in np.split(rng.integers(1, 10_000, 4_000), 16):
        h.observe_many(chunk)
        _, weights = h.snapshot_weights()
        assert h.effective_count == pytest.approx(weights.sum(), rel=1e-9)
    # the decayed mass can never exceed the undecayed geometric bound
    decay = 0.5 ** (1.0 / 200.0)
    assert h.effective_count <= 1.0 / (1.0 - decay) + 1e-6


def test_observe_many_weighted_matches_sequential_observe():
    """Regression: observe_many used to silently drop weights."""
    sizes = [10, 20, 10, 30]
    weights = [1.0, 2.5, 0.5, 3.0]
    a = DecayedSizeHistogram(half_life=100.0)
    a.observe_many(sizes, weights)
    b = DecayedSizeHistogram(half_life=100.0)
    for s, w in zip(sizes, weights):
        b.observe(s, w)
    sa, wa = a.snapshot_weights()
    sb, wb = b.snapshot_weights()
    np.testing.assert_array_equal(sa, sb)
    np.testing.assert_allclose(wa, wb, rtol=1e-12)
    assert a.effective_count == pytest.approx(b.effective_count)


def test_observe_many_scalar_weight_broadcasts():
    h = DecayedSizeHistogram()
    h.observe_many([10, 10, 20], 2.0)
    support, freqs = h.snapshot()
    assert support.tolist() == [10, 20]
    assert freqs.tolist() == [4, 2]


# -- device sketch: kernel + parity with the host sketch ---------------------

def test_sketch_update_kernel_matches_oracle():
    from repro.kernels.ops import sketch_update
    from repro.kernels.sketch_update import sketch_update_ref
    rng = np.random.default_rng(3)
    state = rng.random(2000).astype(np.float32)
    idx = rng.integers(0, 2000, 700).astype(np.int32)
    w = rng.random(700).astype(np.float32)
    got = np.asarray(sketch_update(state, idx, w, 0.875, interpret=True))
    want = np.asarray(sketch_update_ref(state, idx, w, 0.875))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_sketch_update_kernel_ignores_padding_ids():
    from repro.kernels.ops import sketch_update
    state = np.zeros(600, dtype=np.float32)
    idx = np.array([5, -1, 5], dtype=np.int32)
    w = np.ones(3, dtype=np.float32)
    out = np.asarray(sketch_update(state, idx, w, 1.0, interpret=True))
    assert out[5] == 2.0 and out.sum() == 2.0


def test_device_sketch_exact_without_decay():
    rng = np.random.default_rng(0)
    sizes = rng.integers(1, 300, 5_000)
    d = DeviceSizeSketch(num_buckets=512)        # no decay, width 1
    d.observe_many(sizes)
    support, freqs = d.snapshot()
    ref_s, ref_f = size_histogram(sizes)
    np.testing.assert_array_equal(support, ref_s)
    np.testing.assert_array_equal(freqs, ref_f)
    assert d.n_observed == 5_000


def test_device_sketch_decay_matches_host_batched():
    rng = np.random.default_rng(1)
    sizes = rng.integers(1, 400, 3_000)
    h = DecayedSizeHistogram(half_life=500.0)
    d = DeviceSizeSketch(half_life=500.0, num_buckets=512)
    for i in range(0, len(sizes), 173):          # ragged batch sizes
        h.observe_many(sizes[i:i + 173])
        d.observe_many(sizes[i:i + 173])
    hs, hw = h.snapshot_weights()
    ds, dw = d.snapshot_weights()
    np.testing.assert_array_equal(hs, ds)
    np.testing.assert_allclose(hw, dw, rtol=2e-5)
    assert d.effective_count == pytest.approx(h.effective_count, rel=1e-4)


def test_device_sketch_weighted_observe():
    h = DecayedSizeHistogram(half_life=100.0)
    d = DeviceSizeSketch(half_life=100.0, num_buckets=64)
    sizes = [10, 20, 10, 30]
    weights = [1.0, 2.5, 0.5, 3.0]
    h.observe_many(sizes, weights)
    d.observe_many(sizes, weights)
    hs, hw = h.snapshot_weights()
    ds, dw = d.snapshot_weights()
    np.testing.assert_array_equal(hs, ds)
    np.testing.assert_allclose(hw, dw, rtol=1e-5)


def test_device_sketch_bucket_width_quantizes_up():
    d = DeviceSizeSketch(num_buckets=32, bucket_width=128)
    d.observe_many([1, 128, 129, 256])
    support, freqs = d.snapshot()
    # 1 -> 128, 128 -> 128, 129 -> 256, 256 -> 256: the representative
    # always covers the item (the direction slab fitting needs)
    assert support.tolist() == [128, 256]
    assert freqs.tolist() == [2, 2]


def test_device_sketch_overflow_clamps_to_top_bucket():
    d = DeviceSizeSketch(num_buckets=16, bucket_width=1)
    d.observe_many([1000, 2000])
    support, freqs = d.snapshot()
    assert support.tolist() == [16]
    assert freqs.tolist() == [2]


def test_device_sketch_negative_dropped_zero_coarsens():
    """The host sketch raises on negatives; raising on device would need
    a readback, so invalid sizes are dropped from the histogram (the
    scatter's ignored pad id). Size 0 — valid on the host — stays
    counted: it coarsens into the first bucket's representative like
    any other in-bucket size."""
    d = DeviceSizeSketch(num_buckets=16, bucket_width=1)
    d.observe_many([-5, 0, 3])
    support, freqs = d.snapshot()
    assert support.tolist() == [1, 3]
    assert freqs.tolist() == [1, 1]


def test_device_sketch_sync_accounting_and_reset():
    d = DeviceSizeSketch(num_buckets=64)
    d.observe_many([1, 2, 3])
    assert d.n_host_syncs == 0                   # observing never syncs
    d.snapshot()
    d.snapshot_weights()
    assert d.n_host_syncs == 2
    d.reset()
    assert d.n_host_syncs == 0 and d.n_observed == 0
    assert d.snapshot()[0].size == 0


def test_device_drift_matches_host_metrics():
    rng = np.random.default_rng(5)
    h1, h2 = DecayedSizeHistogram(), DecayedSizeHistogram()
    d1 = DeviceSizeSketch(num_buckets=512)
    d2 = DeviceSizeSketch(num_buckets=512)
    s1 = rng.integers(1, 500, 2_000)
    s2 = rng.integers(200, 480, 1_500)
    h1.observe_many(s1)
    d1.observe_many(s1)
    h2.observe_many(s2)
    d2.observe_many(s2)
    for metric in ("l1", "emd"):
        host = histogram_distance(h1.snapshot_weights(),
                                  h2.snapshot_weights(), metric=metric)
        dev = float(histogram_distance_device(
            d1.weights_device, d2.weights_device, metric=metric))
        assert dev == pytest.approx(host, abs=1e-5)


def test_device_drift_empty_semantics():
    import jax.numpy as jnp
    z = jnp.zeros(64)
    m = jnp.zeros(64).at[3].set(5.0)
    assert float(histogram_distance_device(z, z)) == 0.0
    assert float(histogram_distance_device(z, m)) == 1.0
    with pytest.raises(ValueError):
        histogram_distance_device(z, m, metric="chi2")


# -- controller device path --------------------------------------------------

def _phase_shift_setup(n: int):
    a, b = PAPER_WORKLOADS[0], PAPER_WORKLOADS[2]
    sizes = phase_shift_traffic(a, b, n_items=n, shift_at=0.5, seed=11)
    support, freqs = size_histogram(sizes[:n // 10])
    fit = SlabPolicy().fit(support, freqs, 6, method="dp")
    return sizes, schedule_with_default_tail(fit.chunk_sizes)


def test_controller_device_path_matches_host_decisions():
    n = 12_000
    sizes, deployed = _phase_shift_setup(n)
    common = dict(k=6, check_every=500, half_life=1000.0,
                  drift_threshold=0.12, min_items_between_refits=2000,
                  amortization_windows=8.0, cost_weight=0.1)
    host = SlabController(deployed, config=ControllerConfig(**common))
    dev = SlabController(deployed, config=ControllerConfig(
        **common, device=True, device_buckets=1 << 12))
    for i in range(0, n, 250):
        host.observe_many(sizes[i:i + 250])
        dev.observe_many(sizes[i:i + 250])
        host.maybe_refit()
        dev.maybe_refit()
    assert host.n_refits == dev.n_refits >= 1
    assert ([(d.approved, d.reason) for d in host.decisions]
            == [(d.approved, d.reason) for d in dev.decisions])
    assert list(host.chunks) == list(dev.chunks)
    # the whole point: the device path materializes the sketch only when
    # a refit is actually evaluated, not at every drift check
    assert dev.sketch.n_host_syncs < host.sketch.n_host_syncs / 4
    assert dev.last_drift == pytest.approx(host.last_drift, abs=1e-4)


def test_controller_device_drift_method():
    ctl = SlabController([64, 256], config=ControllerConfig(
        check_every=4, half_life=float("inf"), device=True,
        device_buckets=64, page_size=4096))
    assert ctl.drift() == 0.0                    # no reference yet
    ctl.observe_many([10, 10, 12, 13])
    assert ctl.maybe_refit() is None             # first check: adopt ref
    ctl.observe_many([50, 50, 50, 50])
    assert 0.0 < ctl.drift() <= 1.0
    assert ctl.sketch.n_host_syncs == 0          # all of that on device


def test_kv_pool_device_observe_batches():
    from repro.serving import KVSlabPool, default_pow2_classes
    pool = KVSlabPool(1 << 20, default_pow2_classes(max_chunk=1 << 13),
                      device_observe=True)
    assert pool.batch_observe and pool.controller.config.device
    assert pool.controller.config.device_bucket_width == pool.align
    # the bucket grid covers every ALLOCATABLE length, not just the
    # initial classes — refits can grow the top class without the
    # sketch silently clamping the traffic that motivates them
    cfg = pool.controller.config
    assert cfg.device_buckets * cfg.device_bucket_width >= pool.pool_tokens
    a = pool.alloc(1, 1000)
    assert a is not None
    assert pool.controller.n_observed == 0       # alloc no longer observes
    pool.observe_lengths(np.asarray([1000, 129, 4096]))
    assert pool.controller.n_observed == 3
    support, freqs = pool.controller.sketch.snapshot()
    assert support.tolist() == [256, 1024, 4096]  # ALIGN-quantized


def test_kv_pool_device_grid_widens_for_huge_pools():
    """When covering the pool at ALIGN resolution would exceed the
    bucket budget, the grid widens (coarser buckets) instead of
    silently clamping allocatable lengths into the top bucket."""
    from repro.serving import KVSlabPool
    pool = KVSlabPool(1 << 19, [256, 512], align=1, device_observe=True)
    cfg = pool.controller.config
    assert cfg.device_buckets <= 1 << 17
    assert cfg.device_bucket_width == 4          # 1 -> 2 -> 4
    assert cfg.device_buckets * cfg.device_bucket_width >= pool.pool_tokens


def test_batcher_batch_observe_includes_rejected_lengths():
    """Parity with the per-alloc path: alloc() observes a length BEFORE
    its failure exits, so batch-observe mode must feed rejected /
    uncoverable lengths too — they are exactly what a refit must learn."""
    from repro.serving import ContinuousBatcher, KVSlabPool, Request
    pool = KVSlabPool(1 << 14, [256, 512], device_observe=True)
    batcher = ContinuousBatcher(pool, max_batch=4, adaptive=False)
    batcher.submit(Request(rid=1, prompt_len=300, output_len=1))
    batcher.submit(Request(rid=2, prompt_len=4000, output_len=1))  # > 512
    batcher.step(0)
    assert batcher.rejected == 1
    assert pool.controller.n_observed == 2       # the reject was observed
    support, _ = pool.controller.sketch.snapshot()
    assert 4096 in support.tolist()              # quantized reject length


# -- property test: device sketch tracks the (fixed) host sketch -------------

try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                              # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    class _PruneRecordingHistogram(DecayedSizeHistogram):
        """Host sketch that remembers every size a prune dropped."""

        def __init__(self, **kw):
            super().__init__(**kw)
            self.pruned = set()

        def _prune(self):
            before = set(self._w)
            super()._prune()
            self.pruned |= before - set(self._w)

    @hypothesis.given(
        seed=st.integers(0, 2**31 - 1),
        half_life=st.one_of(st.none(), st.floats(5.0, 5000.0)),
        max_bins=st.sampled_from([16, 64, 1 << 14]),
        n=st.integers(1, 400),
    )
    @hypothesis.settings(max_examples=40, deadline=None)
    def test_device_sketch_tracks_host_property(seed, half_life, max_bins,
                                                n):
        """For random streams, decays, and prune pressure: every bin the
        host sketch kept is at most the device bucket of the same size,
        and equal to it when the host never pruned that size (a pruned
        size seen again restarts from its new weight, while the device
        sketch, which has no prune, keeps its history). The device total
        never undershoots the host's."""
        rng = np.random.default_rng(seed)
        sizes = rng.integers(1, 512, n)
        h = _PruneRecordingHistogram(half_life=half_life, max_bins=max_bins)
        d = DeviceSizeSketch(half_life=half_life, num_buckets=512)
        for i in range(0, n, 97):
            h.observe_many(sizes[i:i + 97])
            d.observe_many(sizes[i:i + 97])
        host_s, host_w = h.snapshot_weights()
        dense = np.zeros(513)
        dense[np.asarray(d.snapshot_weights()[0])] = d.snapshot_weights()[1]
        for s, w in zip(host_s.tolist(), host_w.tolist()):
            if s in h.pruned:
                assert w <= dense[s] * (1 + 1e-3) + 1e-5
            else:
                assert dense[s] == pytest.approx(w, rel=1e-3, abs=1e-5)
        assert (np.asarray(d.weights_device).sum()
                >= h.effective_count * (1 - 1e-4))


# -- fused observe windows (single-launch cadence) ---------------------------

ENGINES = [
    pytest.param(dict(window_kernel=False), id="jnp-oracle"),
    pytest.param(dict(window_kernel=True, interpret=True),
                 id="pallas-interpret"),
]


def _reference_sketch(rng, engine):
    ref = DeviceSizeSketch(half_life=300.0, num_buckets=256,
                           bucket_width=4, **engine)
    ref.observe_many(rng.integers(1, 900, 300))
    return ref.weights_device


@pytest.mark.parametrize("engine", ENGINES)
def test_observe_window_bitwise_matches_sequential(engine):
    """One fused window over K ragged, weighted batches produces the
    SAME bits as K per-batch launches — sketch and drift scalar alike.

    Batch lengths here share one BLOCK_N pad band (all <= 128), where
    the window stacks rows at exactly the width each per-batch launch
    used — the condition under which the kernel engine is bit-stable
    (see test_window_cross_band_rounding for the cross-band contract)."""
    rng = np.random.default_rng(5)
    batches = [rng.integers(1, 900, n) for n in (64, 1, 33, 100, 128)]
    weights = [rng.uniform(0.25, 3.0, len(b)).astype(np.float32)
               for b in batches]
    reference = _reference_sketch(np.random.default_rng(9), engine)

    seq = DeviceSizeSketch(half_life=300.0, num_buckets=256,
                           bucket_width=4, **engine)
    for b, w in zip(batches, weights):
        seq.observe_many(b, w)
    drift_seq = float(histogram_distance_device(reference,
                                                seq.weights_device))

    win = DeviceSizeSketch(half_life=300.0, num_buckets=256,
                           bucket_width=4, window=True, **engine)
    # the fused launch must not smuggle in implicit device->host syncs
    with no_implicit_transfers():
        drift_win = win.observe_window(batches, weights,
                                       reference=reference)

    assert win.n_dispatches == 1
    assert win.n_observed == seq.n_observed
    np.testing.assert_array_equal(np.asarray(win.weights_device),
                                  np.asarray(seq.weights_device))
    assert float(drift_win) == drift_seq


def test_window_cross_band_rounding():
    """The padding contract across BLOCK_N bands: the jnp oracle stays
    BITWISE identical for arbitrarily ragged windows (scatter-add order
    is index-determined; zero pads are exact no-ops), while the kernel
    engine — whose padded grid shape changes across bands, and XLA does
    not promise identical rounding across different programs — may
    drift by ~1 f32 ulp, far inside every decision threshold."""
    rng = np.random.default_rng(8)
    lens = (64, 1, 33, 200, 300, 513)       # three different pad bands
    batches = [rng.integers(1, 900, n) for n in lens]
    weights = [rng.uniform(0.25, 3.0, n).astype(np.float32) for n in lens]
    for engine, exact in ((dict(window_kernel=False), True),
                          (dict(window_kernel=True, interpret=True),
                           False)):
        seq = DeviceSizeSketch(half_life=300.0, num_buckets=256,
                               bucket_width=4, **engine)
        for b, w in zip(batches, weights):
            seq.observe_many(b, w)
        win = DeviceSizeSketch(half_life=300.0, num_buckets=256,
                               bucket_width=4, window=True, **engine)
        win.observe_window(batches, weights)
        a = np.asarray(seq.weights_device)
        b_ = np.asarray(win.weights_device)
        if exact:
            np.testing.assert_array_equal(a, b_)
        else:
            np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("engine", ENGINES)
def test_window_buffering_is_invisible(engine):
    """window=True buffers observe_many batches (zero launches) and any
    state view flushes them — consumers cannot tell the modes apart."""
    rng = np.random.default_rng(2)
    batches = [rng.integers(1, 250, n) for n in (40, 7, 40)]
    plain = DeviceSizeSketch(half_life=50.0, num_buckets=64, **engine)
    win = DeviceSizeSketch(half_life=50.0, num_buckets=64, window=True,
                           **engine)
    for b in batches:
        plain.observe_many(b)
        win.observe_many(b)
    assert win.n_dispatches == 0          # everything still buffered
    assert win.n_observed == plain.n_observed
    sup_w, frq_w = win.snapshot()         # view -> implicit flush
    sup_p, frq_p = plain.snapshot()
    assert win.n_dispatches == 1
    np.testing.assert_array_equal(sup_w, sup_p)
    np.testing.assert_array_equal(frq_w, frq_p)
    assert win.effective_count == pytest.approx(plain.effective_count)


def test_window_flush_empty_is_noop_and_reset_clears_pending():
    win = DeviceSizeSketch(num_buckets=64, window=True,
                           window_kernel=False)
    assert win.flush_window() is None
    assert win.n_dispatches == 0
    win.observe_many([1, 2, 3])
    win.reset()
    assert win.n_observed == 0 and win.n_dispatches == 0
    assert win.snapshot()[0].size == 0    # pending was dropped, not kept


def test_fused_window_single_dispatch_no_retrace():
    """Dispatch-count regression: every same-shaped cadence window is
    exactly ONE launch of ONE compiled program (no per-window retrace —
    the trace counter in kernels.sketch_update ticks at most once)."""
    from repro.kernels import sketch_update as su
    rng = np.random.default_rng(0)
    win = DeviceSizeSketch(half_life=100.0, num_buckets=256, window=True,
                           window_kernel=False)
    win.observe_window([rng.integers(1, 900, 64) for _ in range(8)])
    traces0 = su.WINDOW_TRACE_COUNT
    with no_implicit_transfers():
        for _ in range(3):
            win.observe_window([rng.integers(1, 900, 64)
                                for _ in range(8)])
    assert win.n_dispatches == 4
    assert su.WINDOW_TRACE_COUNT == traces0      # shapes reuse the jit
    # ragged batch lengths pad to the same compiled shapes too
    win.observe_window([rng.integers(1, 900, n)
                        for n in (63, 64, 1, 17, 60, 64, 2, 9)])
    assert su.WINDOW_TRACE_COUNT == traces0
    assert win.n_dispatches == 5


def test_escaped_reference_survives_later_windows():
    """A weights_device reference handed out (the controller's drift
    reference) must stay valid across later fused launches — donation
    is skipped while a reference is escaped."""
    win = DeviceSizeSketch(num_buckets=64, window=True,
                           window_kernel=False)
    win.observe_many([10, 10, 20])
    ref = win.weights_device
    before = np.asarray(ref).copy()
    win.observe_window([[30, 40, 50]] * 4)
    np.testing.assert_array_equal(np.asarray(ref), before)


def test_controller_fused_window_matches_per_batch_decisions():
    """ControllerConfig.fused_observe must not change a single verdict:
    same decisions, same drifts, same final schedule — with one launch
    and at most one scalar sync per cadence window."""
    n = 12_000
    sizes, deployed = _phase_shift_setup(n)
    common = dict(k=6, check_every=500, half_life=1000.0,
                  drift_threshold=0.12, min_items_between_refits=2000,
                  amortization_windows=8.0, cost_weight=0.1,
                  device=True, device_buckets=1 << 12)
    per_batch = SlabController(deployed, config=ControllerConfig(
        **common, fused_observe=False))
    fused = SlabController(deployed, config=ControllerConfig(**common))
    assert fused.sketch._window and not per_batch.sketch._window
    # the whole drive runs under the transfer sanitizer: the only
    # device->host pulls allowed are the declared deliberate_sync sites
    # (drift gates, refit-search readbacks)
    with no_implicit_transfers():
        for i in range(0, n, 125):      # 4 batches per cadence window
            per_batch.observe_many(sizes[i:i + 125])
            fused.observe_many(sizes[i:i + 125])
            per_batch.maybe_refit()
            fused.maybe_refit()
    assert fused.n_refits == per_batch.n_refits >= 1
    assert ([(d.approved, d.reason, d.drift) for d in fused.decisions]
            == [(d.approved, d.reason, d.drift)
                for d in per_batch.decisions])
    assert list(fused.chunks) == list(per_batch.chunks)
    # the tentpole accounting contract: a cadence window of buffered
    # batches folds in ONE dispatch, the drift gate rides along as a
    # single scalar readback
    assert fused.sketch.n_dispatches <= fused.n_checks
    assert fused.sketch.n_scalar_syncs <= fused.n_checks
    assert fused.sketch.n_dispatches < per_batch.sketch.n_dispatches / 2


if HAVE_HYPOTHESIS:
    @hypothesis.given(
        seed=st.integers(0, 2**31 - 1),
        # one BLOCK_N pad band (<=128): the regime where the kernel
        # engine guarantees bit-identity (test_window_cross_band_rounding
        # covers the ulp-bounded cross-band contract)
        lens=st.lists(st.integers(1, 128), min_size=1, max_size=6),
        half_life=st.one_of(st.none(), st.floats(5.0, 2000.0)),
        weighted=st.booleans(),
    )
    @hypothesis.settings(max_examples=25, deadline=None)
    def test_observe_window_property(seed, lens, half_life, weighted):
        """For random ragged windows, decays, and weights: the fused
        window is bit-identical to sequential launches on BOTH engines,
        and drift comes back identical to the standalone metric."""
        rng = np.random.default_rng(seed)
        batches = [rng.integers(1, 1000, n) for n in lens]
        weights = ([rng.uniform(0.1, 4.0, n).astype(np.float32)
                    for n in lens] if weighted else None)
        ref_sizes = rng.integers(1, 1000, 150)
        for engine in (dict(window_kernel=False),
                       dict(window_kernel=True, interpret=True)):
            ref = DeviceSizeSketch(half_life=half_life, num_buckets=128,
                                   bucket_width=8, **engine)
            ref.observe_many(ref_sizes)
            reference = ref.weights_device
            seq = DeviceSizeSketch(half_life=half_life, num_buckets=128,
                                   bucket_width=8, **engine)
            for i, b in enumerate(batches):
                seq.observe_many(b, None if weights is None
                                 else weights[i])
            drift_seq = float(histogram_distance_device(
                reference, seq.weights_device))
            win = DeviceSizeSketch(half_life=half_life, num_buckets=128,
                                   bucket_width=8, window=True, **engine)
            drift_win = win.observe_window(batches, weights,
                                           reference=reference)
            assert win.n_dispatches == 1
            np.testing.assert_array_equal(
                np.asarray(win.weights_device),
                np.asarray(seq.weights_device))
            assert float(drift_win) == drift_seq
