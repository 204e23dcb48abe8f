"""Fleet-batched drift gate — one launch for every due tenant.

The legacy arbiter runs each due tenant's drift gate as its own device
launch (fused into that tenant's observe-window flush). At fleet scale
that is O(due tenants) dispatches per tick; this module collapses the
whole gate stage into ONE jitted launch over the stacked
``[capacity, num_buckets]`` fleet sketch, followed by a single vector
readback — the per-stage dispatch accounting
``TenantArbiter(fleet=True)`` reports as ``n_gate_launches``.

:func:`fold_gate_fleet` is that launch. It folds every due tenant's
buffered observe window (handed over by ``FleetSketchView.take_window``
and packed by :func:`pack_fleet_windows`) into the tenant's row by
scatter-add, gathers the folded rows, and computes their distances
to the tenants' references, so the window fold and the distance share
the gate's one launch. Before the fold moved here, each due tenant
also paid its own window-flush dispatch, plus an eager write of its row
into the stacked matrix, before the batched distance ran.

:func:`drift_gate_fleet` is the distance alone over two stacks; the
per-row math of both is :func:`repro.core.observe._dense_distance` —
the exact traced ops the solo gate (``histogram_distance_device`` and
the fused observe-window flush) runs — so a fleet row computes the
same distance the tenant would have computed alone, up to vmap's
reduction framing (float32 sums may differ in the last ulp; the
bit-identical differential contract is carried by the host-sketch path,
and the device path is held to decision-level parity in
``tests/test_fleet.py``).
"""
from __future__ import annotations

import functools

import numpy as np

_GATE_CACHE = {}
_FOLD_CACHE = {}

# Padding floors of the fused fold: items and due rows round up to powers
# of two no smaller than these, so a handful of programs covers rounds
# of a few to about a thousand due tenants. Items are scattered
# MIN_FOLD_ITEMS at a time (the chip's compile time grows with the
# updates of one scatter), and references are stacked MIN_FOLD_ROWS at a
# time (and with the operands of one program).
MIN_FOLD_ITEMS = 4096
MIN_FOLD_ROWS = 64


def _build_gate(metric: str):
    import jax

    from repro.core.observe import _dense_distance

    @jax.jit
    def gate(refs, sketches):
        return jax.vmap(lambda a, b: _dense_distance(a, b, metric))(
            refs, sketches)

    return gate


def drift_gate_fleet(refs, sketches, *, metric: str = "l1"):
    """Drift distance per fleet row, in one jitted launch.

    ``refs`` and ``sketches`` are ``[n, num_buckets]`` stacks of dense
    per-bucket weight vectors (reference vs live, same grid). Returns a
    ``[n]`` device vector of distances in [0, 1]; the caller reads it
    back in one host sync for the whole fleet.
    """
    if metric not in ("l1", "emd"):
        raise ValueError(f"unknown metric {metric!r}")
    fn = _GATE_CACHE.get(metric)
    if fn is None:
        fn = _GATE_CACHE[metric] = _build_gate(metric)
    import jax.numpy as jnp
    refs = jnp.asarray(refs)
    sketches = jnp.asarray(sketches)
    if refs.ndim != 2 or refs.shape != sketches.shape:
        raise ValueError(
            f"need matching [n, buckets] stacks, got {refs.shape} "
            f"vs {sketches.shape}")
    return fn(refs, sketches)


def _build_fold(metric: str, bucket_width: int, donate: bool):
    import jax
    import jax.numpy as jnp

    from repro.core.observe import _dense_distance
    from repro.kernels.sketch_update import _bucketize

    def fold(sketch, sizes, item_rows, weights, due, decay_totals, refs):
        capacity, num_buckets = sketch.shape

        def add(state, chunk):
            s, r, w = chunk
            idx = _bucketize(s, bucket_width, num_buckets)
            # negative sizes (bucket -1) and padding items (row
            # capacity) fall outside the matrix; the scatter drops them
            r = jnp.where(idx < 0, capacity, r)
            return state.at[r, idx].add(w, mode="drop"), None

        factor = jnp.ones(capacity, sketch.dtype).at[due].set(
            decay_totals, mode="drop")
        chunks = tuple(a.reshape(-1, MIN_FOLD_ITEMS)
                       for a in (sizes, item_rows, weights))
        new, _ = jax.lax.scan(add, sketch * factor[:, None], chunks)
        live = new.at[due].get(mode="fill", fill_value=0.0)
        drift = jax.vmap(lambda a, b: _dense_distance(a, b, metric))(
            jnp.concatenate(refs), live)
        return new, drift

    return jax.jit(fold, donate_argnums=(0,) if donate else ())


@functools.cache
def _stack_rows():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda *rows: jnp.stack(rows))


def _stack_blocks(refs):
    """``[MIN_FOLD_ROWS, num_buckets]`` stacks of ``refs``, one launch
    each."""
    return tuple(_stack_rows()(*refs[i:i + MIN_FOLD_ROWS])
                 for i in range(0, len(refs), MIN_FOLD_ROWS))


def pack_fleet_windows(rows, windows, refs, *, capacity: int):
    """Host operands of :func:`fold_gate_fleet` for the due ``rows``.

    ``windows[i]`` is row i's ``(sizes, weights, decay_total)`` from
    ``FleetSketchView.take_window`` (or None: nothing buffered), and
    ``refs[i]`` its reference, a ``[num_buckets]`` device vector. Items
    pad to a power of two of at least :data:`MIN_FOLD_ITEMS` with row
    ``capacity``; due rows to one of at least :data:`MIN_FOLD_ROWS` with
    row ``capacity`` and a zero reference. Both are dropped on device.
    The references go as blocks of :data:`MIN_FOLD_ROWS` stacked rows.
    """
    import jax.numpy as jnp
    n = len(rows)
    n_pad = max(MIN_FOLD_ROWS, 1 << (n - 1).bit_length())
    due = np.full(n_pad, capacity, dtype=np.int32)
    due[:n] = rows
    decay_totals = np.ones(n_pad, dtype=np.float32)
    decay_totals[:n] = [1.0 if w is None else w[2] for w in windows]
    live = [(r, w) for r, w in zip(rows, windows) if w is not None]
    m = sum(len(w[0]) for _, w in live)
    m_pad = max(MIN_FOLD_ITEMS, 1 << (m - 1).bit_length())
    sizes = np.zeros(m_pad, dtype=np.int32)
    item_rows = np.full(m_pad, capacity, dtype=np.int32)
    weights = np.zeros(m_pad, dtype=np.float32)
    if live:
        sizes[:m] = np.concatenate([w[0] for _, w in live])
        weights[:m] = np.concatenate([w[1] for _, w in live])
        item_rows[:m] = np.repeat([r for r, _ in live],
                                  [len(w[0]) for _, w in live])
    zero = jnp.zeros_like(refs[0])
    refs = _stack_blocks(tuple(refs) + (zero,) * (n_pad - n))
    return sizes, item_rows, weights, due, decay_totals, refs


def fold_gate_fleet(sketch, sizes, item_rows, weights, due, decay_totals,
                    refs, *, metric: str = "l1", bucket_width: int = 1,
                    donate: bool = False):
    """Fold observe windows into the stacked sketch and gate its rows,
    in one jitted launch.

    ``sketch`` is the ``[capacity, num_buckets]`` fleet matrix (donated
    when ``donate``); the other operands come from
    :func:`pack_fleet_windows`. Each due row decays once by its
    ``decay_totals`` entry (other rows are multiplied by exactly 1),
    then item i adds ``weights[i]`` to bucket
    ``ceil(sizes[i] / bucket_width) - 1`` of row ``item_rows[i]`` — the
    bucketing of ``DeviceSizeSketch.bucket_of``. Returns the new matrix
    and a ``[len(due)]`` device vector of distances of the folded rows
    to ``refs``; entries of padding rows are meaningless. Undecayed
    sketches hold integer counts, so their rows equal the per-tenant
    flush bit for bit whatever the scatter order.
    """
    if metric not in ("l1", "emd"):
        raise ValueError(f"unknown metric {metric!r}")
    key = (metric, int(bucket_width), bool(donate))
    fn = _FOLD_CACHE.get(key)
    if fn is None:
        fn = _FOLD_CACHE[key] = _build_fold(*key)
    return fn(sketch, sizes, item_rows, weights, due, decay_totals,
              tuple(refs))
