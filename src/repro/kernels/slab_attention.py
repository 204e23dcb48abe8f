"""Pallas TPU kernel: decode attention over a contiguous slab KV pool.

Where the paper's technique meets the serving hot path. With *learned*
slab classes bounding internal fragmentation (repro.serving.kv_slab_pool),
a sequence's whole KV cache can live in ONE contiguous pool range
(start, len) instead of vLLM-style scattered pages. That trade is
TPU-native: contiguous KV streams through VMEM with plain sequential DMA
and zero per-page index indirection (TPU DMA engines strongly prefer
contiguous transfers; gather-style paging is the expensive GPU-ism this
replaces — see DESIGN.md §2). The allocator's fragmentation cost that
contiguity usually implies is exactly what the learned schedule minimizes.

Kernel: flash-decoding over the pool.
  grid = (B, max_tiles); scalar-prefetched (starts_tiles, lens) steer
  each sequence's BlockSpec window into the pool: the k/v block for grid
  step (b, t) is pool tile  starts_tiles[b] + t  (clamped; tiles past
  ceil(len/BLOCK_T) are masked out of the online softmax). One block
  carries every KV head, (BLOCK_T, Hkv, D): its last two dims are the
  pool's own, which is what Mosaic requires of a block whose trailing
  dims are not (8, 128)-divisible — a one-head (BLOCK_T, 1, D) block is
  refused at Hkv=8. A static loop over heads runs the online softmax;
  per-head (m, l, acc) state lives in (Hkv, G, .) VMEM scratch across
  the inner t dimension, and the normalized output is written on the
  last tile.

VMEM per step (BLOCK_T=128, f32; Hkv*D laid out on (8, 128) tiles):
  Hkv=8, D=128: k,v blocks 2*128*8*128*4 = 1 MiB (x2 double-buffered);
  q/acc/m/l < 64 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_T = 128
NEG_INF = -1e30


def _decode_kernel(starts_ref, lens_ref, q_ref, k_ref, v_ref, out_ref,
                   acc_ref, m_ref, l_ref, *, sm_scale: float,
                   max_tiles: int, hkv: int):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    g, block_t = q_ref.shape[2], k_ref.shape[0]
    pos = t * block_t + jax.lax.broadcasted_iota(jnp.int32, (g, block_t), 1)
    live = pos < lens_ref[b]                          # (G, BLOCK_T)
    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32)          # (G, D)
        k = k_ref[:, h, :].astype(jnp.float32)       # (BLOCK_T, D)
        v = v_ref[:, h, :].astype(jnp.float32)       # (BLOCK_T, D)

        # f32 pools are checked against an f32 oracle: keep the MXU from
        # rounding the operands through bf16
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) * sm_scale  # (G, BLOCK_T)
        scores = jnp.where(live, scores, NEG_INF)

        m_prev, l_prev = m_ref[h], l_ref[h]
        m_cur = jnp.max(scores, axis=1, keepdims=True)    # (G, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                       # (G, BLOCK_T)
        p = jnp.where(live, p, 0.0)                       # kill NEG_INF shift
        l_ref[h] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_new
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    @pl.when(t == max_tiles - 1)
    def _finalize():
        for h in range(hkv):
            l_fin = l_ref[h]
            safe = jnp.where(l_fin > 0.0, l_fin, 1.0)  # empty sequence -> 0s
            out_ref[0, h] = (acc_ref[h] / safe).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("max_chunk_tokens", "block_t", "sm_scale",
                              "interpret"))
def slab_decode_attention_pallas(q, k_pool, v_pool, starts, lens, *,
                                 max_chunk_tokens: int,
                                 block_t: int = BLOCK_T,
                                 sm_scale: float | None = None,
                                 interpret: bool = False) -> jnp.ndarray:
    """Decode attention over a contiguous slab KV pool.

    q:        (B, Hq, D);  k_pool/v_pool: (T_pool, Hkv, D)
    starts:   (B,) int32, pool token offset of each sequence's chunk —
              must be multiples of ``block_t`` (the slab allocator aligns
              chunk starts; see kv_slab_pool)
    lens:     (B,) int32 current KV length per sequence
    max_chunk_tokens: static bound = largest slab class (tokens)
    """
    b, hq, d = q.shape
    t_pool, hkv, _ = k_pool.shape
    g = hq // hkv
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    if sm_scale is None:
        sm_scale = float(d) ** -0.5
    max_tiles = -(-max_chunk_tokens // block_t)

    pad_t = (-t_pool) % block_t
    if pad_t:
        k_pool = jnp.pad(k_pool, ((0, pad_t), (0, 0), (0, 0)))
        v_pool = jnp.pad(v_pool, ((0, pad_t), (0, 0), (0, 0)))
    n_tiles = (t_pool + pad_t) // block_t

    q4 = q.reshape(b, hkv, g, d)
    starts_tiles = (starts // block_t).astype(jnp.int32)
    lens = lens.astype(jnp.int32)

    def kv_index(bb, tt, starts_t, lens_t):
        return (jnp.minimum(starts_t[bb] + tt, n_tiles - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_tiles),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d),
                         lambda bb, tt, s, l: (bb, 0, 0, 0)),
            pl.BlockSpec((block_t, hkv, d), kv_index),
            pl.BlockSpec((block_t, hkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d),
                               lambda bb, tt, s, l: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, d), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale,
                          max_tiles=max_tiles, hkv=hkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(starts_tiles, lens, q4, k_pool, v_pool)
    return out.reshape(b, hq, d)
