"""Sharding rules: parameter/activation/cache PartitionSpecs per mesh.

Conventions (GSPMD mesh axes):
  'pod'   — cross-pod axis (multi-pod mesh only): pure data parallel by
            default (the slow DCN hop carries one gradient all-reduce).
  'data'  — intra-pod data parallelism; also hosts ZeRO-sharded optimizer
            moments, MoE expert parallelism, and sequence parallelism for
            long-context decode (B=1 cells).
  'model' — tensor parallelism: attention heads / FFN hidden / vocab.

Rules are applied by leaf path-name matching over the param pytree, so
every family's parameter naming (wq/wk/wv/wo, we_*, in_proj, ...) maps
without per-model code.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (regex on 'path/leafname', spec builder given leaf ndim)
# Specs are written for the UNSTACKED leaf; stacked layer dims (leading
# scan axes) are padded with None automatically by _pad_spec.
_RULES: Tuple[Tuple[str, P], ...] = (
    # embeddings: vocab on model axis
    (r"embedding/embed$", P("model", None)),
    (r"embedding/unembed$", P(None, "model")),
    # attention: head (output) dim on model axis
    (r"attn/wq$", P(None, "model")),
    (r"attn/wk$", P(None, "model")),
    (r"attn/wv$", P(None, "model")),
    (r"attn/wo$", P("model", None)),
    (r"xattn/wq$", P(None, "model")),
    (r"xattn/wk$", P(None, "model")),
    (r"xattn/wv$", P(None, "model")),
    (r"xattn/wo$", P("model", None)),
    # dense mlp: hidden dim on model axis
    (r"mlp/wg$", P(None, "model")),
    (r"mlp/wi$", P(None, "model")),
    (r"mlp/wo$", P("model", None)),
    (r"dense/wg$", P(None, "model")),
    (r"dense/wi$", P(None, "model")),
    (r"dense/wo$", P("model", None)),
    # moe: experts on data axis (EP), expert hidden on model axis (TP)
    (r"moe/we_gate$", P("data", None, "model")),
    (r"moe/we_in$", P("data", None, "model")),
    (r"moe/we_out$", P("data", "model", None)),
    (r"moe/router$", P(None, None)),
    # mamba2: inner channels on model axis
    (r"in_proj$", P(None, "model")),
    (r"out_proj$", P("model", None)),
    (r"conv_w$", P(None, "model")),
    (r"conv_b$", P("model")),
    (r"gate_norm$", P("model")),
    # xlstm
    (r"wgate$", P(None, "model")),
    (r"wog$", P(None, "model")),
    (r"wx$", P(None, "model")),
    (r"out_norm$", P("model")),
    (r"(^|/)r$", P(None, None, "model")),
    (r"mlstm.*/(wq|wk|wv)$", P(None, "model")),
    (r"mlstm.*/wo$", P("model", None)),
)


def _pad_spec(spec: P, ndim: int) -> P:
    """Left-pad a spec with None for stacked (scan) leading dims."""
    parts = tuple(spec)
    if len(parts) > ndim:
        # small leaves (biases/norms stacked): drop leading Nones
        parts = parts[len(parts) - ndim:]
    return P(*([None] * (ndim - len(parts)) + list(parts)))


def _shardable(dim: int, mesh: Mesh, axis: Optional[str]) -> bool:
    if axis is None:
        return True
    return dim % int(np.prod([mesh.shape[a] for a in (
        (axis,) if isinstance(axis, str) else axis)])) == 0


def param_spec(params: Any, mesh: Mesh, *, tp_attention: bool = True
               ) -> Any:
    """PartitionSpec pytree for a parameter pytree (path-rule matched).

    ``tp_attention=False`` replicates attention projections over the
    model axis — the right call for architectures whose head counts
    don't divide the model axis (gemma3's 4 q / 1 kv heads on a 16-way
    axis force XLA into activation all-gathers otherwise; see
    EXPERIMENTS.md §Perf iteration 1).
    """

    def leaf_spec(path, leaf):
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        if not tp_attention and re.search(
                r"(attn|xattn)/(wq|wk|wv|wo)$", name):
            return P()
        for pat, spec in _RULES:
            if re.search(pat, name):
                spec = _pad_spec(spec, leaf.ndim)
                # divisibility guard: replicate any non-divisible dim
                parts = []
                for dim, ax in zip(leaf.shape, tuple(spec)):
                    parts.append(ax if _shardable(dim, mesh, ax) else None)
                return P(*parts)
        return P()  # norms, gates, scalars: replicated

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def param_sharding(params: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_spec(params, mesh),
                        is_leaf=lambda x: isinstance(x, P))


def zero_spec(params: Any, mesh: Mesh, *, axis: str = "data") -> Any:
    """ZeRO-1 sharding for optimizer moments: take the param spec and
    additionally shard the largest replicated dim over the data axis."""
    base = param_spec(params, mesh)

    axis_elems = (axis,) if isinstance(axis, str) else tuple(axis)
    n_ways = int(np.prod([mesh.shape[a] for a in axis_elems]))

    def upgrade(path, leaf, spec):
        parts = list(tuple(_pad_spec(spec, leaf.ndim)))
        if any((p in axis_elems) or (isinstance(p, tuple)
                                     and set(p) & set(axis_elems))
               for p in parts if p is not None):
            return P(*parts)
        # choose the largest dim that is divisible and unsharded
        order = sorted(range(leaf.ndim), key=lambda i: -leaf.shape[i])
        for i in order:
            if parts[i] is None and _shardable(leaf.shape[i], mesh, axis) \
                    and leaf.shape[i] >= n_ways:
                parts[i] = axis
                break
        return P(*parts)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf, spec: upgrade(path, leaf, spec), params, base)


def batch_spec(mesh: Mesh, ndim: int, *, batch_dim: int = 0) -> P:
    """Activations/tokens: batch over ('pod','data') when present."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape.keys())
    batch_axes = axes if len(axes) > 1 else (axes[0] if axes else None)
    parts = [None] * ndim
    parts[batch_dim] = batch_axes
    return P(*parts)


def cache_spec(cache: Any, mesh: Mesh, *, seq_parallel: bool = False,
               seq_axis: Optional[str] = None,
               head_dim_axis: Optional[str] = None) -> Any:
    """KV/state cache sharding.

    Default: shard the batch dim (first dim after stacked layer-group
    dims — detected as the first dim whose size matches none of the
    stack heuristics; here we shard the largest divisible dim among the
    first two non-layer dims). With ``seq_parallel`` (long-context B=1
    decode), shard the sequence dim over 'data' instead.
    """
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape.keys())
    n_data = int(np.prod([mesh.shape[a] for a in data_axes]))
    batch_axes = data_axes if len(data_axes) > 1 else data_axes[0]

    def leaf_spec(path, leaf):
        parts = [None] * leaf.ndim
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        # find batch dim: first dim from the left that divides by n_data
        # skipping stacked layer dims (conventionally small and leading).
        # KV leaves: (L..., B, S, H, D); state leaves: (L..., B, ...)
        kv_like = leaf.ndim >= 3 and re.search(r"(^|/)(k|v|pos)$", name)
        if kv_like:
            b_dim = leaf.ndim - (3 if name.endswith("pos") else 4)
            s_dim = b_dim + 1
            if seq_parallel and leaf.shape[s_dim] % n_data == 0 and \
                    leaf.shape[s_dim] >= n_data:
                parts[s_dim] = batch_axes
            elif leaf.shape[b_dim] % n_data == 0:
                parts[b_dim] = batch_axes
            # shard heads over model if divisible; else optionally shard
            # the sequence dim over the model axis instead (flash-decode
            # partial softmax — the fix for few-KV-head caches that
            # otherwise replicate 16x; EXPERIMENTS.md §Perf cell 3)
            if not name.endswith("pos"):
                h_dim = b_dim + 2
                if _shardable(leaf.shape[h_dim], mesh, "model") and \
                        leaf.shape[h_dim] >= mesh.shape["model"]:
                    parts[h_dim] = "model"
                elif head_dim_axis and _shardable(
                        leaf.shape[h_dim + 1], mesh, head_dim_axis):
                    # few-KV-head caches: shard head_dim instead — the
                    # decode write stays local (seq unsharded) and the
                    # QK/AV contractions only all-reduce tiny scores
                    parts[h_dim + 1] = head_dim_axis
                elif seq_axis and parts[s_dim] is None and \
                        _shardable(leaf.shape[s_dim], mesh, seq_axis):
                    parts[s_dim] = seq_axis
            elif seq_axis and parts[s_dim] is None and \
                    _shardable(leaf.shape[s_dim], mesh, seq_axis):
                parts[s_dim] = seq_axis
        else:
            # recurrent states: shard batch if possible (search dims)
            for i in range(leaf.ndim):
                if leaf.shape[i] % n_data == 0 and leaf.shape[i] >= n_data:
                    parts[i] = batch_axes
                    break
        return P(*parts)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)


def to_shardings(tree_spec: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_spec,
                        is_leaf=lambda x: isinstance(x, P))
