"""Plain reference for the serving cells: decode attention of the
hash-content toy model with its output head, written from its
definition in straightforward ``jax.numpy`` at float32 and ``highest``
matmul precision. It imports nothing of the program.

The model: the key and value rows at position ``p`` of request ``rid``
carrying token ``tok`` are elementwise integer hashes of
``(rid, p, tok, head, dim)`` mapped to [-1, 1); prompt positions carry
token ``p % vocab``. Decode step ``j`` (0-based) queries every head with
the hash of ``(rid, prompt_len + j, head, dim)`` over rows
``0 .. prompt_len + j - 1`` and softmax-attends with scale
``head_dim ** -0.5``. The output head projects the whole
``(heads, head_dim)`` attention output onto ``vocab`` logits with the
fixed hashed matrix of :func:`head_weights`; the next token is their
argmax, and its K and V rows go to position ``prompt_len + j``.

Two comparisons read this model:

* the logits, teacher-forced over each prompt and the tokens the program
  served, once per request: the number compared is the widest gap by
  which a served token's logit lies below the reference's best;
* the K and V rows a request holds in the pool, every head and every
  dim, which are exact (hashes and one exact float conversion).
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import numpy as np

SALT_K, SALT_V, SALT_Q, SALT_HEAD = 0x9E37, 0x85EB, 0xC2B2, 0x51ED
M1, M2, M3 = 2654435761, 40503, 69069


def _mix(rid, pos, token, salt: int, hkv: int, d: int):
    """(..., hkv, d) uint32 hash of (rid, pos, token, head, dim)."""
    import jax.numpy as jnp
    u = jnp.uint32
    rid = jnp.asarray(rid).astype(u)
    pos = jnp.asarray(pos).astype(u)
    token = jnp.asarray(token).astype(u)
    head = jnp.arange(hkv, dtype=u)[:, None]
    dd = jnp.arange(d, dtype=u)[None, :]
    x = (rid[..., None, None] * u(M1) + pos[..., None, None] * u(M2)
         + token[..., None, None] * u(M3) + head * u(97) + dd * u(131)
         + u(salt))
    x = x ^ (x >> 15)
    x = x * u(2246822519)
    x = x ^ (x >> 13)
    return x


def _unit(x):
    import jax.numpy as jnp
    return (x & jnp.uint32(0xFFFF)).astype(jnp.float32) / 32768.0 - 1.0


def head_weights(features: int, vocab: int) -> np.ndarray:
    """The output head: a fixed ``(features, vocab)`` float32 matrix of
    hashes of (feature, logit) in [-1, 1), scaled by ``features ** -0.5``."""
    f = np.arange(features, dtype=np.uint64)[:, None]
    v = np.arange(vocab, dtype=np.uint64)[None, :]
    x = (f * M1 + v * M2 + SALT_HEAD) & 0xFFFFFFFF
    x ^= x >> 15
    x = (x * 2246822519) & 0xFFFFFFFF
    x ^= x >> 13
    w = (x & 0xFFFF).astype(np.float32) / np.float32(32768.0) - np.float32(1)
    return (w * np.float32(features ** -0.5)).astype(np.float32)


def _rows(rid, prompt_len, tokens, rows: int, steps: int, hkv: int, d: int,
          vocab: int):
    """K and V rows ``0 .. rows-1`` of one request whose served tokens
    are ``tokens`` (padded to ``steps``)."""
    import jax.numpy as jnp
    pos = jnp.arange(rows, dtype=jnp.int32)
    tok_idx = jnp.clip(pos - prompt_len, 0, steps - 1)
    tok = jnp.where(pos < prompt_len, pos % vocab, tokens[tok_idx])
    return (_unit(_mix(rid, pos, tok, SALT_K, hkv, d)),
            _unit(_mix(rid, pos, tok, SALT_V, hkv, d)))


@functools.lru_cache(maxsize=None)
def _logits_fn(rows: int, steps: int, hkv: int, d: int, vocab: int,
               low: bool):
    """Jitted logits of one request, padded to ``rows`` KV rows and
    ``steps`` decode steps so that every request shares one program.
    ``low`` computes in bfloat16 at default precision (the control)."""
    import jax
    import jax.numpy as jnp
    w = jnp.asarray(head_weights(hkv * d, vocab))

    def run(rid, prompt_len, n, tokens):
        k, v = _rows(rid, prompt_len, tokens, rows, steps, hkv, d, vocab)
        pos = jnp.arange(rows, dtype=jnp.int32)
        qpos = prompt_len + jnp.arange(steps, dtype=jnp.int32)
        q = _unit(_mix(rid, qpos, 0, SALT_Q, hkv, d))
        head = w
        if low:
            q, k, v, head = (a.astype(jnp.bfloat16) for a in (q, k, v, w))
            prec = jax.lax.Precision.DEFAULT
        else:
            prec = jax.lax.Precision.HIGHEST
        s = jnp.einsum("shd,thd->hst", q, k, precision=prec,
                       preferred_element_type=jnp.float32) * (d ** -0.5)
        live = pos[None, None, :] < qpos[None, :, None]
        s = jnp.where(live, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if low:
            p = p.astype(jnp.bfloat16)
        out = jnp.einsum("hst,thd->shd", p, v, precision=prec,
                         preferred_element_type=jnp.float32)
        if low:
            out = out.astype(jnp.bfloat16)
        logits = jnp.einsum("sf,fv->sv", out.reshape(steps, hkv * d), head,
                            precision=prec,
                            preferred_element_type=jnp.float32)
        return logits

    return jax.jit(run)


def request_logits(rid: int, prompt_len: int, tokens: Sequence[int], *,
                   hkv: int, d: int, vocab: int, rows: int, steps: int,
                   low: bool = False) -> np.ndarray:
    """(len(tokens), vocab) logits of each decode step of one request."""
    import jax.numpy as jnp
    n = len(tokens)
    if prompt_len + n - 1 > rows or n > steps:
        raise ValueError(f"request of {prompt_len} + {n} exceeds the "
                         f"padded shape ({rows} rows, {steps} steps)")
    fn = _logits_fn(rows, steps, hkv, d, vocab, low)
    logits = fn(jnp.int32(rid), jnp.int32(prompt_len), jnp.int32(n),
                jnp.asarray(pad_tokens(tokens, steps)))
    return np.asarray(logits)[:n]


def pad_tokens(tokens: Sequence[int], steps: int) -> np.ndarray:
    padded = np.zeros(steps, np.int32)
    padded[:len(tokens)] = tokens
    return padded


@functools.lru_cache(maxsize=None)
def kv_rows_fn(rows: int, steps: int, hkv: int, d: int, vocab: int):
    """Jitted ``(rid, prompt_len, tokens) -> (K, V)`` of one request's
    first ``rows`` rows, ``(rows, hkv, d)`` each."""
    import jax

    def run(rid, prompt_len, tokens):
        return _rows(rid, prompt_len, tokens, rows, steps, hkv, d, vocab)

    return jax.jit(run)


def widest_gap(served: List[Tuple[int, int, Sequence[int]]], *, hkv: int,
               d: int, vocab: int, rows: int, steps: int) -> float:
    """Widest gap, over every served token of every request, between
    the reference's best logit and the served token's logit."""
    worst = 0.0
    for rid, plen, toks in served:
        lg = request_logits(rid, plen, toks, hkv=hkv, d=d, vocab=vocab,
                            rows=rows, steps=steps)
        got = lg[np.arange(len(toks)), np.asarray(toks)]
        worst = max(worst, float(np.max(lg.max(axis=1) - got)))
    return worst


def control_gap(served: List[Tuple[int, int, Sequence[int]]], *, hkv: int,
                d: int, vocab: int, rows: int, steps: int) -> float:
    """The control: at each position of the same prompts and served
    tokens, the token that bfloat16 puts first, and the widest gap of
    that token below the float32 reference's best."""
    worst = 0.0
    for rid, plen, toks in served:
        shape = dict(hkv=hkv, d=d, vocab=vocab, rows=rows, steps=steps)
        ref = request_logits(rid, plen, toks, **shape)
        low = request_logits(rid, plen, toks, low=True, **shape)
        got = ref[np.arange(len(toks)), low.argmax(axis=1)]
        worst = max(worst, float(np.max(ref.max(axis=1) - got)))
    return worst
