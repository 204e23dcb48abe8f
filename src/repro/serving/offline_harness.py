"""MLPerf-offline-style serving harness over the slab KV pool: batched
prefill/decode with ONE jitted dispatch per decode tick.

The last serving-path gap (ROADMAP "serve a real inference trace
end-to-end"): ``ContinuousBatcher`` proved the allocator's *decisions*
at the serving layer but decoded with a per-request host loop —
O(requests) dispatches per tick, exactly the engine-level bottleneck
that hides allocator wins. This harness runs the same open-loop request
stream (arrival timestamps, mixed prompt/output lengths, tenant tags —
synthetic or replayed through ``scenarios.trace.trace_requests``)
against the real device path:

* decode tick = ONE jitted call for the whole active batch: pending
  class-overflow chunk moves execute as a batched
  ``kv_chunk_copy_pallas`` scatter, ``slab_decode_attention_pallas``
  reads every sequence's KV straight out of the stacked slab-pool
  pages, and the new tokens' KV rows land via ``kv_append_pallas`` —
  carry buffers donated between ticks (off-CPU), O(ticks) dispatches
  (off-TPU the same step composes the kernels' jnp oracles instead:
  interpret-mode Pallas serializes the grid — see ``impl=``);
* prefill is batched per tick the same way (one call writes every
  newly admitted prompt's KV);
* admission runs at tick granularity through the forecast-driven
  token-quota arbiter when one is attached
  (``TenantArbiter.admission``), with the pool's own quota check as
  the enforcement backstop.

Parity contract (CI-gated in ``benchmarks/serving_bench.py --quick``):
``mode="legacy"`` executes the identical host bookkeeping but issues
one jitted call per request — and because every kernel computes each
sequence on fixed per-sequence block shapes, the generated tokens and
every admission/rejection/realloc decision are BIT-identical between
the two modes. The toy model is deterministic by construction: KV/Q
content are elementwise integer hashes of (request id, position,
token) — bit-exact under any compilation, no cross-batch matmuls —
and the next token is an argmax over a slice of the attention output,
so parity is exact, not approximate.

Junk-range contract: the device pools are padded ``max_chunk_tokens``
past ``pool_tokens`` so the scatter kernels' reserved tail range (see
``kernels/kv_scatter``) can never alias a real allocation.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import hot_path
from repro.analysis.spans import span
from repro.kernels.kv_scatter import (kv_append_pallas, kv_append_ref,
                                      kv_chunk_copy_pallas,
                                      kv_chunk_copy_ref)
from repro.kernels.ref import slab_decode_attention_window_ref
from repro.kernels.slab_attention import slab_decode_attention_pallas
from repro.serving.kv_slab_pool import ALIGN, KVSlabPool
from repro.serving.scheduler import Request, queue_delay_stats


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _default_impl() -> str:
    # The Pallas kernels only parallelize their grid on a real TPU; in
    # interpret mode the grid runs serially, so a B=64 call costs the
    # same wall time as 64 B=1 calls and batching could never show its
    # dispatch-amortization win. Off-TPU the step functions therefore
    # compose the kernels' jnp oracles (same masked-softmax / scatter
    # semantics, batch-vectorized by XLA; kernel == oracle is CI-gated
    # in tests/test_kernels.py).
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# -- deterministic toy model ---------------------------------------------------
# Content functions are INTEGER-hash based, not transcendental: uint32
# mixing wraps identically under every compilation, and the only float
# ops are a single convert + multiply-add per element (IEEE-exact). XLA
# compiles sin/cos with shape-dependent vectorization (a B=1 program
# and a B=64 program disagree in the last few ulps), which would break
# the batched-vs-legacy bit-parity contract; hashes cannot.

def _mix(rid, pos, token, salt: int, hkv: int, d: int) -> jnp.ndarray:
    """(..., hkv, d) uint32 hash of (request id, position, token)."""
    rid = jnp.asarray(rid).astype(jnp.uint32)
    pos = jnp.asarray(pos).astype(jnp.uint32)
    token = jnp.asarray(token).astype(jnp.uint32)
    h = jnp.arange(hkv, dtype=jnp.uint32)
    dd = jnp.arange(d, dtype=jnp.uint32)
    x = (rid[..., None, None] * jnp.uint32(2654435761)
         + pos[..., None, None] * jnp.uint32(40503)
         + token[..., None, None] * jnp.uint32(69069)
         + h[:, None] * jnp.uint32(97) + dd[None, :] * jnp.uint32(131)
         + jnp.uint32(salt))
    x = x ^ (x >> 15)
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    return x


def _to_unit(x: jnp.ndarray) -> jnp.ndarray:
    """uint32 hash -> float32 in [-1, 1), one convert + one fma."""
    return ((x & jnp.uint32(0xFFFF)).astype(jnp.float32) / 32768.0 - 1.0)


def _kv_content(rid, pos, token, hkv: int, d: int):
    return (_to_unit(_mix(rid, pos, token, 0x9E37, hkv, d)),
            _to_unit(_mix(rid, pos, token, 0x85EB, hkv, d)))


def _q_content(rid, pos, hkv: int, d: int) -> jnp.ndarray:
    return _to_unit(_mix(rid, pos, 0, 0xC2B2, hkv, d))


# -- jitted step factories -----------------------------------------------------
# One compiled fn per (static config, donate) pair; donation follows the
# repo's conditional pattern (core/observe.py): enabled off-CPU, where
# jit donation is actually supported, disabled on CPU to avoid
# per-launch donation warnings (guards escalate those to errors).

_STEP_CACHE: Dict[tuple, Callable] = {}


def _decode_step_fn(max_chunk: int, vocab: int, interpret: bool,
                    donate: bool, impl: str) -> Callable:
    key = ("decode", max_chunk, vocab, interpret, donate, impl)
    fn = _STEP_CACHE.get(key)
    if fn is not None:
        return fn
    kernels = impl == "pallas"

    def copy(pool, src, dst, tok):
        if kernels:
            return kv_chunk_copy_pallas(pool, src, dst, tok,
                                        max_copy_tokens=max_chunk,
                                        interpret=interpret)
        return kv_chunk_copy_ref(pool, src, dst, tok,
                                 max_copy_tokens=max_chunk)

    def attend(q, k_pool, v_pool, starts, alens):
        if kernels:
            return slab_decode_attention_pallas(
                q, k_pool, v_pool, starts, alens,
                max_chunk_tokens=max_chunk, interpret=interpret)
        return slab_decode_attention_window_ref(
            q, k_pool, v_pool, starts, alens,
            max_chunk_tokens=max_chunk)

    def append(pool, rows, vals):
        if kernels:
            return kv_append_pallas(pool, rows, vals, interpret=interpret)
        return kv_append_ref(pool, rows, vals)

    def run(k_pool, v_pool, starts, lens, rids, active,
            mv_src, mv_dst, mv_tok):
        hkv, d = k_pool.shape[1], k_pool.shape[2]
        # 1) pending class-overflow chunk moves (array order = the
        #    allocator's processing order; WAR-safe, see kv_scatter)
        k_pool = copy(k_pool, mv_src, mv_dst, mv_tok)
        v_pool = copy(v_pool, mv_src, mv_dst, mv_tok)
        # 2) flash-decode over the pool for the whole batch
        q = _q_content(rids, lens, hkv, d)
        alens = jnp.where(active > 0, lens, 0).astype(jnp.int32)
        out = attend(q, k_pool, v_pool, starts.astype(jnp.int32), alens)
        # 3) next token: argmax over a slice of the attention output —
        #    per-row, no cross-batch mixing, ties break low
        tokens = jnp.argmax(out[:, 0, :vocab], axis=-1).astype(jnp.int32)
        tokens = jnp.where(active > 0, tokens, -1)
        # 4) append the new token's KV row at position lens
        kc, vc = _kv_content(rids, lens, jnp.maximum(tokens, 0), hkv, d)
        rows = jnp.where(active > 0, starts + lens, -1).astype(jnp.int32)
        k_pool = append(k_pool, rows, kc)
        v_pool = append(v_pool, rows, vc)
        return k_pool, v_pool, tokens

    fn = jax.jit(run, donate_argnums=(0, 1) if donate else ())
    _STEP_CACHE[key] = fn
    return fn


def _prefill_step_fn(max_chunk: int, vocab: int, donate: bool) -> Callable:
    key = ("prefill", max_chunk, vocab, donate)
    fn = _STEP_CACHE.get(key)
    if fn is not None:
        return fn

    def run(k_pool, v_pool, starts, plens, rids):
        t, hkv, d = k_pool.shape
        pos = jnp.arange(max_chunk, dtype=jnp.int32)

        def body(i, kv):
            k, v = kv
            rid_vec = jnp.full((max_chunk,), rids[i], jnp.float32)
            kc, vc = _kv_content(rid_vec, pos, pos % vocab, hkv, d)
            mask = (pos < plens[i])[:, None, None]
            base = (starts[i], 0, 0)
            curk = jax.lax.dynamic_slice(k, base, (max_chunk, hkv, d))
            curv = jax.lax.dynamic_slice(v, base, (max_chunk, hkv, d))
            k = jax.lax.dynamic_update_slice(
                k, jnp.where(mask, kc, curk), base)
            v = jax.lax.dynamic_update_slice(
                v, jnp.where(mask, vc, curv), base)
            return k, v

        return jax.lax.fori_loop(0, starts.shape[0], body, (k_pool, v_pool))

    fn = jax.jit(run, donate_argnums=(0, 1) if donate else ())
    _STEP_CACHE[key] = fn
    return fn


@dataclasses.dataclass
class HarnessResult:
    """One offline run's ledger. ``tokens`` maps request id → generated
    token ids (the parity surface: batched vs legacy must match
    bit-for-bit); dispatch counters are the O(ticks) contract."""
    ticks: int
    completed: int
    rejected: int
    realloc_copies: int
    realloc_tokens: int
    generated_tokens: int
    n_decode_dispatches: int
    n_prefill_dispatches: int
    queue_delay_mean: float
    queue_delay_p50: float
    queue_delay_p99: float
    mean_waste_fraction: float
    peak_active: int
    mean_active: float
    n_refits: int
    n_admission_denials: int
    tokens: Dict[int, List[int]]

    def decisions(self) -> tuple:
        """The admission/progress decision fingerprint two runs must
        share to count as identical (tokens compared separately)."""
        return (self.ticks, self.completed, self.rejected,
                self.realloc_copies, self.realloc_tokens,
                self.n_refits, self.n_admission_denials)


class OfflineHarness:
    """Open-loop offline serving over a :class:`KVSlabPool`.

    ``mode="batched"`` — one jitted decode dispatch per tick for the
    whole active batch (and one prefill dispatch per tick with
    admissions). ``mode="legacy"`` — identical host bookkeeping, one
    dispatch per request: the bit-parity oracle the bench gates on.

    ``impl`` picks the device math inside the step functions:
    ``"pallas"`` (the TPU kernels; default on TPU) or ``"ref"`` (the
    kernels' batch-vectorized jnp oracles; default elsewhere, where
    interpret-mode Pallas would serialize the grid and erase the
    batching win — see :func:`_default_impl`). Both modes of one
    harness config share one step function, so the parity contract is
    per-impl.

    The harness owns stacked device pools shaped
    ``(pool_tokens_padded, hkv, d)``; ``pool`` supplies allocation
    decisions only. Chunk classes may refit DOWN or re-partition freely
    mid-run (``adaptive=True``), but growing the top class past the
    harness's compiled ``max_chunk_tokens`` ceiling raises — the static
    shapes baked into the step functions cannot stretch.

    Admission: FIFO over arrivals; with an ``arbiter``, each candidate
    first passes ``TenantArbiter.admission`` (tick-granular gate,
    denials recorded as tenant pressure), then the pool's own
    quota/capacity check. Gate or alloc failure rejects (drops) the
    request — the ContinuousBatcher contract.
    """

    def __init__(self, pool: KVSlabPool, *, max_batch: int = 64,
                 mode: str = "batched", hkv: int = 1, d: int = 16,
                 vocab: int = 16, max_chunk_tokens: Optional[int] = None,
                 adaptive: bool = False, arbiter=None,
                 impl: Optional[str] = None,
                 interpret: Optional[bool] = None):
        if mode not in ("batched", "legacy"):
            raise ValueError(f"unknown mode {mode!r}")
        if vocab > d:
            raise ValueError(f"vocab {vocab} > head dim {d}")
        self.impl = _default_impl() if impl is None else impl
        if self.impl not in ("pallas", "ref"):
            raise ValueError(f"unknown impl {self.impl!r}")
        self.pool = pool
        self.mode = mode
        self.max_batch = int(max_batch)
        self.adaptive = adaptive
        self.arbiter = arbiter
        self.max_chunk = int(max_chunk_tokens or pool.max_chunk_tokens)
        if self.max_chunk % ALIGN:
            raise ValueError(f"max_chunk_tokens must be a multiple "
                             f"of {ALIGN}")
        self._interpret = (_default_interpret() if interpret is None
                           else bool(interpret))
        self._donate = jax.default_backend() != "cpu"
        # device pools: pad past pool_tokens so the scatter kernels'
        # reserved tail range is never a real allocation (junk-range
        # contract), and keep rows a multiple of ALIGN for tile copies
        t_pad = -(-pool.pool_tokens // ALIGN) * ALIGN + self.max_chunk
        self._k = jnp.zeros((t_pad, hkv, d), jnp.float32)
        self._v = jnp.zeros((t_pad, hkv, d), jnp.float32)
        self._decode = _decode_step_fn(self.max_chunk, vocab,
                                       self._interpret, self._donate,
                                       self.impl)
        self._prefill = _prefill_step_fn(self.max_chunk, vocab,
                                         self._donate)
        # fixed-size slot state (RT001: one traced shape per run)
        self._starts = np.zeros(self.max_batch, np.int32)
        self._lens = np.zeros(self.max_batch, np.int32)
        self._rids = np.zeros(self.max_batch, np.int32)
        self._act = np.zeros(self.max_batch, np.int32)
        self._free_slots = list(range(self.max_batch - 1, -1, -1))
        self._slot_of: Dict[int, int] = {}
        self._queue: Deque[Request] = deque()
        self._active: Dict[int, Request] = {}
        # ledger
        self.completed = 0
        self.rejected = 0
        self.realloc_copies = 0
        self.realloc_tokens = 0
        self.n_refits = 0
        self.n_decode_dispatches = 0
        self.n_prefill_dispatches = 0
        self.queue_delays: List[float] = []
        # (slot→rid snapshot, device tokens) per decode dispatch; synced
        # to host ONCE in result()
        self._token_log: List[Tuple[Tuple[Optional[int], ...],
                                    jnp.ndarray]] = []

    def submit(self, req: Request) -> None:
        if req.tenant not in self.pool._tenants:
            self.pool.register_tenant(req.tenant)
        self._queue.append(req)

    # -- host bookkeeping phases (shared verbatim by both modes) -------------
    def _admit_phase(self, t: int, observed: List[int]
                     ) -> List[Tuple[int, int, int, int]]:
        """FIFO admission under arrivals/slots/gate/quota; returns the
        prefill plan ``[(slot, start, prompt_len, rid), ...]`` in
        admission order."""
        plan: List[Tuple[int, int, int, int]] = []
        while (self._queue and self._queue[0].arrival <= t
                and self._free_slots):
            req = self._queue[0]
            # observed BEFORE the attempt (ContinuousBatcher contract)
            observed.append(req.kv_len)
            if self.arbiter is not None:
                chunk = self.pool.class_for(req.kv_len)
                if chunk is not None:
                    units = -(-chunk // self.arbiter.pool.unit_size)
                    if not self.arbiter.admission(req.tenant, units):
                        self.rejected += 1
                        self._queue.popleft()
                        continue
            a = self.pool.alloc(req.rid, req.kv_len, tenant=req.tenant)
            if a is None:
                self.rejected += 1
                self._queue.popleft()
                continue
            self._queue.popleft()
            slot = self._free_slots.pop()
            self._slot_of[req.rid] = slot
            self._active[req.rid] = req
            self._starts[slot] = a.start
            self._lens[slot] = req.kv_len
            self._rids[slot] = req.rid
            self._act[slot] = 1
            self.queue_delays.append(t - req.arrival)
            plan.append((slot, a.start, req.prompt_len, req.rid))
        return plan

    def _decode_phase(self, observed: List[int]
                      ) -> Tuple[List[Tuple[int, int, int, int]],
                                 List[int]]:
        """Per-tick decode bookkeeping: growth (bulk), class-overflow
        reallocation (inline, processing order), completion/drop
        marking. Returns ``(plan, finished)`` where plan rows are
        ``(slot, mv_src, mv_dst, mv_tok)`` (move tokens 0 = no move)
        and ``finished`` lists drops and completions in processing
        order (freelist order is part of the decision contract)."""
        plan: List[Tuple[int, int, int, int]] = []
        grown: List[Tuple[int, int]] = []
        finished: List[int] = []
        for rid, req in self._active.items():
            slot = self._slot_of[rid]
            req.decoded += 1
            old = self.pool.allocation(rid)
            pre_len = req.kv_len - 1
            mv = (0, 0, 0)
            if req.kv_len <= old.chunk:
                grown.append((rid, req.kv_len))
                start = old.start
            else:
                new = self.pool.extend(rid, req.kv_len)
                if new is None:   # pool full mid-flight: drop, no decode
                    observed.append(req.kv_len)
                    self.rejected += 1
                    finished.append(rid)
                    self._act[slot] = 0
                    continue
                if new.start != old.start:
                    self.realloc_copies += 1
                    self.realloc_tokens += old.length
                    observed.append(req.kv_len)
                    mv = (old.start, new.start, old.length)
                start = new.start
            self._starts[slot] = start
            self._lens[slot] = pre_len
            plan.append((slot, *mv))
            if req.decoded >= req.output_len:
                finished.append(rid)
                self.completed += 1
        if grown:
            self.pool.extend_bulk(grown)
        return plan, finished

    def _release(self, rids: List[int]) -> None:
        for rid in rids:
            if rid in self.pool._live:
                self.pool.free(rid)
            del self._active[rid]
            slot = self._slot_of.pop(rid)
            self._act[slot] = 0
            self._free_slots.append(slot)

    # -- device dispatches ----------------------------------------------------
    def _dispatch_prefill(self, plan) -> None:
        if not plan:
            return
        with span("harness.prefill_dispatch"):
            if self.mode == "batched":
                starts = np.zeros(self.max_batch, np.int32)
                plens = np.zeros(self.max_batch, np.int32)
                rids = np.zeros(self.max_batch, np.int32)
                for i, (_slot, start, plen, rid) in enumerate(plan):
                    starts[i], plens[i], rids[i] = start, plen, rid
                # starts/plens/rids are freshly built per call: safe to
                # hand to jnp.asarray without copying
                self._k, self._v = self._prefill(
                    self._k, self._v, jnp.asarray(starts), jnp.asarray(plens),
                    jnp.asarray(rids))
                self.n_prefill_dispatches += 1
                return
            for _slot, start, plen, rid in plan:
                self._k, self._v = self._prefill(
                    self._k, self._v,
                    jnp.asarray([start], jnp.int32),
                    jnp.asarray([plen], jnp.int32),
                    jnp.asarray([rid], jnp.int32))
                self.n_prefill_dispatches += 1

    def _dispatch_decode(self, plan) -> None:
        if not plan:
            return
        with span("harness.decode_dispatch"):
            if self.mode == "batched":
                mv_src = np.zeros(self.max_batch, np.int32)
                mv_dst = np.zeros(self.max_batch, np.int32)
                mv_tok = np.zeros(self.max_batch, np.int32)
                n_mv = 0
                for _slot, src, dst, tok in plan:
                    if tok:
                        mv_src[n_mv], mv_dst[n_mv] = src, dst
                        mv_tok[n_mv] = tok
                        n_mv += 1
                # .copy(): jnp.asarray may zero-copy a host array, and the
                # async-dispatched step can read it AFTER the next tick's
                # bookkeeping mutates the slot state in place
                self._k, self._v, tokens = self._decode(
                    self._k, self._v, jnp.asarray(self._starts.copy()),
                    jnp.asarray(self._lens.copy()),
                    jnp.asarray(self._rids.copy()),
                    jnp.asarray(self._act.copy()), jnp.asarray(mv_src),
                    jnp.asarray(mv_dst), jnp.asarray(mv_tok))
                self.n_decode_dispatches += 1
                snap = tuple(int(self._rids[s]) if self._act[s] else None
                             for s in range(self.max_batch))
                self._token_log.append((snap, tokens))
                return
            for slot, src, dst, tok in plan:
                self._k, self._v, tokens = self._decode(
                    self._k, self._v,
                    jnp.asarray(self._starts[slot:slot + 1].copy()),
                    jnp.asarray(self._lens[slot:slot + 1].copy()),
                    jnp.asarray(self._rids[slot:slot + 1].copy()),
                    jnp.asarray(self._act[slot:slot + 1].copy()),
                    jnp.asarray([src], np.int32), jnp.asarray([dst], np.int32),
                    jnp.asarray([tok], np.int32))
                self.n_decode_dispatches += 1
                self._token_log.append(((int(self._rids[slot]),), tokens))

    # -- the tick -------------------------------------------------------------
    @hot_path(counters=("n_decode_dispatches", "n_prefill_dispatches"))
    def tick(self, t: int) -> None:
        """One serving tick: admit → prefill dispatch → decode
        bookkeeping → ONE decode dispatch (batched mode) → frees →
        observe/arbitrate/refit. No device value is synced to host
        here — tokens stay on device until :meth:`result`. The
        dispatches are made through ``self.`` so that a caller may wrap
        them on the instance."""
        with span("harness.tick"):
            observed: List[int] = []
            with span("harness.admit"):
                prefill_plan = self._admit_phase(t, observed)
            self._dispatch_prefill(prefill_plan)
            with span("harness.decode_phase"):
                decode_plan, finished = self._decode_phase(observed)
            self._dispatch_decode(decode_plan)
            with span("harness.release"):
                self._release(finished)
            with span("harness.observe"):
                self._observe(observed)

    def _observe(self, observed: List[int]) -> None:
        """The tick's tail: observed lengths into the pool's sketch,
        the arbiter's cadence, and the pool's refit check."""
        if self.pool.batch_observe and observed:
            self.pool.observe_lengths(np.asarray(observed, dtype=np.int64))
        if self.arbiter is not None:
            self.arbiter.tick(1)
        if self.adaptive:
            decision = self.pool.maybe_refit()
            if decision is not None and decision.approved:
                self.n_refits += 1
                if self.pool.max_chunk_tokens > self.max_chunk:
                    raise RuntimeError(
                        f"refit grew the top class to "
                        f"{self.pool.max_chunk_tokens} tokens, past the "
                        f"harness's compiled ceiling {self.max_chunk}; "
                        f"construct the harness with max_chunk_tokens= "
                        f"headroom for adaptive runs")

    def run(self, workload: List[Request],
            max_ticks: Optional[int] = None) -> HarnessResult:
        for req in sorted(workload, key=lambda r: r.arrival):
            self.submit(req)
        if max_ticks is None:
            horizon = max((int(r.arrival) for r in workload), default=0)
            max_ticks = horizon + sum(r.output_len for r in workload) + 16
        waste_samples: List[float] = []
        active_samples: List[int] = []
        t = -1
        for t in range(max_ticks):
            self.tick(t)
            st = self.pool.stats()
            if st.active_requests:
                waste_samples.append(st.waste_fraction)
            active_samples.append(st.active_requests)
            if not self._active and not self._queue:
                break
        return self.result(t + 1, waste_samples, active_samples)

    def result(self, ticks: int, waste_samples=(), active_samples=(0,)
               ) -> HarnessResult:
        """Fold the run's ledger (syncing the device token log to host
        exactly once)."""
        tokens: Dict[int, List[int]] = {}
        for snap, dev in self._token_log:
            arr = np.asarray(dev)
            for slot, rid in enumerate(snap):
                if rid is not None and arr[slot] >= 0:
                    tokens.setdefault(rid, []).append(int(arr[slot]))
        qd_mean, qd_p50, qd_p99 = queue_delay_stats(self.queue_delays)
        denials = (self.arbiter.n_admission_denials
                   if self.arbiter is not None else 0)
        return HarnessResult(
            ticks=ticks,
            completed=self.completed,
            rejected=self.rejected,
            realloc_copies=self.realloc_copies,
            realloc_tokens=self.realloc_tokens,
            generated_tokens=sum(len(v) for v in tokens.values()),
            n_decode_dispatches=self.n_decode_dispatches,
            n_prefill_dispatches=self.n_prefill_dispatches,
            queue_delay_mean=qd_mean,
            queue_delay_p50=qd_p50,
            queue_delay_p99=qd_p99,
            mean_waste_fraction=(float(np.mean(waste_samples))
                                 if len(waste_samples) else 0.0),
            peak_active=int(np.max(active_samples)),
            mean_active=float(np.mean(active_samples)),
            n_refits=self.n_refits,
            n_admission_denials=denials,
            tokens=tokens)
