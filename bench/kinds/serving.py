"""Serving cells: ``OfflineHarness`` over a ``KVSlabPool`` at the KV
width of the configuration, driven one tick at a time.

Closed mixes queue every request before the window and keep the batch
full; the end-to-end number is generated tokens per second over the
whole window, ended by a device sync. Open mixes submit each request
when it falls due on the host clock; every tick's tokens are read back
to the host as soon as the tick ends, and the end-to-end number is the
95th percentile of every gap between consecutive tokens of every
request that completed inside the window.

The harness's toy model picks each token from the first ``vocab`` dims
of query head 0's attention output, so nothing it returns depends on
the other heads. The benchmark gives the model an output head
(:class:`OutputHead`): the attention call that the decode step makes is
wrapped so that those dims carry a fixed projection of every head's
whole output, and every served token depends on every head and dim.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from lib import traffic as traffic_lib
from lib.common import percentile
from reference import serving_ref

HOLE_SAMPLE_EVERY = 8        # ticks between hole-fraction samples
STALL_MS = 100.0             # a tick longer than this is counted as a stall


def pow2_classes(lo: int, hi: int) -> List[int]:
    out, c = [], lo
    while c <= hi:
        out.append(c)
        c *= 2
    return out


class OutputHead:
    """Wraps the attention entries that the harness's decode step calls
    (``slab_decode_attention_pallas`` for the Pallas path, its window
    oracle for the jnp path) so that the dims the harness reads its
    token from hold the output head's logits over the whole
    ``(heads, head_dim)`` output. The wrap is made once per process,
    before any decode step is built; ``traced`` turns true when a decode
    step is traced through it."""

    NAMES = ("slab_decode_attention_pallas",
             "slab_decode_attention_window_ref")
    traced = False

    @classmethod
    def install(cls, hkv: int, d: int, vocab: int) -> None:
        import jax
        import jax.numpy as jnp
        import repro.serving.offline_harness as oh
        w = serving_ref.head_weights(hkv * d, vocab)
        for name in cls.NAMES:
            fn = getattr(oh, name)
            if getattr(fn, "output_head", None) == (hkv, d, vocab):
                continue
            fn = getattr(fn, "inner", fn)
            # decode steps built before the wrap would bypass it
            oh._STEP_CACHE.clear()
            cls.traced = False

            def wrapped(q, k_pool, v_pool, starts, lens, *, _fn=fn, **kw):
                cls.traced = True
                out = _fn(q, k_pool, v_pool, starts, lens, **kw)
                b = out.shape[0]
                logits = jnp.einsum(
                    "bf,fv->bv", out.reshape(b, hkv * d), jnp.asarray(w),
                    precision=jax.lax.Precision.HIGHEST)
                return out.at[:, 0, :vocab].set(logits)

            wrapped.output_head = (hkv, d, vocab)
            wrapped.inner = fn
            setattr(oh, name, wrapped)


class ServingCell:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg = ctx.config
        self.sv = cfg["serving"]
        self.hkv = int(cfg["num_key_value_heads"])
        if int(cfg["num_attention_heads"]) != self.hkv:
            raise ValueError("the harness makes one query head per KV "
                             "head: num_attention_heads must equal "
                             "num_key_value_heads")
        self.d = int(cfg["head_dim"])
        self.vocab = int(cfg["vocab_size"])
        self.mix = ctx.traffic
        self.closed = self.mix["arrival"]["kind"] == "closed"
        # per-dispatch counters, read by the per-layer metrics
        self.decode_live_tokens: List[int] = []
        self.decode_lanes: List[int] = []
        self.prefill_prompt_tokens: List[int] = []

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        from repro.serving import KVSlabPool, OfflineHarness, Request
        ctx = self.ctx
        t0 = time.perf_counter()
        self.requests = traffic_lib.serving_requests(self.mix, ctx.seed)
        ctx.setup_parts["traffic_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        classes = pow2_classes(int(self.sv["min_class"]),
                               int(self.sv["max_class"]))
        self.pool = KVSlabPool(int(self.sv["pool_tokens"]), classes)
        OutputHead.install(self.hkv, self.d, self.vocab)
        self.h = OfflineHarness(self.pool, max_batch=int(self.sv["max_batch"]),
                                hkv=self.hkv, d=self.d, vocab=self.vocab)
        self._instrument()
        ctx.setup_parts["pool_s"] = time.perf_counter() - t0
        # warm-up: one request through the real tick compiles (or loads
        # from the compile cache) the prefill and the decode program at
        # the window's shapes; nothing else is ever dispatched
        t0 = time.perf_counter()
        self.warm_rid = 0
        self.h.submit(Request(rid=self.warm_rid, prompt_len=1, output_len=1))
        self.t = 0
        while self.h.completed < 1:
            self.h.tick(self.t)
            self.t += 1
        self._sync()
        if not OutputHead.traced:
            raise RuntimeError("the harness's decode step no longer calls "
                               "the attention entries the output head "
                               "wraps: " + ", ".join(OutputHead.NAMES))
        self.base_completed = self.h.completed
        self.base_dispatch = len(self.decode_lanes)
        self.base_admitted = len(self.h.queue_delays)
        self.base_prefill = len(self.prefill_prompt_tokens)
        ctx.setup_parts["warmup_s"] = time.perf_counter() - t0
        if self.closed:
            for r in self.requests:
                self.h.submit(self._request(r))

    def _request(self, r):
        from repro.serving import Request
        return Request(rid=r["rid"], prompt_len=r["prompt_len"],
                       output_len=r["output_len"])

    def _instrument(self) -> None:
        """Count what each dispatch carries (live KV lengths, lanes,
        prompt tokens) as the harness hands it to the device."""
        h = self
        harness = self.h
        decode, prefill = harness._dispatch_decode, harness._dispatch_prefill

        def dispatch_decode(plan):
            if plan:
                act = harness._act > 0
                h.decode_lanes.append(int(act.sum()))
                h.decode_live_tokens.append(
                    int(harness._lens[act].astype(np.int64).sum()))
            decode(plan)

        def dispatch_prefill(plan):
            if plan:
                h.prefill_prompt_tokens.append(
                    int(sum(p[2] for p in plan)))
            prefill(plan)

        harness._dispatch_decode = dispatch_decode
        harness._dispatch_prefill = dispatch_prefill

    def _sync(self) -> None:
        import jax
        jax.block_until_ready((self.h._k, self.h._v))

    # -- the window ------------------------------------------------------------
    def window(self, seconds: float) -> Dict:
        ctx = self.ctx
        spans = ctx.spans
        h = self.h
        self.hole_samples: List[float] = []
        self.pool_samples: List[tuple] = []     # (allocated, used) tokens
        self.tick_ms: List[float] = []
        self.tick_cpu_ms: List[float] = []     # main thread's CPU time
        self.tick_at: List[float] = []         # start, s into the window
        self.tick_prefill: List[bool] = []
        self.token_times: Dict[int, List[float]] = {}
        self.due_at: Dict[int, float] = {}
        lateness: List[float] = []
        pending = list(self.requests) if not self.closed else []
        nxt = 0
        ticks = 0
        t_start = time.perf_counter()
        end = t_start + seconds
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if not self.closed:
                while nxt < len(pending) and \
                        t_start + pending[nxt]["due_s"] <= now:
                    r = pending[nxt]
                    self.due_at[r["rid"]] = t_start + r["due_s"]
                    lateness.append(now - self.due_at[r["rid"]])
                    h.submit(self._request(r))
                    nxt += 1
                if not h._active and not h._queue:
                    if nxt >= len(pending):
                        raise RuntimeError("the traffic file holds too few "
                                           "requests for the window")
                    wake = t_start + pending[nxt]["due_s"]
                    with spans.span("wait"):
                        time.sleep(max(0.0, min(wake, end) - now))
                    continue
            t0 = time.perf_counter()
            c0 = time.thread_time()
            n_before = h.n_decode_dispatches
            p_before = h.n_prefill_dispatches
            with spans.span("tick"):
                h.tick(self.t)
                if not self.closed and h.n_decode_dispatches > n_before:
                    snap, toks = h._token_log[-1]
                    toks = np.asarray(toks)
                    t_tok = time.perf_counter()
                    for rid, tok in zip(snap, toks):
                        if rid is not None and tok >= 0:
                            self.token_times.setdefault(rid, []).append(t_tok)
            self.tick_ms.append((time.perf_counter() - t0) * 1e3)
            self.tick_cpu_ms.append((time.thread_time() - c0) * 1e3)
            self.tick_at.append(t0 - t_start)
            self.tick_prefill.append(h.n_prefill_dispatches > p_before)
            self.t += 1
            ticks += 1
            if self.closed and h.n_decode_dispatches == n_before:
                raise RuntimeError("the queue ran dry inside the window")
            if ticks % HOLE_SAMPLE_EVERY == 0:
                st = self.pool.stats()
                if st.allocated_tokens:
                    self.hole_samples.append(
                        st.waste_tokens / st.allocated_tokens)
                    self.pool_samples.append((st.allocated_tokens,
                                              st.used_tokens))
        with spans.span("final_sync"):
            self._sync()
        t_end = time.perf_counter()
        self.window_s = t_end - t_start
        self.ticks = ticks
        self.lateness = lateness
        return self._end_to_end()

    def _end_to_end(self) -> Dict:
        h = self.h
        lanes = self.decode_lanes[self.base_dispatch:]
        tokens = int(sum(lanes))
        self.window_tokens = tokens
        out = {"gen_tokens_per_s": tokens / self.window_s}
        if self.hole_samples:
            out["hole_fraction"] = float(np.mean(self.hole_samples))
        res = h.result(self.t)
        self.result = res
        completed = [r for r in self.requests
                     if r["rid"] in res.tokens
                     and len(res.tokens[r["rid"]]) >= r["output_len"]]
        self.completed_reqs = completed
        if not self.closed:
            gaps = []
            ttft = []
            for r in completed:
                ts = self.token_times.get(r["rid"], [])
                gaps.extend(np.diff(ts).tolist())
                if ts:
                    ttft.append(ts[0] - self.due_at[r["rid"]])
            self.itl_samples = gaps
            self.ttft = ttft
            if gaps:
                out["itl_p95_s"] = percentile(gaps, 95)
        return out

    # -- what the run prints on earlier lines -------------------------------
    def report(self, log) -> None:
        h = self.h
        tick = np.asarray(self.tick_ms)
        log(f"window: ticks={self.ticks} window_s={self.window_s} "
            f"tokens={self.window_tokens} admitted="
            f"{len(h.queue_delays) - self.base_admitted} "
            f"completed={h.completed - self.base_completed} "
            f"rejected={h.rejected} "
            f"prefill_dispatches="
            f"{len(self.prefill_prompt_tokens) - self.base_prefill} "
            f"realloc_copies={h.realloc_copies}")
        alloc, used = (np.asarray(self.pool_samples, dtype=np.float64).T
                       if self.pool_samples else (np.zeros(1), np.zeros(1)))
        log(f"pool: pool_tokens={self.pool.pool_tokens} "
            f"allocated_share_mean={float(alloc.mean()) / self.pool.pool_tokens} "
            f"allocated_share_max={float(alloc.max()) / self.pool.pool_tokens} "
            f"live_share_mean={float(used.mean()) / self.pool.pool_tokens} "
            f"bump_share={self.pool._bump / self.pool.pool_tokens}")
        slow = [(i, round(self.tick_at[i], 3), round(self.tick_ms[i], 1),
                 round(self.tick_cpu_ms[i], 1), self.tick_prefill[i])
                for i in np.argsort(-tick)[:10] if tick[i] > STALL_MS]
        log(f"stalls: longest_tick_ms={float(tick.max())} "
            f"ticks_over_{int(STALL_MS)}ms={int((tick > STALL_MS).sum())} "
            f"slowest=[(tick, at_s, wall_ms, main_thread_cpu_ms, prefill)]"
            f"={slow}")
        if not self.closed:
            lat = self.lateness or [0.0]
            log(f"backlog: queued_at_end={len(h._queue)} "
                f"active_at_end={len(h._active)}")
            log(f"generator: submitted={len(self.lateness)} "
                f"late_p95_s={percentile(lat, 95)} late_max_s={max(lat)}")
            log(f"latency: itl_samples={len(self.itl_samples)} "
                f"itl_p50_s={percentile(self.itl_samples or [0], 50)} "
                f"ttft_p50_s={percentile(self.ttft or [0], 50)} "
                f"ttft_p95_s={percentile(self.ttft or [0], 95)} "
                f"requests_completed={len(self.completed_reqs)}")

    def attempted_failed(self):
        h = self.h
        admitted = len(h.queue_delays) - self.base_admitted
        return admitted + h.rejected, h.rejected

    # -- correctness ------------------------------------------------------------
    def check(self, control: bool = False) -> List[tuple]:
        """Numbers compared against their limits: the widest logit gap
        of served tokens against the plain reference, the K and V rows
        that the requests still live at the close hold in the pool
        (every head and dim, prompt rows, appended rows and chunk copies)
        against the reference's, requests whose token count is wrong,
        and overlapping live allocations. With ``control`` the logit gap
        is the control's: bfloat16 attention put in the program's place
        on the same sample."""
        ctx = self.ctx
        res = self.result
        # every request the program counts as completed served exactly
        # its output length, and none served more
        lengths = {r["rid"]: r["output_len"] for r in self.requests}
        full = sum(1 for rid, t in res.tokens.items()
                   if rid in lengths and len(t) == lengths[rid])
        over = sum(1 for rid, t in res.tokens.items()
                   if rid in lengths and len(t) > lengths[rid])
        wrong_count = abs(self.h.completed - self.base_completed - full) + over
        overlaps = self._overlaps()
        kv_err, kv_requests = self._kv_rows_err()
        sample = self.sample()
        served = [(r["rid"], r["prompt_len"], res.tokens[r["rid"]])
                  for r in sample]
        # free the program's device state before the reference runs
        self.h._k = self.h._v = None
        gap_fn = serving_ref.control_gap if control else serving_ref.widest_gap
        gap = gap_fn(served, **self._ref_shape())
        ctx.log(f"reference: requests={len(served)} served_tokens="
                f"{sum(len(s[2]) for s in served)} "
                f"kv_rows_requests={kv_requests}")
        lim = ctx.limits
        return [("logit_gap", gap, lim["logit_gap"]),
                ("kv_rows_err", kv_err, 0),
                ("token_count_errors", wrong_count, 0),
                ("overlapping_chunks", overlaps, 0)]

    def _ref_shape(self) -> dict:
        """One padded shape for every request the reference reads: the
        top class's rows and the longest output the mix can hold."""
        return {"hkv": self.hkv, "d": self.d, "vocab": self.vocab,
                "rows": int(self.sv["max_class"]),
                "steps": int(self.mix["output"]["max"])}

    def _kv_rows_err(self):
        """Largest difference between the K and V rows each request
        still live at the close holds in the program's pool and the
        reference's rows of its prompt and served tokens; the rows
        compared are those the request has written so far."""
        import jax
        import jax.numpy as jnp
        shape = self._ref_shape()
        rows, steps = shape["rows"], shape["steps"]
        ref_fn = serving_ref.kv_rows_fn(**shape)

        @jax.jit
        def err(k_pool, v_pool, start, n_rows, k_ref, v_ref):
            live = (jnp.arange(rows) < n_rows)[:, None, None]
            k = jax.lax.dynamic_slice(k_pool, (start, 0, 0),
                                      (rows,) + k_pool.shape[1:])
            v = jax.lax.dynamic_slice(v_pool, (start, 0, 0),
                                      (rows,) + v_pool.shape[1:])
            return jnp.maximum(
                jnp.max(jnp.where(live, jnp.abs(k - k_ref), 0.0)),
                jnp.max(jnp.where(live, jnp.abs(v - v_ref), 0.0)))

        worst = []
        reqs = {r["rid"]: r for r in self.requests}
        for rid in sorted(self.h._active):
            r = reqs[rid]
            toks = self.result.tokens.get(rid, [])
            start = self.pool.allocation(rid).start
            k_ref, v_ref = ref_fn(jnp.int32(rid), jnp.int32(r["prompt_len"]),
                                  jnp.asarray(serving_ref.pad_tokens(
                                      toks, steps)))
            worst.append(err(self.h._k, self.h._v, jnp.int32(start),
                             jnp.int32(r["prompt_len"] + len(toks)),
                             k_ref, v_ref))
        if not worst:
            return 0.0, 0
        return float(jnp.max(jnp.stack(worst))), len(worst)

    def sample(self):
        """A sample of the completed requests drawn from the seed, with
        the longest of them in it."""
        done = sorted(self.completed_reqs, key=lambda r: r["rid"])
        if not done:
            raise RuntimeError("no request completed inside the window")
        n = int(self.mix["check_requests"])
        longest = max(done, key=lambda r: r["prompt_len"] + r["output_len"])
        rng = traffic_lib.seed_rng(self.ctx.seed, "serving-check")
        rest = [r for r in done if r is not longest]
        pick = rng.permutation(len(rest))[:max(0, n - 1)]
        return [longest] + [rest[i] for i in sorted(pick)]

    def _overlaps(self) -> int:
        spans = []
        for r in self.requests:
            try:
                a = self.pool.allocation(r["rid"])
            except KeyError:
                continue
            spans.append((a.start, a.start + a.chunk))
        spans.sort()
        bad = sum(1 for (s0, e0), (s1, _) in zip(spans, spans[1:]) if s1 < e0)
        bad += sum(1 for s, e in spans if s < 0 or e > self.pool.pool_tokens)
        return bad


def make(ctx):
    return ServingCell(ctx)
