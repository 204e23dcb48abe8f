"""Run the system's main paths once on one TPU chip and check the results.

    python chip_smoke.py [--seed N]

Three phases, in one process (a chip belongs to one process at a time):

(a) device   -- fail unless JAX's first device is a TPU; never fall back
                to the CPU.
(b) serving  -- ``OfflineHarness`` over a ``KVSlabPool`` at the KV width
                of one Mixtral-8x7B layer (8 KV heads of 128), batch 64,
                a 262,144-token f32 pool, 160 log-normal requests. It
                runs the Pallas kernels compiled for the chip, then again
                with the kernels' jnp oracles at "highest" matmul
                precision. The decisions must match, every request must
                complete, decode must take at most one dispatch per tick,
                and the decode-attention kernel must agree with the
                float32 oracle on one decode batch of the pools the run
                left behind.
(c) allocator -- a 1,000-tenant ``TenantArbiter(fleet=True)`` over
                memcached slab allocators on one page pool, with
                device-resident fused observe sketches, fed a seeded
                phased multi-tenant stream in serving mode (allocator
                traffic, ``observe`` per tenant, ``tick`` per round). Its
                refit and transfer decisions must equal a host-sketch
                twin's, one fleet waste frontier must equal the exact
                host waste, and pages must be conserved.

Per-phase compile time, run time and ``peak_bytes_in_use`` are printed
as set-up facts, not as speed. The last line of standard output is one
JSON object naming the device; it is printed only when every check
passed. Everything is built from ``--seed``; nothing is read but the
repository's own sources.

The compile cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, and in ``.jax_cache`` next to this file otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# (b) serving
HKV, HEAD_DIM = 8, 128             # src/repro/configs/mixtral_8x7b.py
QUERY_HEADS = 32                   # same config; the GQA attention check
MAX_BATCH = 64
CLASSES = (128, 256, 512, 1024)
POOL_TOKENS = 262144
N_REQUESTS = 160
ATTN_TOL = 1e-3                    # max-abs, kernel vs float32 oracle

# (c) allocator
N_TENANTS = 1000
PAGE = 1 << 14
ROUNDS = 16
SETS_PER_TENANT_ROUND = 24
CHECK_EVERY = 64
FRONTIER_TENANTS = 64              # tenants in the waste-frontier check
FRONTIER_RTOL = 1e-5               # f32 accumulation vs exact int64


def check(cond: bool, what: str) -> None:
    """A failed check ends the process with exit code 1 and no result."""
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


# -- (b) serving ---------------------------------------------------------------

def serving_workload(seed: int):
    """160 open-loop requests, log-normal prompt and output lengths (the
    offline serving mix: prompt mean 96, output mean 10, 4 per tick)."""
    from repro.serving import Request, lognormal_request_workload
    rng = np.random.default_rng(seed)
    reqs = lognormal_request_workload(
        rng, N_REQUESTS, prompt_mean=96.0, prompt_std=64.0,
        output_mean=10.0, output_std=5.0, arrival_rate=4.0)
    # every request must fit the top class, or "none rejected" is moot
    check(max(r.prompt_len + r.output_len for r in reqs) <= CLASSES[-1],
          "workload has a request longer than the top slab class")
    return [Request(rid=r.rid, prompt_len=r.prompt_len,
                    output_len=r.output_len, arrival=r.arrival)
            for r in reqs]


def serving_harness(impl: str):
    from repro.serving import KVSlabPool, OfflineHarness
    pool = KVSlabPool(POOL_TOKENS, CLASSES)
    return OfflineHarness(pool, max_batch=MAX_BATCH, hkv=HKV, d=HEAD_DIM,
                          impl=impl, interpret=False)


def serving_run(impl: str, seed: int, dev):
    """Warm up (compile every step shape) on a throwaway harness, then
    run the workload on a fresh one."""
    from repro.serving import Request
    t0 = time.perf_counter()
    warm = serving_harness(impl)
    warm.run([Request(rid=0, prompt_len=8, output_len=2)], max_ticks=8)
    del warm
    compile_s = time.perf_counter() - t0
    h = serving_harness(impl)
    t0 = time.perf_counter()
    res = h.run(serving_workload(seed))   # result() syncs every token
    run_s = time.perf_counter() - t0
    log(f"serving impl={impl} hkv={HKV} d={HEAD_DIM} batch={MAX_BATCH} "
        f"pool_tokens={POOL_TOKENS}: compile_s={compile_s} run_s={run_s} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    log(f"serving impl={impl}: ticks={res.ticks} completed={res.completed} "
        f"rejected={res.rejected} generated_tokens={res.generated_tokens} "
        f"decode_dispatches={res.n_decode_dispatches} "
        f"prefill_dispatches={res.n_prefill_dispatches} "
        f"realloc_copies={res.realloc_copies}")
    check(res.completed == N_REQUESTS,
          f"serving impl={impl}: {res.completed}/{N_REQUESTS} completed")
    check(res.rejected == 0, f"serving impl={impl}: {res.rejected} rejected")
    check(res.n_decode_dispatches <= res.ticks,
          f"serving impl={impl}: {res.n_decode_dispatches} decode "
          f"dispatches > {res.ticks} ticks")
    return h, res


def attention_check(h, seed: int) -> None:
    """The decode-attention kernel against the full-pool float32 oracle
    on the pools the Pallas run left behind, read at the slot
    descriptors of its last decode batch (never-used slots are empty
    sequences), for the harness's one query head per KV head and for
    Mixtral's 32 query heads."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ref import slab_decode_attention_ref
    from repro.kernels.slab_attention import slab_decode_attention_pallas
    k_pool, v_pool = h._k, h._v
    starts = jnp.asarray(h._starts.copy())
    lens = jnp.asarray(h._lens.copy())
    check(int(np.max(h._lens)) > 0, "attention check: no live sequence")
    for hq in (HKV, QUERY_HEADS):
        q = jax.random.normal(jax.random.PRNGKey(seed),
                              (MAX_BATCH, hq, HEAD_DIM), jnp.float32)
        got = slab_decode_attention_pallas(
            q, k_pool, v_pool, starts, lens,
            max_chunk_tokens=CLASSES[-1], interpret=False)
        err = 0.0
        with jax.default_matmul_precision("highest"):
            for lo in range(0, MAX_BATCH, 16):     # bounds the T-wide scores
                sl = slice(lo, lo + 16)
                want = slab_decode_attention_ref(q[sl], k_pool, v_pool,
                                                 starts[sl], lens[sl])
                err = max(err, float(jnp.max(jnp.abs(got[sl] - want))))
        log(f"attention hq={hq} hkv={HKV} d={HEAD_DIM}: max_abs_err={err} "
            f"(bound {ATTN_TOL})")
        check(err <= ATTN_TOL, f"attention hq={hq}: max-abs error {err} "
                               f"> {ATTN_TOL}")


def serving_phase(seed: int, dev) -> None:
    import jax
    h, pallas = serving_run("pallas", seed, dev)
    attention_check(h, seed)
    del h
    with jax.default_matmul_precision("highest"):
        _, ref = serving_run("ref", seed, dev)
    check(pallas.decisions() == ref.decisions(),
          f"serving decisions differ: pallas {pallas.decisions()} "
          f"vs ref {ref.decisions()}")
    total = agree = 0
    for rid, toks in ref.tokens.items():
        got = pallas.tokens.get(rid, [])
        total += len(toks)
        agree += sum(a == b for a, b in zip(got, toks))
    log(f"serving: decisions match the highest-precision oracle; "
        f"token agreement {agree}/{total} = {agree / max(total, 1)}")


# -- (c) allocator loop -----------------------------------------------------------

def tenant_stream(seed: int):
    """The phased multi-tenant stream fanned out to the fleet: each set
    goes round-robin to one of the physical tenants behind its operating
    point; gets and deletes follow their key. The first four of the
    paper's operating points: their items (up to ~4.2 KB, plus the next
    point's sizes in troughs) sit on the default 8,192-bucket unit grid,
    where the device sketch bins sizes exactly as the host sketch does."""
    from repro.core.distribution import PAPER_WORKLOADS
    from repro.memcached import multitenant_phased_ops
    workloads = PAPER_WORKLOADS[:4]
    w = len(workloads)
    base = multitenant_phased_ops(
        workloads, n_sets=ROUNDS * N_TENANTS * SETS_PER_TENANT_ROUND,
        trough_mix=0.5, seed=seed)
    cycles = -(-N_TENANTS // w)
    cnt = [0] * w
    home = {}
    out = []
    for op in base:
        k = (op.tenant, op.key)
        if op.op == "set" and k not in home:
            home[k] = (op.tenant + w * cnt[op.tenant]) % N_TENANTS
            cnt[op.tenant] = (cnt[op.tenant] + 1) % cycles
        out.append((home[k], op))
    per = -(-len(out) // ROUNDS)
    return [out[i:i + per] for i in range(0, len(out), per)]


def tenant_name(i: int) -> str:
    return f"t{i:04d}"


def build_arbiter(device: bool, ops_per_round: int):
    from repro.core import ControllerConfig, PagePool, TenantArbiter
    from repro.core.slab_policy import default_memcached_schedule
    from repro.memcached import SlabAllocator
    pool = PagePool(2 * N_TENANTS, page_size=PAGE)
    # half_life=inf: undecayed counts are exact in the device sketch's
    # float32, so the host twin sees the same histogram bit for bit
    cfg = ControllerConfig(page_size=PAGE, check_every=CHECK_EVERY,
                           min_items_between_refits=2 * CHECK_EVERY,
                           half_life=float("inf"), device=device,
                           fused_observe=True)
    arb = TenantArbiter(pool, controller_config=cfg,
                        arbitrate_every=ops_per_round, fleet=True,
                        fleet_capacity=N_TENANTS)
    classes = default_memcached_schedule(page_size=PAGE)
    for i in range(N_TENANTS):
        name = tenant_name(i)
        arb.register(name, SlabAllocator(classes, page_size=PAGE,
                                         page_pool=pool, tenant=name))
    pool.equal_partition(floor=1)
    return arb


def drive(arb, rounds) -> None:
    """Serving mode: traffic goes to each tenant's allocator, the sizes
    of a round's sets reach the tenant's sketch in one ``observe``, and
    one ``tick`` runs every due drift check (batched gate, batched
    frontier scoring) and the arbitration round."""
    for chunk in rounds:
        sizes = defaultdict(list)
        for phys, op in chunk:
            name = tenant_name(phys)
            alloc = arb.tenants[name].allocator
            if op.op == "set":
                alloc.set(op.key, op.size)
                sizes[name].append(op.size + alloc.item_overhead)
            elif op.op == "delete":
                alloc.delete(op.key)
            else:
                alloc.get(op.key)
        for name in sorted(sizes):
            arb.observe(name, np.asarray(sizes[name], dtype=np.int64))
        arb.tick(len(chunk))


def refit_sig(arb):
    return [(n, d.approved, d.reason,
             None if d.chunks is None else tuple(np.asarray(d.chunks).tolist()))
            for n in sorted(arb.tenants)
            for d in arb.tenants[n].controller.decisions]


def transfer_sig(arb):
    return [(d.approved, d.reason, d.donor, d.recipient, d.benefit, d.cost,
             d.forecast_penalty, d.evicted_items, d.evicted_bytes, d.at_op)
            for d in arb.decisions]


def frontier_check(arb) -> None:
    """One fleet frontier through ``score_requests`` (the
    ``waste_eval_fleet`` launch) against ``core/waste.py``'s exact
    int64 waste, row by row."""
    from repro.core.controller import ScoreRequest, score_requests
    from repro.core.slab_policy import (covering_default_classes,
                                        default_memcached_schedule)
    from repro.core.waste import waste_exact
    reqs = []
    for name in sorted(arb.tenants)[:FRONTIER_TENANTS]:
        ctl = arb.tenants[name].controller
        support, freqs = ctl.sketch.snapshot()
        if support.size == 0:
            continue
        rows = [np.asarray(ctl.chunks, dtype=np.int64),
                default_memcached_schedule(page_size=PAGE),
                covering_default_classes(support, k=8, page_size=PAGE)]
        reqs.append(ScoreRequest(rows=rows, support=support, freqs=freqs,
                                 page_size=PAGE, drift=0.0,
                                 cost_bytes_fn=None))
    check(len(reqs) >= 2, "frontier check: fewer than two tenants observed")
    worst = 0.0
    for req, scores in zip(reqs, score_requests(reqs)):
        for row, got in zip(req.rows, scores):
            want = waste_exact(row, req.support, req.freqs, page_size=PAGE)
            rel = abs(float(got) - want) / max(want, 1)
            worst = max(worst, rel)
    log(f"allocator: waste_eval_fleet frontier of {len(reqs)} tenants x 3 "
        f"rows vs exact waste: worst relative error {worst} "
        f"(bound {FRONTIER_RTOL})")
    check(worst <= FRONTIER_RTOL,
          f"frontier scores off exact waste by {worst} relative")


def allocator_phase(seed: int, dev) -> None:
    from repro.scenarios.invariants import check_fleet
    rounds = tenant_stream(seed)
    ops_per_round = len(rounds[0])
    arbs = {}
    for device in (True, False):
        arb = build_arbiter(device, ops_per_round)
        t0 = time.perf_counter()
        drive(arb, rounds[:1])
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        drive(arb, rounds[1:])
        run_s = time.perf_counter() - t0
        n_refits = sum(t.controller.n_refits for t in arb.tenants.values())
        log(f"allocator sketch={'device' if device else 'host'} "
            f"tenants={N_TENANTS} ops={sum(len(r) for r in rounds)} "
            f"rounds={len(rounds)}: first_round_s={first_s} "
            f"run_s={run_s} peak_bytes_in_use={peak_bytes(dev)}")
        log(f"allocator sketch={'device' if device else 'host'}: "
            f"refit_checks={len(refit_sig(arb))} refits={n_refits} "
            f"transfer_decisions={len(arb.decisions)} "
            f"transfers={arb.n_transfers} "
            f"gate_launches={arb.n_gate_launches} "
            f"score_launches={arb.n_score_launches}")
        violations = check_fleet(arb)
        check(not violations, f"fleet invariants: {violations[:3]}")
        check(arb.pool.conserved, "page pool not conserved")
        arbs[device] = arb
    dev_arb, host_arb = arbs[True], arbs[False]
    check(dev_arb.n_gate_launches >= 1, "the batched drift gate never ran")
    check(dev_arb.fleet.sketch is not None
          and dev_arb.fleet.sketch.shape == (N_TENANTS, 1 << 13),
          "fleet sketches are not stacked on the device")
    check(sum(t.controller.n_refits for t in dev_arb.tenants.values()) >= 1,
          "no refit was approved; the parity check would be vacuous")
    check(dev_arb.n_transfers >= 1,
          "no page transfer happened; the parity check would be vacuous")
    check(refit_sig(dev_arb) == refit_sig(host_arb),
          "refit decisions differ between the device and host sketches")
    drifts = [(d.drift, e.drift) for n in sorted(dev_arb.tenants)
              for d, e in zip(dev_arb.tenants[n].controller.decisions,
                              host_arb.tenants[n].controller.decisions)]
    worst = max((abs(a - b) for a, b in drifts), default=0.0)
    check(worst <= 1e-4, f"drift differs by {worst} between sketches")
    check(transfer_sig(dev_arb) == transfer_sig(host_arb),
          "transfer decisions differ between the device and host sketches")
    check(dev_arb.stats() == host_arb.stats(),
          "allocator stats differ between the device and host sketches")
    log(f"allocator: refit and transfer decisions match the host-sketch "
        f"twin (worst drift difference {worst}); pages conserved")
    frontier_check(dev_arb)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no package at {SRC / 'repro'}; run this from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {jax.config.jax_compilation_cache_dir}")
    for name, phase in (("serving", serving_phase),
                        ("allocator", allocator_phase)):
        t0 = time.perf_counter()
        phase(args.seed, dev)
        log(f"phase {name}: wall_s={time.perf_counter() - t0} "
            f"peak_bytes_in_use={peak_bytes(dev)}")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
