"""Memcached fleet cells: ``TenantArbiter(fleet=True)`` over one
``SlabAllocator`` per tenant on one shared page pool, with the fused
device sketches stacked on the chip.

The window runs whole rounds. A round is the fleet's sets and deletes
through each tenant's allocator, one ``observe`` per tenant with the
sizes it stored (one observe window), and one arbiter ``tick``: the
batched drift gate, the batched frontier scoring, refits and page
transfers. Each round ends with the device synced. After ``--seconds``
the round in flight is finished; ``alloc_ops_per_s`` is every served op
of every round in the window (each delete, each set that was stored: a
set refused for want of memory, memcached's out-of-memory reply, is not
counted) over the sum of those rounds' times. Between rounds the clock
stops while the benchmark counts the refusals, samples the hole
fraction, replays the round into its reference and generates the next
round.
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Dict, List

import numpy as np

from lib import traffic as traffic_lib
from lib.rounds import whole_round_rate
from lib.common import BENCH
from lib.shapes import ShapeLog

STALL_MS = 100.0


def tenant_name(i: int) -> str:
    return f"t{i:04d}"


class Fleet:
    """One arbiter over one allocator per tenant, as the configuration
    states it, and the round that drives it."""

    def __init__(self, cfg: dict):
        from repro.core import ControllerConfig, PagePool, TenantArbiter
        from repro.memcached import SlabAllocator
        n = int(cfg["tenants"])
        page = int(cfg["page_size"])
        pool = PagePool(int(cfg["pages_total"]), page_size=page)
        ccfg = ControllerConfig(
            page_size=page, check_every=int(cfg["check_every"]),
            min_items_between_refits=int(cfg["min_items_between_refits"]),
            half_life=float(cfg["half_life"]), device=True,
            k=int(cfg["class_budget"]),
            device_buckets=int(cfg["sketch_buckets"]),
            fused_observe=True)
        self.arb = TenantArbiter(
            pool, controller_config=ccfg,
            arbitrate_every=int(cfg["arbitrate_every_ops"]),
            fleet=True, fleet_capacity=n)
        classes = memcached_classes(cfg)
        self.names = [tenant_name(i) for i in range(n)]
        self.allocs = []
        for name in self.names:
            alloc = SlabAllocator(classes, page_size=page, page_pool=pool,
                                  tenant=name,
                                  item_overhead=int(cfg["item_overhead"]))
            self.arb.register(name, alloc)
            self.allocs.append(alloc)
        pool.equal_partition(floor=1)

    def round(self, ops, spans, observed=None) -> int:
        """The round's sets and deletes through the allocators, one
        observe window per tenant, one arbiter tick, a device sync.
        Returns the ops served: every delete and every stored set."""
        allocs = self.allocs
        overhead = allocs[0].item_overhead
        sizes = defaultdict(list)
        refused = 0
        with spans.span("ops"):
            for op, t, key, size in ops:
                if op == traffic_lib.SET:
                    if not allocs[t].set(key, size):
                        refused += 1
                    sizes[t].append(size + overhead)
                else:
                    allocs[t].delete(key)
        with spans.span("observe"):
            for t in sorted(sizes):
                arr = np.asarray(sizes[t], dtype=np.int64)
                self.arb.observe(self.names[t], arr)
                if observed is not None:
                    observed[t].append(arr)
        with spans.span("arbiter_tick"):
            self.arb.tick(len(ops))
        with spans.span("round_sync"):
            self.sync()
        return len(ops) - refused

    def sync(self) -> None:
        import jax
        jax.block_until_ready(self.arb.fleet.sketch)


class FleetCell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.mix = ctx.traffic
        self.scored: List[tuple] = []      # (rows, support, freqs, page, scores)
        self.recording = False

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        ctx = self.ctx
        cfg = self.cfg
        t0 = time.perf_counter()
        self.gen = traffic_lib.FleetTraffic(cfg, self.mix, ctx.seed)
        ctx.setup_parts["traffic_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.fleet = Fleet(cfg)
        self.arb, self.allocs = self.fleet.arb, self.fleet.allocs
        self.names = self.fleet.names
        self._instrument()
        cache = ctx.cache_dir
        name = ctx.workload["name"]
        committed = BENCH / "shapes" / f"{name}.json"
        self.shapes = (ShapeLog(None) if cache is None else
                       ShapeLog(cache / f"shapes-{name}.json",
                                seed_from=committed))
        self.shapes.install()
        ctx.setup_parts["arbiter_s"] = time.perf_counter() - t0
        if self.shapes.path is not None and not self.shapes.entries:
            t0 = time.perf_counter()
            self._shadow(int(self.mix["shadow_rounds"]))
            ctx.setup_parts["shadow_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.observed: Dict[int, List[np.ndarray]] = defaultdict(list)
        from reference.fleet_ref import Replay
        self.replay = Replay(int(cfg["item_overhead"]))
        self.next_round = 0
        for _ in range(int(self.mix["warmup_rounds"])):
            schedules = self._schedules()
            ops = self._next_ops()
            self.fleet.round(ops, ctx.spans, self.observed)
            self.replay.apply(ops, traffic_lib.SET, schedules)
        self.fleet.sync()
        ctx.setup_parts["warmup_s"] = time.perf_counter() - t0
        # the window records nothing: shapes are met in set-up or not at
        # all, and a window that meets a new one says so in its compiles
        self.shapes.uninstall()
        self.shapes.save()
        t0 = time.perf_counter()
        n = self.shapes.preload()
        ctx.setup_parts["preload_s"] = time.perf_counter() - t0
        ctx.setup_parts["preloaded_shapes"] = n

    def _instrument(self) -> None:
        """Record every frontier the arbiter scores inside the window,
        with its scores, as the arbiter receives them."""
        import repro.core.arbiter as arbiter_mod
        cell = self
        batch, solo = arbiter_mod.score_requests, arbiter_mod._score_frontier

        # the scores are host arrays already (the controller syncs them
        # for its decision), so keeping a reference costs no device sync
        def score_requests(reqs):
            out = batch(reqs)
            if cell.recording:
                for r, s in zip(reqs, out):
                    cell.scored.append((r.rows, r.support, r.freqs,
                                        r.page_size, s))
            return out

        def score_frontier(rows, support, freqs, *, page_size):
            out = solo(rows, support, freqs, page_size=page_size)
            if cell.recording:
                cell.scored.append((rows, support, freqs, page_size, out))
            return out

        arbiter_mod.score_requests = score_requests
        arbiter_mod._score_frontier = score_frontier

        def restore():
            arbiter_mod.score_requests = batch
            arbiter_mod._score_frontier = solo
        self._restore = restore

    def _shadow(self, rounds: int) -> None:
        """Only where no shapes are recorded for the cell (neither under
        ``bench/shapes`` nor beside the compile cache): drive a second
        fleet, with the same traffic, through as many rounds as a window
        can reach, so that every program of data-dependent shape the
        window will launch is compiled, recorded, and cached before the
        window opens."""
        shadow = Fleet(self.cfg)
        gen = traffic_lib.FleetTraffic(self.cfg, self.mix, self.ctx.seed)
        for r in range(rounds):
            shadow.round(gen.round_ops(r), self.ctx.spans)
        shadow.sync()

    # -- one round -------------------------------------------------------------
    def _next_ops(self):
        ops = self.gen.round_ops(self.next_round)
        self.next_round += 1
        return ops

    def _hole_fraction(self) -> float:
        waste = alloc = 0
        for a in self.allocs:
            st = a.stats()
            waste += st.waste
            alloc += st.allocated_bytes
        return waste / max(alloc, 1)

    def _schedules(self) -> List[np.ndarray]:
        """Each tenant's slab classes as they stand: the classes the
        next round's sets are placed by (refits happen only in the
        arbiter tick that ends a round)."""
        return [a.chunk_sizes for a in self.allocs]

    def _refits(self) -> int:
        return sum(t.controller.n_refits for t in self.arb.tenants.values())

    # -- the window ------------------------------------------------------------
    def window(self, seconds: float) -> Dict:
        """Whole rounds until their own time reaches ``seconds``. The hole
        fraction is sampled, with the clock stopped, at the ends of the
        window's first ``hole_rounds`` rounds: the same stretch of traffic
        in every run, however many rounds the window's time holds."""
        self.recording = True
        refits0 = self._refits()
        transfers0 = self.arb.n_transfers
        self.timeline: List[tuple] = []     # (begin, end, served ops) per round
        self.holes: List[float] = []
        measured = 0.0
        schedules = self._schedules()
        with self.ctx.spans.span("generate"):
            ops = self._next_ops()
        self.round_ops: List[int] = []
        while True:
            begin = time.perf_counter()
            served = self.fleet.round(ops, self.ctx.spans, self.observed)
            end = time.perf_counter()
            last = measured + (end - begin) >= seconds
            self.timeline.append((begin, end, served))
            self.round_ops.append(len(ops))
            measured += end - begin
            with self.ctx.spans.span("sample"):
                if len(self.holes) < int(self.mix["hole_rounds"]):
                    self.holes.append(self._hole_fraction())
                self.replay.apply(ops, traffic_lib.SET, schedules)
                schedules = self._schedules()
            if last:
                break
            with self.ctx.spans.span("generate"):
                ops = self._next_ops()
        self.recording = False
        self._restore()
        self.window_s = measured
        self.n_refits = self._refits() - refits0
        self.n_transfers = self.arb.n_transfers - transfers0
        return {"alloc_ops_per_s": whole_round_rate(self.timeline),
                "hole_fraction": float(np.mean(self.holes))}

    def report(self, log) -> None:
        round_ms = np.asarray([(e - b) * 1e3 for b, e, _ in self.timeline])
        ops = sum(self.round_ops)
        served = sum(n for _, _, n in self.timeline)
        log(f"window: rounds={len(self.timeline)} ops={ops} "
            f"served={served} refused={ops - served} "
            f"refused_share={(ops - served) / max(ops, 1)} "
            f"window_s={self.window_s} refits={self.n_refits} "
            f"transfers={self.n_transfers} "
            f"frontiers_scored={len(self.scored)} "
            f"gate_launches={self.arb.n_gate_launches} "
            f"score_launches={self.arb.n_score_launches}")
        log(f"stalls: longest_round_ms={float(round_ms.max())} "
            f"rounds_over_{int(STALL_MS)}ms={int((round_ms > STALL_MS).sum())} "
            f"round_ms={[round(float(x), 1) for x in round_ms]}")

    def _frontier_sample(self) -> List[int]:
        """Frontiers to check, drawn from the seed (all of the window's
        when they are few)."""
        rng = traffic_lib.seed_rng(self.ctx.seed, "fleet-check")
        n_check = int(self.mix["check_frontiers"])
        return [int(i) for i in rng.permutation(len(self.scored))[:n_check]]

    def _frontier_err(self, pick, score) -> float:
        """Worst relative error of ``score(i, j, row)`` against the exact
        waste of row ``j`` of frontier ``i``."""
        from reference import fleet_ref
        rel = 0.0
        for i in pick:
            rows, support, freqs, page, _ = self.scored[i]
            for j, row in enumerate(rows):
                want = fleet_ref.waste_exact(row, support, freqs,
                                             page_size=page)
                rel = max(rel, abs(float(score(i, j, row)) - want)
                          / max(want, 1))
        return rel

    def attempted_failed(self):
        ops = sum(self.round_ops)
        return ops, ops - sum(n for _, _, n in self.timeline)

    # -- correctness ------------------------------------------------------------
    def check(self, control: bool = False) -> List[tuple]:
        """Numbers compared against their limits. With ``control`` the
        frontier scores compared are the control's: the same waste
        summed in bfloat16, put in the arbiter's place on the same
        frontiers."""
        from reference import fleet_ref
        cfg = self.cfg
        lim = self.ctx.limits
        buckets = int(cfg["sketch_buckets"])
        # device sketches against the counts of every size observed
        for name in self.names:
            self.arb.tenants[name].controller.sketch.flush_window()
        sketch = np.asarray(self.arb.fleet.sketch, dtype=np.float64)
        sketch_err = 0.0
        for t, arrs in self.observed.items():
            want = fleet_ref.sketch_counts(np.concatenate(arrs),
                                           buckets=buckets, width=1)
            row = self.arb.fleet.row_of[self.names[t]]
            sketch_err = max(sketch_err,
                             float(np.abs(sketch[row] - want).max()))
        # frontier scores against exact waste, on a sample drawn from the
        # seed (the whole window's frontiers when they are few)
        pick = self._frontier_sample()
        if control:
            def score(i, _j, row):
                _, support, freqs, page, _ = self.scored[i]
                return fleet_ref.waste_low(row, support, freqs,
                                           page_size=page)
        else:
            def score(i, j, _row):
                return self.scored[i][4][j]
        rel = self._frontier_err(pick, score)
        # residency, holes and pages, tenant by tenant
        bad_items = hole_diff = 0
        live = self.replay.live
        pages = 0
        for t, a in enumerate(self.allocs):
            holes = 0
            for cls in a.classes:
                for key, total in cls.lru.items():
                    if live.get(key) != (t, total, cls.chunk_size):
                        bad_items += 1
                    holes += cls.chunk_size - total
            hole_diff += abs(holes - a.stats().waste)
            pages += a.pages_allocated
            carved = sum(c.pages for c in a.classes) + a.free_pages
            if carved != a.pages_allocated:
                bad_items += 1
        pool = self.arb.pool
        page_err = abs(pages + pool.free_units - pool.total_units)
        self.ctx.log(f"reference: tenants={len(self.allocs)} "
                     f"frontiers_checked={len(pick)} of {len(self.scored)}")
        return [("frontier_rel_err", rel, lim["frontier_rel_err"]),
                ("sketch_count_err", sketch_err, 0),
                ("resident_item_errors", bad_items, 0),
                ("hole_bytes_err", hole_diff, 0),
                ("pages_not_conserved", page_err, 0)]


def memcached_classes(cfg: dict) -> np.ndarray:
    """memcached's slab classes: ``-n`` minimum chunk grown by ``-f``,
    8-byte aligned, up to half a page, then one page-sized class."""
    f = float(cfg["growth_factor"])
    page = int(cfg["page_size"])
    size, out = int(cfg["min_chunk"]), []
    while size <= page / 2:
        out.append(size)
        nxt = int(np.ceil(size * f))
        nxt += (-nxt) % 8
        size = max(nxt, size + 8)
    out.append(page)
    return np.asarray(out, dtype=np.int64)


def make(ctx):
    return FleetCell(ctx)
