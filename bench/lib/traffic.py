"""The one traffic generator: every length, gap and lifetime comes from
a fixed stratified table, and the seed only orders the table inside
each block and salts the content.

A stratified table of ``k`` values from a distribution is its inverse
CDF at ``(i + 0.5) / k`` for ``i`` in ``0..k-1``. The stream repeats the
table in blocks of ``k``; the seed shuffles the order inside each block.
So every window holds the same mix of sizes, to within one block,
whatever the seed, while the order, the ids and every token compared
still follow the seed.

Two kinds of mix, both read from a traffic file:

* ``serving``: (prompt, output) length pairs and, for open-loop mixes,
  Poisson gaps between arrivals, in seconds.
* ``fleet``: rounds of memcached sets and TTL deletes for N tenants whose
  arrival intensities peak out of phase. Here the block is one round:
  each tenant's sets and deletes in round ``r``, sizes and order, are
  fixed by the file and the configuration, and the seed interleaves the
  tenants and salts the keys. Tenants share nothing but the page pool,
  whose quotas add up to it, so every allocator sees the same sequence
  of its own ops under every seed.
"""
from __future__ import annotations

import hashlib
import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

_NORMAL = NormalDist()


def quantile(dist: dict, p: float) -> float:
    """Inverse CDF of a distribution given as a traffic-file dict."""
    kind = dist["dist"]
    if kind == "lognormal":
        # byte- or token-space median and log-space sigma
        return dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(p))
    if kind == "lognormal_moments":
        # byte-space mean and standard deviation (the paper's tables)
        var_ratio = (dist["std"] / dist["mean"]) ** 2
        sigma = math.sqrt(math.log1p(var_ratio))
        mu = math.log(dist["mean"]) - 0.5 * sigma * sigma
        return math.exp(mu + sigma * _NORMAL.inv_cdf(p))
    if kind == "exponential":
        return -math.log1p(-p) * dist["mean"]
    if kind == "uniform":
        return dist["low"] + p * (dist["high"] - dist["low"])
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: dict, k: int) -> np.ndarray:
    """``k`` values at the quantiles ``(i + 0.5) / k``, clipped to the
    distribution's ``min``/``max`` when it has them."""
    vals = np.array([quantile(dist, (i + 0.5) / k) for i in range(k)])
    lo, hi = dist.get("min"), dist.get("max")
    if lo is not None or hi is not None:
        vals = np.clip(vals, lo, hi)
    return vals


def seed_rng(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of one seed; seeds may be any
    non-negative integer, larger than 32 bits included."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    words = [int(seed) >> (32 * i) & 0xFFFFFFFF for i in range(4)]
    digest = hashlib.sha256(stream.encode()).digest()
    words += [int.from_bytes(digest[i:i + 4], "little") for i in (0, 4)]
    return np.random.default_rng(np.random.SeedSequence(words))


def blocked_order(k: int, n_blocks: int, rng: np.random.Generator
                  ) -> np.ndarray:
    """Indices into a table of ``k`` for ``n_blocks`` blocks, each block
    a fresh permutation of the whole table."""
    return np.concatenate([rng.permutation(k) for _ in range(n_blocks)])


# -- serving ----------------------------------------------------------------

def serving_table(mix: dict) -> List[Tuple[int, int]]:
    """The fixed (prompt, output) pairs of one block. Prompt and output
    tables are stratified apart and paired by a fixed permutation drawn
    from the file's ``pairing_seed``, so the pairs do not depend on the
    run's seed."""
    k = int(mix["block"])
    prompts = np.rint(stratified(mix["prompt"], k)).astype(int)
    outputs = np.rint(stratified(mix["output"], k)).astype(int)
    pair = np.random.default_rng(int(mix["pairing_seed"])).permutation(k)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs[pair])]


def serving_requests(mix: dict, seed: int) -> List[Dict]:
    """``mix["requests"]`` requests: dicts of ``rid``, ``prompt_len``,
    ``output_len`` and ``due_s`` (arrival time in seconds from the start
    of the window; 0 for a closed mix, where everything is queued)."""
    table = serving_table(mix)
    k = len(table)
    n = int(mix["requests"])
    n_blocks = -(-n // k)
    rng = seed_rng(seed, "serving-order")
    order = blocked_order(k, n_blocks, rng)[:n]
    arrival = mix["arrival"]
    if arrival["kind"] == "closed":
        due = np.zeros(n)
    elif arrival["kind"] == "poisson":
        gaps = stratified({"dist": "exponential",
                           "mean": 1.0 / float(arrival["rate_per_s"])}, k)
        g_order = blocked_order(k, n_blocks, seed_rng(seed, "serving-gaps"))
        due = np.cumsum(gaps[g_order[:n]])
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    # request ids salt the hashed KV and query content; they stay below
    # 2**23 so that the program's float32 copy of an id is exact
    base = int(seed_rng(seed, "serving-rid").integers(1, 1 << 22))
    return [{"rid": base + i, "prompt_len": table[j][0],
             "output_len": table[j][1], "due_s": float(due[i])}
            for i, j in enumerate(order)]


# -- memcached fleet -----------------------------------------------------------

SET, DELETE = 0, 1


class FleetTraffic:
    """Rounds of memcached traffic for ``n`` tenants.

    Tenant ``t`` serves operating point ``t % P`` of the configuration's
    ``operating_points`` and peaks at phase ``t / n`` of a raised cosine
    of ``period_rounds`` rounds. In round ``r`` it receives
    ``round(sets_per_tenant_round * I(t, r) / mean(I))`` sets, of which
    a share ``trough_mix * troughness(t, r)`` takes its sizes from the
    next operating point (drift), the rest from its own. Sizes and TTLs
    come from stratified tables of ``table_size`` values walked with a
    fixed odd stride, a per-tenant counter choosing the position: fixed
    for every seed. Each item is deleted ``ceil(ttl)`` rounds after its
    set.
    """

    def __init__(self, config: dict, mix: dict, seed: int):
        self.n = int(config["tenants"])
        points = config["operating_points"]
        self.n_points = len(points)
        k = int(mix["table_size"])
        self.k = k
        self.stride = int(mix["table_stride"])
        if math.gcd(self.stride, k) != 1:
            raise ValueError("table_stride must be coprime to table_size")
        self.size_tables = [np.rint(stratified(
            {"dist": "lognormal_moments", "mean": p["mean"], "std": p["std"],
             "min": 1, "max": int(config["page_size"])}, k)).astype(np.int64)
            for p in points]
        self.ttl_table = stratified(
            {"dist": "uniform", "low": mix["ttl_rounds"][0],
             "high": mix["ttl_rounds"][1]}, k)
        self.sets_per_round = float(mix["sets_per_tenant_round"])
        self.period = float(mix["period_rounds"])
        self.base_rate = float(mix["base_rate"])
        self.trough_mix = float(mix["trough_mix"])
        self.order_seed = int(mix["order_seed"])
        self.seed = int(seed)
        self.salt = int(seed_rng(seed, "fleet-keys").integers(1 << 40))
        self._used = np.zeros(self.n, dtype=np.int64)      # sets so far
        self._alt_used = np.zeros(self.n, dtype=np.int64)
        self._offset = (np.arange(self.n, dtype=np.int64) * 7919) % k
        self._deletes: Dict[int, List[Tuple[int, str]]] = {}
        self._next_round = 0

    def intensity(self, r: int) -> np.ndarray:
        phase = np.arange(self.n) / self.n
        cosarg = 2.0 * np.pi * (r / self.period - phase)
        inten = self.base_rate + (1 - self.base_rate) * 0.5 * (
            1 - np.cos(cosarg))
        trough = 0.5 * (1.0 + np.cos(cosarg))
        return inten, trough

    def _draw(self, table: np.ndarray, start: np.ndarray, count: int,
              t: int) -> np.ndarray:
        idx = (self._offset[t] + (start + np.arange(count)) * self.stride
               ) % self.k
        return table[idx]

    def round_ops(self, r: int) -> List[Tuple[int, int, str, int]]:
        """The ops of round ``r`` as ``(op, tenant, key, size)`` tuples
        (size 0 for deletes), in the seed's order. Rounds are generated
        in sequence."""
        if r != self._next_round:
            raise ValueError(f"round {r} asked before round "
                             f"{self._next_round}")
        self._next_round += 1
        inten, trough = self.intensity(r)
        mean_i = self.base_rate + (1 - self.base_rate) * 0.5
        n_sets = np.rint(self.sets_per_round * inten / mean_i
                         ).astype(np.int64)
        n_alt = np.rint(n_sets * self.trough_mix * trough).astype(np.int64)
        deletes: Dict[int, List[str]] = {}
        for t, key in self._deletes.pop(r, ()):
            deletes.setdefault(t, []).append(key)
        per_tenant: List[List[Tuple[int, int, str, int]]] = []
        for t in range(self.n):
            own = self.size_tables[t % self.n_points]
            alt = self.size_tables[(t + 1) % self.n_points]
            m, a = int(n_sets[t]), int(n_alt[t])
            sizes = np.concatenate([
                self._draw(own, self._used[t], m - a, t),
                self._draw(alt, self._alt_used[t], a, t)])
            ttls = self._draw(self.ttl_table,
                              self._used[t] + self._alt_used[t], m, t)
            base = int(self._used[t] + self._alt_used[t])
            self._used[t] += m - a
            self._alt_used[t] += a
            mine = [(DELETE, t, key, 0) for key in deletes.get(t, ())]
            for j in range(m):
                key = f"{self.salt:x}:{t}:{base + j}"
                mine.append((SET, t, key, int(sizes[j])))
                self._deletes.setdefault(
                    r + max(1, math.ceil(ttls[j])), []).append((t, key))
            # a tenant's own ops come in an order fixed by the file, so
            # that each allocator sees the same sequence under every seed
            fixed = np.random.default_rng([self.order_seed, r, t])
            per_tenant.append([mine[i] for i in fixed.permutation(len(mine))])
        # the seed interleaves the tenants
        labels = np.repeat(np.arange(self.n),
                           [len(ops) for ops in per_tenant])
        seed_rng(self.seed, f"fleet-order-{r}").shuffle(labels)
        nxt = [0] * self.n
        out = []
        for t in labels.tolist():
            out.append(per_tenant[t][nxt[t]])
            nxt[t] += 1
        return out
