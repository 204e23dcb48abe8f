"""Plain reference for the memcached fleet cells, written from the
semantics in straightforward numpy. It imports nothing of the program.

* Waste of a slab-class schedule over a size histogram (the paper's
  objective): an item of size ``s`` takes the smallest chunk ``c >= s``
  and wastes ``c - s``; a size no chunk covers is charged whole pages,
  ``max(1, ceil(s / page)) * page - s``. Exact, in int64.
* A tenant's undecayed size sketch: the count of every observed size,
  in bucket ``clip(ceil(s / width) - 1, 0, buckets - 1)``.
* Residency: replaying the sets and deletes, an item may be resident
  only if it was set and not deleted since, and then with the size of
  that set plus the per-item overhead, in the smallest class of its
  tenant at the time of the set that holds it; its hole is that class's
  chunk minus its size.
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple

import numpy as np


def waste_exact(chunks, support, freqs, *, page_size: int) -> int:
    chunks = np.sort(np.asarray(chunks, dtype=np.int64))
    support = np.asarray(support, dtype=np.int64)
    freqs = np.rint(np.asarray(freqs, dtype=np.float64)).astype(np.int64)
    total = 0
    for s, f in zip(support.tolist(), freqs.tolist()):
        if f == 0:
            continue
        i = int(np.searchsorted(chunks, s, side="left"))
        if i < len(chunks):
            total += (int(chunks[i]) - s) * f
        else:
            pages = max(1, -(-s // page_size))
            total += (pages * page_size - s) * f
    return total


def waste_low(chunks, support, freqs, *, page_size: int) -> float:
    """The control: the same waste accumulated in bfloat16."""
    import jax.numpy as jnp
    chunks = np.sort(np.asarray(chunks, dtype=np.int64))
    support = np.asarray(support, dtype=np.int64)
    idx = np.searchsorted(chunks, support, side="left")
    storable = idx < len(chunks)
    assigned = chunks[np.minimum(idx, len(chunks) - 1)]
    pages = np.maximum(-(-support // page_size), 1)
    per = np.where(storable, assigned - support, pages * page_size - support)
    prod = (jnp.asarray(per, jnp.bfloat16)
            * jnp.asarray(freqs, jnp.bfloat16))
    return float(jnp.sum(prod, dtype=jnp.bfloat16))


def sketch_counts(sizes: np.ndarray, *, buckets: int, width: int
                  ) -> np.ndarray:
    idx = np.clip(-(-np.asarray(sizes, np.int64) // width) - 1, 0,
                  buckets - 1)
    return np.bincount(idx, minlength=buckets).astype(np.float64)


class Replay:
    """Where every live key may be, from the op stream alone: its
    tenant, its stored size, and the class it went to, the smallest of
    the tenant's classes at the time of its set that holds it (a refit
    leaves resident items in the classes that survive it)."""

    def __init__(self, overhead: int):
        self.overhead = overhead
        self.live: Dict[str, Tuple[int, int, int]] = {}

    def apply(self, ops: Iterable[Tuple[int, int, str, int]],
              set_code: int, schedules: Sequence[np.ndarray]) -> None:
        live = self.live
        for op, tenant, key, size in ops:
            if op == set_code:
                total = size + self.overhead
                live[key] = (tenant, total,
                             smallest_class(schedules[tenant], total))
            else:
                live.pop(key, None)


def smallest_class(chunks: np.ndarray, total: int) -> int:
    i = int(np.searchsorted(chunks, total, side="left"))
    return int(chunks[i]) if i < len(chunks) else -1
