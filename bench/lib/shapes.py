"""Programs whose shapes follow the data, loaded before the window.

The fleet's arbiter launches programs shaped by what a round brings:
the batched drift gate over however many tenants came due (with the
stacks of their sketches), the frontier scorers over however many
candidate rows and sizes, and the observe windows over however many
batches a tenant buffered. Each new shape compiles, or loads from the
persistent cache, where it is first met. ``ShapeLog`` records every such
shape a run's set-up meets, keeps the union in a file beside the
compile cache, and each run's set-up launches every recorded shape once
on zeros, so that its window meets only programs it already holds. The
shapes a cell's window reaches are committed under ``bench/shapes``
(recorded on the chip), so that every run pays the same set-up.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import List, Optional


def _spec(a) -> list:
    """Shape, dtype and whether the value already lives on the device."""
    import jax
    import numpy as np
    return [list(np.shape(a)), np.dtype(a.dtype).str,
            isinstance(a, jax.Array)]


def _zeros(spec):
    import jax.numpy as jnp
    import numpy as np
    shape, dtype, on_device = spec
    z = np.zeros(shape, dtype=np.dtype(dtype))
    return jnp.asarray(z) if on_device else z


class ShapeLog:
    """``path`` is the writable record beside the compile cache;
    ``seed_from`` a committed record read as well."""

    def __init__(self, path: Optional[Path], seed_from: Optional[Path] = None):
        self.path = path
        self.entries: List[list] = []
        self._seen = set()
        for p in (seed_from, path):
            if p is not None and p.exists():
                for e in json.loads(p.read_text()):
                    self._add(e)
        self._undo = []

    def _add(self, entry: list) -> None:
        key = json.dumps(entry)
        if key not in self._seen:
            self._seen.add(key)
            self.entries.append(entry)

    def install(self) -> None:
        """Wrap the launchers the program looks up at call time so that
        each call's shapes are recorded."""
        import repro.core.observe as observe_mod
        import repro.kernels.fleet_gate as gate_mod
        import repro.kernels.ops as ops_mod
        log = self
        gate, fleet, solo = (gate_mod.drift_gate_fleet,
                             ops_mod.waste_eval_fleet, ops_mod.waste_eval)
        flush = observe_mod._window_flush_fn

        def window_flush_fn(*key):
            fn = flush(*key)

            def run(*args):
                log._add(["flush", list(key), [_spec(a) for a in args]])
                return fn(*args)
            return run

        def drift_gate_fleet(refs, sketches, *, metric="l1"):
            log._add(["gate", list(refs.shape), metric])
            return gate(refs, sketches, metric=metric)

        def waste_eval_fleet(chunks, supports, freqs, *, page_size,
                             interpret=None):
            log._add(["fleet_scores", _spec(chunks), _spec(supports),
                      _spec(freqs), page_size])
            return fleet(chunks, supports, freqs, page_size=page_size,
                         interpret=interpret)

        def waste_eval(chunks, support, freqs, *, page_size,
                       interpret=None):
            log._add(["scores", _spec(chunks), _spec(support),
                      _spec(freqs), page_size])
            return solo(chunks, support, freqs, page_size=page_size,
                        interpret=interpret)

        gate_mod.drift_gate_fleet = drift_gate_fleet
        ops_mod.waste_eval_fleet = waste_eval_fleet
        ops_mod.waste_eval = waste_eval
        observe_mod._window_flush_fn = window_flush_fn
        self._undo = [(gate_mod, "drift_gate_fleet", gate),
                      (ops_mod, "waste_eval_fleet", fleet),
                      (ops_mod, "waste_eval", solo),
                      (observe_mod, "_window_flush_fn", flush)]

    def uninstall(self) -> None:
        for mod, name, fn in self._undo:
            setattr(mod, name, fn)
        self._undo = []

    def preload(self) -> int:
        """Launch every recorded shape once on zeros; returns how many."""
        import jax
        import jax.numpy as jnp
        import repro.core.observe as observe_mod
        import repro.kernels.fleet_gate as gate_mod
        import repro.kernels.ops as ops_mod
        outs = []
        for e in self.entries:
            if e[0] == "flush":
                fn = observe_mod._window_flush_fn(*e[1])
                outs.append(fn(*[_zeros(a) for a in e[2]]))
            elif e[0] == "gate":
                (n, buckets), metric = e[1], e[2]
                row = jnp.zeros(buckets, jnp.float32)
                refs = jnp.stack([row] * n)
                live = jnp.stack([row] * n)
                outs.append(gate_mod.drift_gate_fleet(refs, live,
                                                      metric=metric))
            else:
                fn = (ops_mod.waste_eval_fleet if e[0] == "fleet_scores"
                      else ops_mod.waste_eval)
                outs.append(fn(*[_zeros(a) for a in e[1:4]],
                               page_size=e[4]))
        jax.block_until_ready(outs)
        return len(outs)

    def save(self) -> None:
        if self.path is None:
            return
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_suffix(".tmp")
        tmp.write_text(json.dumps(self.entries))
        os.replace(tmp, self.path)
