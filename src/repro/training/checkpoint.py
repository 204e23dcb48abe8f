"""Fault-tolerant checkpointing: atomic sharded writes, async, resharding.

Design (production posture, dependency-free):
  * one ``step_NNNNNNNN/`` directory per checkpoint,
  * each pytree leaf saved as its own .npy (device_get'd shard-merged),
    with a JSON manifest (treedef, shapes, dtypes, step, wall-time),
  * writes go to ``<dir>.tmp`` then os.rename — a crashed writer can
    never leave a half-checkpoint that restore would pick up,
  * an async writer thread moves serialization off the step path
    (``save(..., blocking=False)``), with ``wait()`` to join before the
    next save (single-writer discipline),
  * restore targets *any* mesh: leaves land as host arrays and are
    re-placed with jax.device_put against the new sharding
    (elastic restart after topology change — see elastic.py),
  * retention: keep the newest ``keep`` checkpoints, delete the rest.
"""
from __future__ import annotations

import json
import os

import jax.numpy as jnp
import shutil
import threading
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

_MANIFEST = "manifest.json"


def _leaf_paths(tree: Any) -> Dict[str, Any]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p, simple=True, separator="."): l
            for p, l in flat}


class CheckpointManager:
    def __init__(self, directory: str, *, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None

    # -- save ---------------------------------------------------------------
    def save(self, step: int, tree: Any, *, blocking: bool = True,
             extra: Optional[Dict[str, Any]] = None) -> str:
        """Serialize ``tree`` at ``step``. Non-blocking mode device_gets
        synchronously (cheap, avoids racing the next update) and writes
        files on a background thread."""
        self.wait()
        host_leaves = {}
        for k, v in _leaf_paths(tree).items():
            arr = np.asarray(jax.device_get(v))
            if arr.dtype.kind not in "biufc":  # bf16 etc: np.load can't
                arr = arr.astype(np.float32)   # read it back; widen on
            host_leaves[k] = arr               # disk, re-narrow on restore
        treedef = jax.tree_util.tree_structure(tree)
        manifest = {
            "step": int(step),
            "time": time.time(),
            "treedef": str(treedef),
            "leaves": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                       for k, v in host_leaves.items()},
            "extra": extra or {},
        }
        final = self._step_dir(step)

        def write():
            tmp = final + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            for k, v in host_leaves.items():
                np.save(os.path.join(tmp, k + ".npy"), v)
            with open(os.path.join(tmp, _MANIFEST), "w") as f:
                json.dump(manifest, f, indent=1)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)           # atomic publish
            self._gc()

        if blocking:
            write()
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()
        return final

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    # -- restore ------------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        steps = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and not name.endswith(".tmp") \
                    and os.path.exists(os.path.join(self.directory, name,
                                                    _MANIFEST)):
                steps.append(int(name[5:]))
        return max(steps) if steps else None

    def restore(self, template: Any, *, step: Optional[int] = None,
                shardings: Any = None) -> Any:
        """Rebuild ``template``-shaped pytree from disk. ``shardings``
        (optional pytree of NamedSharding) re-places leaves onto the
        *current* mesh — which may differ from the saving mesh."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoints under {self.directory}")
        d = self._step_dir(step)
        names = list(_leaf_paths(template))
        host = {}
        for k in names:
            host[k] = np.load(os.path.join(d, k + ".npy"))
        leaves_t, treedef = jax.tree_util.tree_flatten(template)
        flat_names = list(_leaf_paths(template))
        new_leaves = []
        for name, tleaf in zip(flat_names, leaves_t):
            arr = host[name]
            if tuple(arr.shape) != tuple(tleaf.shape):
                raise ValueError(
                    f"{name}: checkpoint shape {arr.shape} != template "
                    f"{tleaf.shape}")
            if arr.dtype != tleaf.dtype:  # jnp casts cover bf16 & friends
                arr = np.asarray(jnp.asarray(arr).astype(tleaf.dtype))
            new_leaves.append(arr)
        tree = jax.tree_util.tree_unflatten(treedef, new_leaves)
        if shardings is not None:
            tree = jax.tree.map(jax.device_put, tree, shardings)
        return tree

    # -- internals ----------------------------------------------------------
    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:08d}")

    def _gc(self) -> None:
        steps = sorted(
            int(n[5:]) for n in os.listdir(self.directory)
            if n.startswith("step_") and not n.endswith(".tmp"))
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
