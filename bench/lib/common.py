"""Pieces every cell shares: host spans, counters, quartiles, the peak
table and the device description."""
from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent


def process_age_s() -> float:
    """Seconds since this process was started by the kernel (Linux), so
    that set-up counts interpreter start and imports too."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])          # field 22 of stat, 1-based
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class Spans:
    """Host spans recorded by the benchmark around its calls into the
    program. With ``annotate`` each span is also written into the
    profiler's trace (``jax.profiler.TraceAnnotation``), so idle gaps on
    the device can be put against what the host was doing."""

    PREFIX = "bench:"

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: Dict[str, List[Tuple[int, int]]] = {}
        self._ann = None
        if annotate:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter_ns()
        if self._ann is None:
            try:
                yield
            finally:
                self.records.setdefault(name, []).append(
                    (t0, time.perf_counter_ns()))
            return
        with self._ann(self.PREFIX + name):
            try:
                yield
            finally:
                self.records.setdefault(name, []).append(
                    (t0, time.perf_counter_ns()))

    def durations_ms(self, name: str) -> List[float]:
        return [(b - a) / 1e6 for a, b in self.records.get(name, [])]


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def peak_for(device_kind: str) -> dict:
    """Published peaks of ``device_kind`` (``bench/peaks.json``); an
    unknown device is an error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to bench/peaks.json")
    return table[device_kind]


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class GcPauses:
    """Python's garbage-collector pauses while armed: how many of each
    generation, and the longest, so that a stall in the window can be
    told apart from one of the collector."""

    def __init__(self):
        import gc
        self.armed = False
        self.count = [0, 0, 0]
        self.total_s = 0.0
        self.longest_s = 0.0
        self._t0 = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dt = time.perf_counter() - self._t0
            self._t0 = None
            if self.armed:
                self.count[info["generation"]] += 1
                self.total_s += dt
                self.longest_s = max(self.longest_s, dt)


class CompileCounter:
    """Counts XLA backend compilations and persistent-cache hits while
    armed (``jax.monitoring`` events), so a run can say whether anything
    compiled inside its measured window."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_load_s = 0.0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if not self.armed:
            return
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += secs
        elif event == self.CACHE_LOAD:
            self.cache_load_s += secs

    def _event(self, event: str, **_kw) -> None:
        if self.armed and event == self.CACHE_HIT:
            self.cache_hits += 1
