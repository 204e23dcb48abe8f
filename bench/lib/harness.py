"""One run of one cell: set up, measure, check, print one result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the names in
``BENCHMARK.json``:

* ``configs[].file`` names the configuration's JSON; its ``kind`` picks
  the runner ``bench/kinds/<kind>.py``, and its ``check_limits`` hold
  the limits of the numbers that decide ``correct``;
* a workload's ``traffic`` names ``bench/traffic/<traffic>.json``;
* a per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``,
  whose ``read(run)`` returns a number or None when it finds nothing.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

from lib.common import (BENCH, ROOT, CompileCounter, GcPauses, Spans,
                        memory_peak_bytes, peak_for, process_age_s)

CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".traces"


class Run:
    """What a cell and the metric readers share during one run."""

    def __init__(self, *, workload: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, err):
        self.workload = workload
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.traced = trace
        self.limits = config["check_limits"]
        self.spans = Spans(annotate=trace)
        self.setup_parts: Dict[str, float] = {}
        self.trace: Optional[dict] = None
        self.cache_dir: Optional[Path] = None
        self.cell = None
        self.peaks: dict = {}
        self._err = err

    def log(self, msg: str) -> None:
        print(msg, file=self._err, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, workload: dict, traced: bool) -> List[dict]:
    """The metrics a cell reports: its end-to-end metrics untraced, the
    per-layer metrics that read it (or move one of its end-to-end
    metrics) traced."""
    name = workload["name"]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    if not traced:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def read_per_layer(metric: str, run: Run) -> Optional[float]:
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def configure_jax() -> None:
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, *, require_accelerator: bool = True,
         doc_root: Path = ROOT, use_cache: bool = True, control: bool = False,
         out=None, err=None) -> int:
    """``doc_root`` holds ``BENCHMARK.json``, the configuration files and
    ``bench/traffic``; the program always comes from this checkout's
    ``src``. Tests pass a small document root, no accelerator check and
    no persistent cache. ``control`` puts the cell's control, the
    reference in the next lower precision, in the program's place on the
    same sample, and the comparison then decides ``correct`` from its
    readings (``bench/control.py``)."""
    out = out or sys.stdout
    err = err or sys.stderr
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program at {ROOT / 'src' / 'repro'}",
              file=err)
        return 2
    bench = load_json(doc_root / "BENCHMARK.json")
    wl = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], wl["config"], "config")
    config = load_json(doc_root / cfg_entry["file"])
    traffic = load_json(doc_root / "bench" / "traffic"
                        / f"{wl['traffic']}.json")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))

    import jax
    if use_cache:
        configure_jax()
    devices = jax.devices()
    if require_accelerator and (devices[0].platform == "cpu"
                                or len(devices) < int(wl["chips"])):
        print(f"bench: the cell needs {wl['chips']} accelerator chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=err)
        return 3
    used = devices[:int(wl["chips"])]
    run = Run(workload=wl, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), err=err)
    if use_cache:
        run.cache_dir = CACHE_DIR
    if require_accelerator:
        run.peaks = peak_for(devices[0].device_kind)
    kind = importlib.import_module(f"kinds.{config['kind']}")
    cell = kind.make(run)
    run.cell = cell
    counter = CompileCounter()
    gc_pauses = GcPauses()
    t0 = time.perf_counter()
    cell.setup()
    run.setup_parts["setup_calls_s"] = time.perf_counter() - t0
    setup_s = process_age_s()
    run.setup_parts["before_setup_s"] = (setup_s
                                         - run.setup_parts["setup_calls_s"])

    trace_dir = TRACE_DIR / f"{wl['name']}-{args.seed}"
    if run.traced:
        from lib import trace as trace_lib
        trace_lib.start(trace_dir)
    run.spans.records.clear()
    run.log("window opens")
    counter.armed = gc_pauses.armed = True
    with run.spans.span("window"):
        e2e = cell.window(args.seconds)
    counter.armed = gc_pauses.armed = False
    run.log("window closed")
    if run.traced:
        run.trace = trace_lib.reduce_xplane(trace_lib.stop(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem = memory_peak_bytes(used)

    cell.report(run.log)
    run.log("setup: " + " ".join(f"{k}={v}" for k, v in
                                 sorted(run.setup_parts.items()))
            + f" setup_s={setup_s}")
    run.log(f"window compiles: backend_compiles={counter.compiles} "
            f"compile_s={counter.compile_s} "
            f"persistent_cache_hits={counter.cache_hits} "
            f"cache_load_s={counter.cache_load_s}")
    run.log(f"window gc: collections_by_generation={gc_pauses.count} "
            f"total_s={gc_pauses.total_s} longest_s={gc_pauses.longest_s}")

    metrics: Dict[str, dict] = {}
    for m in cell_metrics(bench, wl, run.traced):
        if not run.traced:
            value = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if value is None:
                raise RuntimeError(f"cell {wl['name']} did not measure "
                                   f"{m['name']}")
        else:
            value = read_per_layer(m["name"], run)
            if value is None:
                continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t0 = time.perf_counter()
    checks = cell.check(control=control)
    run.log(f"reference: seconds={time.perf_counter() - t0}")
    correct = all(value <= limit for _, value, limit in checks)
    attempted, failed = cell.attempted_failed()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.traced:
        from lib import trace as trace_lib
        device["busy_s"] = trace_lib.busy_seconds(run.trace)
        device["window_s"] = trace_lib.window_seconds(run.trace)
        result["breakdown"] = trace_lib.breakdown(run.trace)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    if control:
        run.log("control: the compared numbers below are the control's")
    for name, value, limit in checks:
        run.log(f"check {name}: {value} (limit {limit})")
    print(json.dumps(result), file=out, flush=True)
    return 0
