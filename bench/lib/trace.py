"""Profiler trace of the measured window, reduced to plain lists.

A traced run wraps its window in the JAX profiler with the Python
tracer off. The ``.xplane.pb`` the profiler writes is reduced to a
small dict that the per-layer readers and the tests share:

    {"window": [start_ns, end_ns],
     "devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the events of a device's "XLA Ops" line, named by their HLO
instruction (``%while.13 = (...) while(...)`` becomes ``while.13``);
``modules`` those
of its "XLA Modules" line (one per program execution), and ``host`` the
benchmark's own spans (``bench:<name>`` annotations) on the host plane.
The window is the ``bench:window`` span.
"""
from __future__ import annotations

import bisect
import glob
import re
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
PREFIX = "bench:"
WINDOW = "window"
_NAME = re.compile(r"%?([^\s=]+)")


def start(log_dir: Path) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # annotations only, no runtime noise
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop(log_dir: Path) -> Path:
    import jax
    jax.profiler.stop_trace()
    found = sorted(glob.glob(str(log_dir / "plugins" / "profile" / "*" /
                                 "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    return Path(found[-1])


def short_name(hlo: str) -> str:
    """The instruction name of an HLO text line: ``%fusion.3 = ...``
    gives ``fusion.3``."""
    m = _NAME.match(hlo)
    return m.group(1) if m else hlo


def reduce_xplane(path: Path) -> dict:
    """The reduced dict of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = ("ops" if line.name == OPS_LINE else
                       "modules" if line.name == MODULES_LINE else None)
                if key is None:
                    continue
                dev[key].extend([short_name(e.name), int(e.start_ns),
                                 int(e.duration_ns)] for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append([e.name[len(PREFIX):], int(e.start_ns),
                                     int(e.duration_ns)])
    wins = [h for h in host if h[0] == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {PREFIX}{WINDOW} span in the "
                           f"trace, found {len(wins)}")
    _, w0, wd = wins[0]
    return {"window": [w0, w0 + wd], "devices": devices, "host": host}


# -- reductions on the reduced dict ---------------------------------------------

def _clip(events, lo: int, hi: int) -> List[Tuple[str, int, int]]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def merged_busy(ops, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Union of the op intervals inside ``[lo, hi)``."""
    spans = sorted((a, b) for _, a, b in _clip(ops, lo, hi))
    out: List[Tuple[int, int]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_seconds(trace: dict) -> float:
    """Seconds in which an op ran on the device, inside the window,
    averaged over the devices."""
    lo, hi = trace["window"]
    devs = trace["devices"].values()
    if not devs:
        return 0.0
    tot = 0
    for dev in devs:
        tot += sum(b - a for a, b in merged_busy(dev["ops"], lo, hi))
    return tot / len(trace["devices"]) / 1e9


def window_seconds(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def op_seconds(trace: dict) -> Dict[str, float]:
    """Device seconds per op name inside the window, summed over
    devices."""
    lo, hi = trace["window"]
    out: Dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, a, b in _clip(dev["ops"], lo, hi):
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def op_durations(trace: dict, prefix: str) -> List[float]:
    """Device seconds of each op inside the window whose name starts
    with ``prefix`` (one per launch)."""
    lo, hi = trace["window"]
    out = []
    for dev in trace["devices"].values():
        for name, s, d in dev["ops"]:
            if name.startswith(prefix) and lo <= s < hi:
                out.append(d / 1e9)
    return out


def module_runs(trace: dict) -> List[Tuple[float, List[str]]]:
    """``(seconds, op names)`` of each program execution that starts
    inside the window: the ops of a device that fall inside one module
    event belong to it."""
    lo, hi = trace["window"]
    out = []
    for dev in trace["devices"].values():
        ops = sorted(dev["ops"], key=lambda e: e[1])
        starts = [e[1] for e in ops]

        for _, s, d in dev["modules"]:
            if not lo <= s < hi:
                continue
            i = bisect.bisect_left(starts, s)
            names = []
            while i < len(ops) and ops[i][1] < s + d:
                names.append(ops[i][0])
                i += 1
            out.append((d / 1e9, names))
    return out


def idle_gaps(trace: dict) -> Dict[str, float]:
    """Idle device time inside the window, put against the innermost
    benchmark span on the host that covers each gap's midpoint
    (``between_spans`` when none does). Averaged over devices."""
    lo, hi = trace["window"]
    spans = [(s, s + d, name) for name, s, d in trace["host"]
             if name != WINDOW]
    out: Dict[str, float] = {}
    devs = list(trace["devices"].values())
    for dev in devs:
        busy = merged_busy(dev["ops"], lo, hi)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            cover = [(e - s, name) for s, e, name in spans if s <= mid < e]
            name = min(cover)[1] if cover else "between_spans"
            out[name] = out.get(name, 0.0) + (b - a) / 1e9 / len(devs)
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: dict) -> dict:
    return {"device_ops": top(op_seconds(trace)),
            "idle_gaps": top(idle_gaps(trace))}
