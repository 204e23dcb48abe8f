"""The program's own spans (``repro.analysis.spans``) inside the
measured window, and the device's idle time put against them.

The program records its spans on ``time.perf_counter_ns()``. The
benchmark records its ``window`` span on that clock
(``run.spans.records["window"]``) and, in a traced run, on the profiler
trace's clock (``run.trace["window"]``), so one offset puts the
program's spans on the trace's clock.

Every reader returns None where there is nothing sound to read: a
program without the recorder, a run without a window, a ring that has
overwritten spans of the window, or (for the trace's clock) two window
lengths more than a millisecond apart.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from lib import trace as trace_lib

OUTSIDE = "outside_program"
MAX_SKEW_NS = 1_000_000
SYNC = "sync:"

Span = Tuple[int, str, int, int, int]     # seq, name, start, end, parent


def _recorder():
    try:
        from repro.analysis.spans import RECORDER
    except ImportError:                   # a program without the recorder
        return None
    return RECORDER


def _window_ns(run) -> Optional[Tuple[int, int]]:
    wins = run.spans.records.get("window") if run.spans is not None else None
    return tuple(wins[0]) if wins else None


def in_window(run) -> Optional[List[Span]]:
    """``(seq, name, start_ns, end_ns, parent_seq)`` of every program
    span that overlaps the window, on the host clock, in the order they
    began."""
    rec, win = _recorder(), _window_ns(run)
    if rec is None or win is None or not rec.holds_since(win[0]):
        return None
    return rec.records(*win)


def to_trace_clock(run) -> Optional[int]:
    """Nanoseconds to add to a program span's times to put it on the
    trace's clock."""
    win = _window_ns(run)
    rec = _recorder()
    if run.trace is None or win is None or rec is None:
        return None
    t0, t1 = run.trace["window"]
    h0, h1 = win
    if abs((t1 - t0) - (h1 - h0)) > MAX_SKEW_NS or not rec.holds_since(h0):
        return None
    return t0 - h0


def _innermost(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """The time covered by ``spans``, cut into disjoint ``(start, end,
    name)`` pieces in time order, each named by the innermost span that
    covers it."""
    seqs = {s[0] for s in spans}
    children: Dict[int, List[Span]] = {}
    roots = []
    for s in spans:
        if s[4] in seqs:
            children.setdefault(s[4], []).append(s)
        else:
            roots.append(s)
    out: List[Tuple[int, int, str]] = []

    def walk(s: Span) -> None:
        seq, name, start, end, _ = s
        at = start
        for c in children.get(seq, ()):
            if c[2] > at:
                out.append((at, c[2], name))
            walk(c)
            at = max(at, c[3])
        if end > at:
            out.append((at, end, name))

    for r in roots:
        walk(r)
    return out


def _idle(busy: List[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def idle_by_span(run) -> Optional[Dict[str, float]]:
    """Device-idle seconds in the window by the innermost program span
    the host was in, ``outside_program`` for the rest; averaged over
    the devices."""
    off, spans = to_trace_clock(run), in_window(run)
    if off is None or spans is None or not run.trace["devices"]:
        return None
    lo, hi = run.trace["window"]
    pieces = [(max(a + off, lo), min(b + off, hi), name)
              for a, b, name in _innermost(spans)]
    pieces = [p for p in pieces if p[1] > p[0]]
    devs = list(run.trace["devices"].values())
    out: Dict[str, float] = {OUTSIDE: 0.0}
    for dev in devs:
        idle = _idle(trace_lib.merged_busy(dev["ops"], lo, hi), lo, hi)
        inside = 0
        j = 0
        for a, b in idle:
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                s, e, name = pieces[k]
                ns = min(b, e) - max(a, s)
                out[name] = out.get(name, 0.0) + ns / 1e9 / len(devs)
                inside += ns
                k += 1
        total = sum(b - a for a, b in idle)
        out[OUTSIDE] += (total - inside) / 1e9 / len(devs)
    return out


def _sum_ns(spans: List[Span], names: Iterable[str]) -> int:
    names = set(names)
    return sum(e - s for _, n, s, e, _ in spans if n in names)


def _has(spans: List[Span], name: str) -> bool:
    return any(s[1] == name for s in spans)


def _fleet_rounds(run) -> Optional[Tuple[List[Span], int]]:
    """The window's spans and its rounds in a fleet cell whose program
    records ``arbiter.tick``."""
    if run.config.get("kind") != "fleet":
        return None
    spans = in_window(run)
    rounds = len(getattr(run.cell, "timeline", ()))
    if spans is None or not rounds or not _has(spans, "arbiter.tick"):
        return None
    return spans, rounds


def per_round_ms(run, names: Iterable[str]) -> Optional[float]:
    """Host ms per fleet round inside the named spans."""
    found = _fleet_rounds(run)
    if found is None:
        return None
    spans, rounds = found
    return _sum_ns(spans, names) / 1e6 / rounds


def sync_under_tick_ms(run) -> Optional[float]:
    """Host ms per fleet round inside ``sync:`` spans (readbacks) that
    lie under ``arbiter.tick``, each counted once however deep."""
    found = _fleet_rounds(run)
    if found is None:
        return None
    spans, rounds = found
    by_seq = {s[0]: s for s in spans}
    ns = 0
    for _, name, start, end, parent in spans:
        if not name.startswith(SYNC):
            continue
        under_tick = False
        while parent in by_seq:
            p = by_seq[parent]
            if p[1].startswith(SYNC):     # counted with the outer sync
                under_tick = False
                break
            under_tick = under_tick or p[1] == "arbiter.tick"
            parent = p[4]
        if under_tick:
            ns += end - start
    return ns / 1e6 / rounds


def _serving_ticks(run, closed: bool) -> Optional[int]:
    cell = run.cell
    if run.config.get("kind") != "serving" or \
            getattr(cell, "closed", None) is not closed:
        return None
    return getattr(cell, "ticks", 0) or None


def per_tick_ms(run, names: Iterable[str], *, closed: bool
                ) -> Optional[float]:
    """Host ms per serving tick inside the named spans, in a closed
    (``closed``) or open mix; None in the other mix or where the
    program records no ``harness.tick``."""
    ticks = _serving_ticks(run, closed)
    spans = in_window(run) if ticks else None
    if spans is None or not _has(spans, "harness.tick"):
        return None
    return _sum_ns(spans, names) / 1e6 / ticks


def idle_per_tick_ms(run, names: Iterable[str], *, closed: bool
                     ) -> Optional[float]:
    """Device-idle ms per serving tick whose innermost program span is
    one of ``names``."""
    ticks = _serving_ticks(run, closed)
    spans = in_window(run) if ticks else None
    if spans is None or not _has(spans, "harness.tick"):
        return None
    idle = idle_by_span(run)
    if idle is None:
        return None
    return 1e3 * sum(idle.get(n, 0.0) for n in names) / ticks
