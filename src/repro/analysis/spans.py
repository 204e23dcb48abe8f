"""Always-on span recorder: where the host's time goes inside a tick.

``with span("arbiter.gate"):`` records one span: a name id, its start
and end on ``time.perf_counter_ns()``, and the sequence number of the
span that encloses it (-1 at the top), so a reader can tell which span
caused which. Records go into a fixed ring of :data:`CAPACITY` spans
held in ``array('q')`` columns (8 MiB), so recording keeps no Python
object per span, adds nothing for the garbage collector to scan, and
its memory is bounded. A reader learns from :attr:`Recorder.dropped`
and :meth:`Recorder.holds_since` whether the ring has overwritten spans
it asks for.

Beside the ring, per-name totals (count and nanoseconds) never wrap:
:func:`totals` is the operator's view, like memcached's ``stats``.
:func:`records` returns the spans of an interval.

The recorder is always on: there is no flag and no "off" branch, it
writes no file, and it stays out of the profiler (a
``jax.profiler.TraceAnnotation`` costs about a microsecond even with no
trace running). A reader puts the spans on a device trace's clock with
one offset, taken from a span recorded on both clocks.

Spans are recorded from the one thread that drives the program (the
serving loop or the arbiter's caller): nesting is kept on a single
stack, and a span opened on another thread would be taken for a child
of whatever that thread interrupted. Spans sit at layer boundaries, not
around per-item calls such as an allocator op or an ``observe``, whose
few microseconds a span would perturb. This module is stdlib-only.
"""
from __future__ import annotations

from array import array
from time import perf_counter_ns
from typing import Dict, List, Tuple

__all__ = ["CAPACITY", "Recorder", "RECORDER", "span", "totals", "records"]

CAPACITY = 1 << 18


def _span_class(rec: "Recorder", capacity: int):
    """The span class of one recorder. Its methods read the ring's
    columns and stacks from closure cells rather than attributes: a
    span costs two clock reads and a few array stores."""
    mask = capacity - 1
    names, starts, ends, parents = rec._name, rec._start, rec._end, rec._parent
    count, total_ns = rec._count, rec._total_ns
    stack = rec._stack            # sequence numbers of open spans, -1 first
    opened = rec._opened          # their start times
    n = rec._seq                  # one cell: spans ever begun

    class _Span:
        """The context manager of one span name; cached by name, so
        entering a span looks up one dict entry and allocates no record
        object."""

        __slots__ = ("_id",)

        def __init__(self, name_id: int):
            self._id = name_id

        def __enter__(self):
            seq = n[0]
            n[0] = seq + 1
            slot = seq & mask
            names[slot] = self._id
            parents[slot] = stack[-1]
            ends[slot] = -1
            stack.append(seq)
            t = perf_counter_ns()
            starts[slot] = t
            opened.append(t)
            return self

        def __exit__(self, *exc):
            t = perf_counter_ns()
            seq = stack.pop()
            t0 = opened.pop()
            if n[0] - seq <= capacity:     # its slot is still its own
                ends[seq & mask] = t
            i = self._id
            count[i] += 1
            total_ns[i] += t - t0
            return False

    return _Span


class Recorder:
    """A ring of ``capacity`` spans (a power of two) and per-name
    totals. The program records into :data:`RECORDER`; tests may make
    small ones."""

    def __init__(self, capacity: int = CAPACITY):
        if capacity <= 0 or capacity & (capacity - 1):
            raise ValueError("capacity must be a power of two")
        self._capacity = capacity
        self._mask = capacity - 1
        zeros = array("q", [0]) * capacity
        self._name = array("q", zeros)
        self._start = array("q", zeros)
        self._end = array("q", zeros)
        self._parent = array("q", zeros)
        self._seq = array("q", [0])
        self._stack: List[int] = [-1]
        self._opened: List[int] = []
        self._names: List[str] = []
        self._spans: Dict[str, object] = {}
        self._count = array("q")
        self._total_ns = array("q")
        self._span_cls = _span_class(self, capacity)

    def span(self, name: str):
        """The context manager that records a span named ``name``."""
        s = self._spans.get(name)
        if s is None:
            s = self._span_cls(len(self._names))
            self._names.append(name)
            self._count.append(0)
            self._total_ns.append(0)
            self._spans[name] = s
        return s

    @property
    def _n(self) -> int:
        return self._seq[0]

    @property
    def dropped(self) -> int:
        """Spans the ring has overwritten since the recorder began."""
        return max(0, self._n - self._capacity)

    def holds_since(self, since_ns: int) -> bool:
        """True when no span that began at or after ``since_ns`` has
        been overwritten."""
        n = self._n
        if n <= self._capacity:
            return True
        return self._start[n & self._mask] < since_ns

    def totals(self) -> Dict[str, Tuple[int, int]]:
        """``name -> (closed spans, total ns)`` since the recorder
        began; never wraps."""
        return {name: (self._count[i], self._total_ns[i])
                for i, name in enumerate(self._names)}

    def records(self, since_ns: int, until_ns: int
                ) -> List[Tuple[int, str, int, int, int]]:
        """Closed spans still in the ring that overlap
        ``[since_ns, until_ns)``, in the order they began:
        ``(seq, name, start_ns, end_ns, parent_seq)``. ``parent_seq``
        is -1 at the top, and may name a span that is not returned."""
        out = []
        n = self._n
        for seq in range(max(0, n - self._capacity), n):
            slot = seq & self._mask
            start, end = self._start[slot], self._end[slot]
            if end < 0 or start >= until_ns or end <= since_ns:
                continue
            out.append((seq, self._names[self._name[slot]], start, end,
                        self._parent[slot]))
        return out


RECORDER = Recorder()
span = RECORDER.span
totals = RECORDER.totals
records = RECORDER.records
