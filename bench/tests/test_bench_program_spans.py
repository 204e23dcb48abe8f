"""The program's spans put on the trace's clock and read as per-layer
numbers, on a hand-built reduced trace and recorder state with known
answers; then one traced CPU run of the fleet cell, where the spans the
program recorded are read back."""
import io
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import tinydoc  # noqa: E402
from lib import program_spans as P  # noqa: E402
from lib.harness import main, read_per_layer  # noqa: E402

FLEET = ("arbiter_gate_ms", "arbiter_fit_ms", "arbiter_score_ms",
         "arbiter_apply_ms", "arbiter_sync_wait_ms")
OFFLINE = ("tick_dispatch_ms.offline", "tick_bookkeeping_ms.offline")
RATE = ("idle_in_bookkeeping_ms.rate",)

# host clock: the window is [1000, 1100); the trace's clock: [0, 100)
HOST_WINDOW = (1000, 1100)


class FakeRecorder:
    def __init__(self, spans, holds=True):
        self.spans, self.holds = spans, holds

    def holds_since(self, since_ns):
        return self.holds

    def records(self, since_ns, until_ns):
        return [s for s in self.spans if s[2] < until_ns and s[3] > since_ns]


def _run(kind, trace, *, closed=None, window=HOST_WINDOW, rounds=2,
         ticks=2):
    cell = SimpleNamespace(timeline=[(0, 1, 1)] * rounds, ticks=ticks,
                           closed=closed)
    return SimpleNamespace(config={"kind": kind}, cell=cell, trace=trace,
                           spans=SimpleNamespace(
                               records={"window": [window]}))


def _trace(ops, window=(0, 100)):
    return {"window": list(window), "host": [],
            "devices": {"/device:TPU:0": {"ops": ops, "modules": []}}}


@pytest.fixture
def recorder(monkeypatch):
    def install(spans, holds=True):
        fake = FakeRecorder(spans, holds)
        monkeypatch.setattr(P, "_recorder", lambda: fake)
        return fake
    return install


# the fleet round: a tick with a gate (and its readback), a frontier
# (with its fit and snapshot readback), a score and an apply
FLEET_SPANS = [
    (0, "arbiter.tick", 1010, 1090, -1),
    (1, "arbiter.gate", 1012, 1030, 0),
    (2, "sync:arbiter.fleet-drift-gate", 1020, 1028, 1),
    (3, "controller.frontier", 1030, 1060, 0),
    (4, "sync:DeviceSizeSketch.snapshot", 1031, 1035, 3),
    (5, "controller.fit", 1036, 1056, 3),
    (6, "arbiter.score", 1060, 1070, 0),
    (7, "sync:controller.fleet-frontier-scores", 1062, 1069, 6),
    (8, "arbiter.apply", 1070, 1080, 0),
    (9, "sync:set-up", 900, 950, -1),          # before the window
]


def test_trace_clock_offset_and_its_refusals(recorder):
    recorder(FLEET_SPANS)
    run = _run("fleet", _trace([]))
    assert P.to_trace_clock(run) == -1000
    # a window 1 ms longer on one clock than the other
    run.trace["window"] = [0, 100 + 1_000_001]
    assert P.to_trace_clock(run) is None
    run.trace["window"] = [0, 100 + 999_999]
    assert P.to_trace_clock(run) == -1000
    # the ring overwrote spans of the window
    recorder(FLEET_SPANS, holds=False)
    assert P.to_trace_clock(_run("fleet", _trace([]))) is None
    assert P.in_window(_run("fleet", _trace([]))) is None
    # an untraced run has no trace clock
    recorder(FLEET_SPANS)
    assert P.to_trace_clock(_run("fleet", None)) is None


def test_in_window_keeps_the_window_spans(recorder):
    recorder(FLEET_SPANS)
    got = P.in_window(_run("fleet", None))
    assert [s[0] for s in got] == list(range(9))    # not the set-up sync


def test_idle_goes_to_the_innermost_span(recorder):
    recorder(FLEET_SPANS)
    # on the trace's clock (host - 1000) the device is busy [0, 15) and
    # [40, 50): idle [15, 40) and [50, 100), 75 ns
    run = _run("fleet", _trace([["a", 0, 15], ["b", 40, 10]]))
    ns = 1e-9
    assert P.idle_by_span(run) == pytest.approx({
        "arbiter.tick": 10 * ns,                          # [80, 90)
        "arbiter.gate": 7 * ns,                # [15, 20) [28, 30)
        "sync:arbiter.fleet-drift-gate": 8 * ns,          # [20, 28)
        "controller.frontier": 6 * ns,  # [30, 31) [35, 36) [56, 60)
        "sync:DeviceSizeSketch.snapshot": 4 * ns,         # [31, 35)
        "controller.fit": 10 * ns,             # [36, 40) [50, 56)
        "arbiter.score": 3 * ns,               # [60, 62) [69, 70)
        "sync:controller.fleet-frontier-scores": 7 * ns,  # [62, 69)
        "arbiter.apply": 10 * ns,                         # [70, 80)
        P.OUTSIDE: 10 * ns,                               # [90, 100)
    })


def test_fleet_readers_on_the_hand_built_round(recorder):
    recorder(FLEET_SPANS)
    run = _run("fleet", _trace([]), rounds=2)
    got = {m: read_per_layer(m, run) for m in FLEET + OFFLINE + RATE}
    ms = 1e-6 / 2                         # ns per round, in ms
    assert got["arbiter_gate_ms"] == pytest.approx(18 * ms)
    assert got["arbiter_fit_ms"] == pytest.approx(20 * ms)
    assert got["arbiter_score_ms"] == pytest.approx(10 * ms)
    assert got["arbiter_apply_ms"] == pytest.approx(10 * ms)
    # the three readbacks under the tick; the set-up one is outside
    assert got["arbiter_sync_wait_ms"] == pytest.approx((8 + 4 + 7) * ms)
    for m in OFFLINE + RATE:
        assert got[m] is None


SERVING_SPANS = [
    (0, "harness.tick", 1000, 1040, -1),
    (1, "harness.admit", 1000, 1005, 0),
    (2, "harness.prefill_dispatch", 1005, 1015, 0),
    (3, "harness.decode_phase", 1015, 1020, 0),
    (4, "harness.decode_dispatch", 1020, 1030, 0),
    (5, "harness.release", 1030, 1032, 0),
    (6, "harness.observe", 1032, 1040, 0),
    (7, "harness.tick", 1050, 1080, -1),
    (8, "harness.admit", 1050, 1052, 7),
    (9, "harness.decode_phase", 1052, 1060, 7),
    (10, "harness.decode_dispatch", 1060, 1070, 7),
    (11, "harness.release", 1070, 1071, 7),
    (12, "harness.observe", 1071, 1080, 7),
]


@pytest.mark.parametrize("closed", [True, False])
def test_serving_readers_on_the_hand_built_ticks(recorder, closed):
    recorder(SERVING_SPANS)
    # busy [10, 35) and [60, 75) on the trace's clock
    run = _run("serving", _trace([["p", 10, 25], ["d", 60, 15]]),
               closed=closed, ticks=2)
    got = {m: read_per_layer(m, run) for m in FLEET + OFFLINE + RATE}
    for m in FLEET:
        assert got[m] is None
    ms = 1e-6 / 2
    if closed:
        assert got["tick_dispatch_ms.offline"] == pytest.approx(30 * ms)
        assert got["tick_bookkeeping_ms.offline"] == \
            pytest.approx((5 + 5 + 2 + 8 + 2 + 8 + 1 + 9) * ms)
        assert got["idle_in_bookkeeping_ms.rate"] is None
    else:
        assert got["tick_dispatch_ms.offline"] is None
        assert got["tick_bookkeeping_ms.offline"] is None
        # idle [0, 10) [35, 60) [75, 100) inside bookkeeping: admit
        # [0, 5) [50, 52), decode_phase [52, 60), release none,
        # observe [35, 40) [75, 80)
        assert got["idle_in_bookkeeping_ms.rate"] == pytest.approx(
            (5 + 2 + 8 + 5 + 5) * ms)


def test_readers_find_nothing_without_the_recorder(monkeypatch):
    monkeypatch.setattr(P, "_recorder", lambda: None)
    for kind, closed in (("fleet", None), ("serving", True),
                         ("serving", False)):
        run = _run(kind, _trace([["a", 0, 10]]), closed=closed)
        for m in FLEET + OFFLINE + RATE:
            assert read_per_layer(m, run) is None
        assert P.idle_by_span(run) is None


def test_traced_fleet_run_reads_the_program_spans(tmp_path):
    doc = tinydoc.build(tmp_path)
    out, err = io.StringIO(), io.StringIO()
    rc = main(["--workload", "memcached-fleet-1k.phased-drift", "--seed",
               "4294967311", "--seconds", "1.5", "--trace", "1"],
              require_accelerator=False, doc_root=doc, use_cache=False,
              out=out, err=err)
    assert rc == 0, err.getvalue()[-3000:]
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    got = {m: res["metrics"][m]["value"] for m in FLEET}
    assert all(v >= 0 for v in got.values()), got
    tick = res["metrics"]["arbiter_tick_ms"]["value"]
    # children of the tick fit inside it
    assert got["arbiter_gate_ms"] + got["arbiter_score_ms"] \
        + got["arbiter_apply_ms"] <= tick
