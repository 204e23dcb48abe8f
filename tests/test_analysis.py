"""slablint test suite: every seeded-violation fixture is caught, clean
code stays quiet, the real tree is clean under the checked-in baseline,
and the two acceptance mutations (undonating the fused window, adding a
host sync to the arbiter tick) flip CI red. Plus runtime coverage for
the transfer-guard sanitizer (repro.analysis.guards)."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from collections import defaultdict
from pathlib import Path

import pytest

from repro.analysis import baseline as baseline_mod
from repro.analysis import check_source, hot_path, run_check
from repro.analysis.cli import main as slablint_main
from repro.analysis.registry import HOT_PATHS, hot_path_counters

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"
FIXTURES = REPO / "tests" / "analysis_fixtures"
BASELINE = REPO / ".slablint-baseline"


# ---------------------------------------------------------------------------
# fixtures: each rule catches its seeded violation, stays quiet on clean
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fixture_findings():
    """One scan of the fixture tree; readers/ is the CC001 corpus."""
    found = run_check(FIXTURES, tests_root=FIXTURES / "readers")
    by_path = defaultdict(list)
    for f in found:
        by_path[f.path].append(f)
    return by_path


def test_hs_fixture_caught(fixture_findings):
    f = fixture_findings["hs_violation.py"]
    assert [x.rule_id for x in f] == ["HS001"]
    assert f[0].qualname == "tick" and f[0].symbol == "float"


def test_hs_clean_fixture_quiet(fixture_findings):
    assert fixture_findings["hs_clean.py"] == []


def test_dn_fixture_caught_both_forms(fixture_findings):
    f = fixture_findings["dn_violation.py"]
    assert {x.rule_id for x in f} == {"DN001"}
    assert {x.qualname for x in f} == {"fold", "make_flush.run"}


def test_rt_fixture_caught_all_three_shapes(fixture_findings):
    f = fixture_findings["rt_violation.py"]
    assert {x.rule_id for x in f} == {"RT001"}
    symbols = {x.symbol for x in f}
    assert "jit-in-loop" in symbols
    assert "closure:table" in symbols
    assert "shape:zeros" in symbols


def test_kc_fixture_caught(fixture_findings):
    f = fixture_findings["kernels/kc_violation.py"]
    assert {x.rule_id for x in f} == {"KC001"}
    assert {x.symbol for x in f} == {"interpret", "ref-missing",
                                     "index-map-bounds"}


def test_kc_clean_fixture_quiet(fixture_findings):
    assert fixture_findings["kernels/kc_clean.py"] == []


def test_cc_fixture_caught(fixture_findings):
    f = fixture_findings["cc_observe_violation.py"]
    assert {x.rule_id for x in f} == {"CC001"}
    symbols = {x.symbol for x in f}
    assert symbols == {"n_fixture_inline_count", "n_fixture_unread_total",
                       "n_ghost_total"}
    # the counter the readers corpus blesses must NOT be flagged
    assert "n_fixture_read_total" not in symbols


def test_clean_fixture_quiet(fixture_findings):
    assert fixture_findings["clean.py"] == []
    assert fixture_findings["readers/reads_counters.py"] == []


# ---------------------------------------------------------------------------
# check_source: the snippet-level API the docs doctest uses
# ---------------------------------------------------------------------------

def test_check_source_flags_undonated_jit():
    assert check_source(
        "import jax\n@jax.jit\ndef f(state): return state") == ["DN001"]


def test_check_source_quiet_on_donated_jit():
    src = ("import functools, jax\n"
           "@functools.partial(jax.jit, donate_argnums=(0,))\n"
           "def f(state): return state\n")
    assert check_source(src) == []


def test_check_source_hot_sync():
    src = ("import jax.numpy as jnp\n"
           "from repro.analysis.registry import hot_path\n"
           "@hot_path\n"
           "def tick(s):\n"
           "    return float(jnp.sum(s))\n")
    assert check_source(src) == ["HS001"]


def test_check_source_rules_filter():
    src = "import jax\n@jax.jit\ndef f(state): return state"
    assert check_source(src, only={"HS001"}) == []


# ---------------------------------------------------------------------------
# the real tree: clean under the checked-in baseline, zero stale entries
# ---------------------------------------------------------------------------

def test_src_tree_zero_unsuppressed_findings():
    findings = run_check(SRC, tests_root=REPO / "tests")
    applied, stale = baseline_mod.apply(findings,
                                        baseline_mod.load(BASELINE))
    unsup = [f for f in applied if not f.suppressed]
    assert unsup == [], [f.render() for f in unsup]
    assert stale == [], stale


def test_baseline_entries_all_justified():
    entries = baseline_mod.load(BASELINE)
    assert entries, "baseline should carry the kernel-entry suppressions"
    for fp, why in entries.items():
        assert why and "TODO" not in why, fp


def test_cli_check_exit_zero_on_real_tree():
    rc = slablint_main([str(SRC), "--check", "--baseline", str(BASELINE),
                        "--tests", str(REPO / "tests")])
    assert rc == 0


def test_cli_module_entrypoint():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--list-rules"],
        capture_output=True, text=True, cwd=REPO,
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0
    for rid in ("HS001", "DN001", "RT001", "KC001", "CC001"):
        assert rid in proc.stdout


# ---------------------------------------------------------------------------
# acceptance mutations: removing discipline from the real tree goes red
# ---------------------------------------------------------------------------

def _mutated_scan(tmp_path, path, old, new):
    root = tmp_path / "src"
    shutil.copytree(SRC, root, ignore=shutil.ignore_patterns("__pycache__"))
    target = root / path
    text = target.read_text()
    assert old in text, f"mutation anchor vanished from {path}"
    target.write_text(text.replace(old, new, 1))
    findings = run_check(root, tests_root=REPO / "tests")
    applied, _ = baseline_mod.apply(findings, baseline_mod.load(BASELINE))
    return [f for f in applied if not f.suppressed]


def test_mutation_undonated_fused_window_fails(tmp_path):
    unsup = _mutated_scan(
        tmp_path, "repro/core/observe.py",
        "fn = jax.jit(run, donate_argnums=(0,) if donate else ())",
        "fn = jax.jit(run)")
    assert any(f.rule_id == "DN001"
               and f.path == "repro/core/observe.py" for f in unsup)


def test_mutation_host_sync_in_tick_fails(tmp_path):
    unsup = _mutated_scan(
        tmp_path, "repro/core/arbiter.py",
        "self._drain_checks_fleet()",
        "_probe = float(drift_gate_fleet(self, n))\n"
        "            self._drain_checks_fleet()")
    assert any(f.rule_id == "HS001"
               and f.path == "repro/core/arbiter.py" for f in unsup)


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

def test_stale_baseline_entry_fails_check(tmp_path):
    bl = tmp_path / ".slablint-baseline"
    bl.write_text(BASELINE.read_text()
                  + "HS001:repro/ghost.py:gone:float  # obsolete\n")
    rc = slablint_main([str(SRC), "--check", "--baseline", str(bl),
                        "--tests", str(REPO / "tests")])
    assert rc == 1


def test_write_baseline_roundtrip(tmp_path):
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "observe_mod.py").write_text(
        "import jax\n@jax.jit\ndef f(state): return state\n")
    bl = tmp_path / "bl"
    assert slablint_main([str(root), "--baseline", str(bl),
                          "--write-baseline"]) == 0
    assert "DN001:observe_mod.py:f:f" in bl.read_text()
    assert slablint_main([str(root), "--check",
                          "--baseline", str(bl)]) == 0


# ---------------------------------------------------------------------------
# the hot-path registry: one source of truth, zero call overhead
# ---------------------------------------------------------------------------

def test_hot_path_registry_returns_function_unchanged():
    def probe(x):
        return x + 1

    decorated = hot_path(probe)
    assert decorated is probe            # no wrapper frame on hot paths
    assert probe.__hot_path__          # label recorded on the function
    assert probe.__hot_path__ in HOT_PATHS


def test_hot_path_counters_cover_dispatch_accounting():
    # the registry is populated by importing the core modules
    import repro.core.arbiter            # noqa: F401
    import repro.core.observe            # noqa: F401
    declared = {c for cs in hot_path_counters().values() for c in cs}
    assert "n_dispatches" in declared
    assert "n_gate_launches" in declared
    labels = set(HOT_PATHS)
    assert any("tick" in l for l in labels)
    assert any("observe_window" in l for l in labels)


# ---------------------------------------------------------------------------
# runtime sanitizer (repro.analysis.guards)
# ---------------------------------------------------------------------------

def test_invariants_check_hot_path_counters():
    from repro.analysis.registry import hot_path as hp
    from repro.scenarios.invariants import check_hot_path_counters

    class Probe:
        @hp(label="test.probe.step", counters=("n_probe_steps",))
        def step(self):
            self.n_probe_steps += 1

    p = Probe()
    missing = check_hot_path_counters(p)
    assert missing and "n_probe_steps" in missing[0]
    p.n_probe_steps = 0
    assert check_hot_path_counters(p) == []
    p.n_probe_steps = -1
    assert any("negative" in v for v in check_hot_path_counters(p))
    # the real core objects honour their declared counters
    from repro.core import DeviceSizeSketch
    s = DeviceSizeSketch(num_buckets=64)
    assert check_hot_path_counters(s) == []


def test_guard_blocks_implicit_scalar_sync():
    jnp = pytest.importorskip("jax.numpy")
    from repro.analysis.guards import GuardViolation, no_implicit_transfers
    x = jnp.ones(())
    assert float(x) == 1.0               # unarmed: plain conversion
    with no_implicit_transfers():
        with pytest.raises(GuardViolation):
            float(x)
        with pytest.raises(GuardViolation):
            jnp.arange(3).item(0)
    assert float(x) == 1.0               # restored on exit


def test_deliberate_sync_allows_and_logs():
    jnp = pytest.importorskip("jax.numpy")
    from repro.analysis import guards, spans
    x = jnp.ones(())

    def n_syncs():
        return spans.totals().get("sync:test.readback", (0, 0))[0]

    before = n_syncs()
    with guards.no_implicit_transfers():
        with guards.deliberate_sync("test.readback"):
            assert float(x) == 1.0
        assert n_syncs() == before + 1   # the readback is a timed span
        with pytest.raises(guards.GuardViolation):
            float(x)                     # re-armed after the sync block


def test_deliberate_sync_is_noop_when_unarmed():
    # a fresh interpreter: unarmed, the seam records its span, raises
    # nothing and never imports jax
    code = (
        "import sys\n"
        "from repro.analysis import spans\n"
        "from repro.analysis.guards import deliberate_sync\n"
        "with deliberate_sync('test.unarmed'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "assert spans.totals()['sync:test.unarmed'][0] == 1\n")
    src = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**os.environ, "PYTHONPATH": src})
    assert out.returncode == 0, out.stderr


def test_guard_nesting_reference_counts():
    jnp = pytest.importorskip("jax.numpy")
    from repro.analysis.guards import GuardViolation, no_implicit_transfers
    x = jnp.ones(())
    with no_implicit_transfers():
        with no_implicit_transfers():
            pass                         # inner exit must not disarm
        with pytest.raises(GuardViolation):
            float(x)
    assert float(x) == 1.0
