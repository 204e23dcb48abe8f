"""slablint: dispatch-discipline static analysis for the jit/Pallas paths.

The device pipeline's performance model rests on contracts — one fused
launch per cadence window, donated sketch buffers, no implicit host
syncs, no silent retraces — that runtime counters (``n_dispatches``,
``WINDOW_TRACE_COUNT``) only check late, in benches, on specific
inputs. This package checks them at lint time, on every line:

* :mod:`repro.analysis.registry` — the ``@hot_path`` decorator, the one
  source of truth for which functions are dispatch-sensitive.
* :mod:`repro.analysis.callgraph` — AST call graph + hot reachability.
* :mod:`repro.analysis.rules` — the pluggable rule registry (HS001
  host-sync, DN001 donation, RT001 retrace hazard, KC001 kernel
  contract, CC001 counter coverage).
* :mod:`repro.analysis.baseline` — deliberate-suppression file support.
* :mod:`repro.analysis.cli` — ``python -m repro.analysis``.
* :mod:`repro.analysis.guards` — the *runtime* half: a transfer-guard
  sanitizer (:func:`guards.no_implicit_transfers`) and the
  :func:`guards.deliberate_sync` escape hatch the static rules
  recognise, which records each readback as a ``sync:<label>`` span.
* :mod:`repro.analysis.spans` — the always-on span recorder: a fixed
  ring of spans at layer boundaries and per-name totals.

Everything except ``guards`` is stdlib-only so the lint CI job needs no
jax install; ``guards`` imports jax lazily and only when armed.
"""
from repro.analysis.findings import Finding
from repro.analysis.registry import HOT_PATHS, hot_path
from repro.analysis.cli import check_source, run_check

__all__ = ["Finding", "HOT_PATHS", "hot_path", "check_source",
           "run_check"]
