"""Runtime transfer-guard sanitizer — the dynamic half of slablint.

:func:`no_implicit_transfers` arms two layers around a code region:

* jax's native ``transfer_guard_device_to_host("disallow")`` — the real
  enforcement on TPU, where any implicit device→host copy raises. On
  the CPU backend this guard is inert (host-resident arrays are
  "transferred" zero-copy), so additionally
* a software layer patches the concrete ``jax.Array`` implementation's
  host-materialising methods (``__float__``/``__int__``/``__bool__``/
  ``__index__``/``item``/``tolist``) to raise :class:`GuardViolation`
  while armed. This catches the common accidental syncs on every
  backend. Known hole: ``np.asarray(x)`` reaches CPU array memory via
  the buffer protocol and cannot be intercepted from Python — the
  native guard covers it on TPU, and slablint's HS001 covers it
  statically everywhere.

Donation-discard warnings are escalated to errors while armed, so a
fused window whose donated buffer silently stopped being donated fails
loudly (again: emitted on TPU; CPU jax does not warn).

:func:`deliberate_sync` is the escape hatch *both* halves recognise:
statically, HS001 skips sinks inside ``with deliberate_sync(...):``;
dynamically it suspends the software patches and enters the native
``"allow"`` scope. Armed or not, it records a span named
``sync:<label>`` (:mod:`repro.analysis.spans`), so every labelled
readback is timed where the host waits on the device. When no guard is
armed it never imports jax — host-only modules can use it freely.
"""
from __future__ import annotations

import contextlib
import threading
import warnings
from typing import Iterator, Optional

from repro.analysis.spans import span

__all__ = ["GuardViolation", "no_implicit_transfers", "deliberate_sync",
           "guards_armed"]


class GuardViolation(RuntimeError):
    """An implicit device→host materialisation inside a guarded region."""


# One process-wide state: benches and tests arm guards around serial
# regions; the lock only protects arm/disarm bookkeeping.
_LOCK = threading.Lock()
_DEPTH = 0          # no_implicit_transfers nesting
_SYNC_DEPTH = 0     # deliberate_sync nesting (while armed)
_SAVED: dict = {}   # patched attr -> original


def guards_armed() -> bool:
    return _DEPTH > 0


def _array_cls():
    import jax.numpy as jnp
    return type(jnp.zeros(0))


_PATCHED = ("__float__", "__int__", "__bool__", "__index__", "item",
            "tolist")


def _install_patches() -> None:
    cls = _array_cls()
    for name in _PATCHED:
        orig = getattr(cls, name)
        _SAVED[name] = orig

        def patched(self, *a, __orig=orig, __name=name, **kw):
            if _SYNC_DEPTH > 0:
                return __orig(self, *a, **kw)
            raise GuardViolation(
                f"implicit host sync: `{__name}` on a jax array inside "
                "a no_implicit_transfers region — wrap a deliberate "
                "cadence-boundary readback in deliberate_sync(...)")

        setattr(cls, name, patched)


def _remove_patches() -> None:
    cls = _array_cls()
    for name, orig in _SAVED.items():
        setattr(cls, name, orig)
    _SAVED.clear()


@contextlib.contextmanager
def no_implicit_transfers(*, donation_errors: bool = True
                          ) -> Iterator[None]:
    """Arm the transfer-guard sanitizer around a code region."""
    global _DEPTH
    import jax
    with _LOCK:
        _DEPTH += 1
        if _DEPTH == 1:
            _install_patches()
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            if donation_errors:
                with warnings.catch_warnings():
                    warnings.filterwarnings(
                        "error", message=".*[Dd]onat.*")
                    yield
            else:
                yield
    finally:
        with _LOCK:
            _DEPTH -= 1
            if _DEPTH == 0:
                _remove_patches()


@contextlib.contextmanager
def deliberate_sync(label: Optional[str] = None) -> Iterator[None]:
    """Mark a deliberate device→host readback (cadence boundaries) and
    record it as a ``sync:<label>`` span.

    Unarmed it only records the span — it never imports jax, so
    host-only sketches can run through it.
    """
    global _SYNC_DEPTH
    with span(f"sync:{label}"):
        if _DEPTH == 0:
            yield
            return
        import jax
        with _LOCK:
            _SYNC_DEPTH += 1
        try:
            with jax.transfer_guard_device_to_host("allow"):
                yield
        finally:
            with _LOCK:
                _SYNC_DEPTH -= 1
