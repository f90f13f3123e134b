"""Compile fence: post-warmup recompiles become hard, attributable errors.

The engine's latency story assumes every compiled program exists before
traffic arrives; a mid-serving XLA compile is a multi-second stall that
tail latencies cannot hide. The fence makes that class of regression LOUD:

* every compile observed at a registered ``jit_family`` site is counted
  here (per-family totals + a bounded recent-event ring), feeding the
  ``sentio_tpu_xla_compiles_total`` counter and the flight recorder's
  per-tick ``xla_compiles`` field;
* with ``SENTIO_COMPILE_FENCE=1``, serving warmup ends with
  :func:`arm` — any LATER compile raises :class:`CompileFenceError`
  carrying the offending family and the abstract signature that compiled.

Arming is strict by design: it is a canary/CI mode for deployments whose
warmup sweeps the traffic shapes they serve (see
``PagedGenerationService.warmup``). A fence error in production means
either warmup coverage or the committed compile manifest is wrong — both
are findings, not noise.
"""

from __future__ import annotations

import os
import threading
from collections import deque

__all__ = [
    "CompileFenceError",
    "enabled",
    "arm",
    "disarm",
    "is_armed",
    "note_compile",
    "compiles_total",
    "per_family_totals",
    "drain_events",
    "reset",
]

_lock = threading.Lock()
_totals: dict[str, int] = {}  # guarded-by: _lock
_events: deque = deque(maxlen=256)  # guarded-by: _lock
_armed = False  # guarded-by: _lock


class CompileFenceError(RuntimeError):
    """A registered jit family compiled AFTER the fence was armed."""

    def __init__(self, family: str, signature: str) -> None:
        self.family = family
        self.signature = signature
        super().__init__(
            f"compile fence: post-warmup XLA compile at family "
            f"{family!r} for signature {signature} — warm this variant "
            f"before arming, or treat it as a recompile regression"
        )


def enabled() -> bool:
    """``SENTIO_COMPILE_FENCE=1`` (read per call: tests flip it)."""
    return os.environ.get("SENTIO_COMPILE_FENCE", "") == "1"


def arm() -> None:
    """Declare warmup over: later compiles at registered families raise."""
    global _armed
    with _lock:
        _armed = True


def disarm() -> None:
    global _armed
    with _lock:
        _armed = False


def is_armed() -> bool:
    with _lock:
        return _armed


def reset() -> None:
    """Zero all counters and disarm (test isolation)."""
    global _armed
    with _lock:
        _totals.clear()
        _events.clear()
        _armed = False


def note_compile(family: str, signature: str, n: int = 1,
                 exempt: bool = False) -> None:
    """Record ``n`` compiles at ``family`` (called by ``FamilyFn`` on jit
    cache growth). Raises :class:`CompileFenceError` when armed — unless
    ``exempt`` (a supervised replica rebuild marks the NEW engine's
    FamilyFn instances exempt for the duration of its warmup, so its cold
    compiles pass while a steady-state recompile on any OTHER engine still
    trips the fence). Exempt compiles are still counted and evented."""
    with _lock:
        _totals[family] = _totals.get(family, 0) + n
        _events.append({"family": family, "signature": signature, "n": n})
        armed = _armed and not exempt
    try:  # telemetry is best-effort; the counter must never break a tick
        from sentio_tpu.infra.metrics import get_metrics

        get_metrics().record_compiles(family, n)
    except Exception:  # noqa: BLE001 — compile-counter telemetry must never break a fence tick
        pass
    if armed:
        raise CompileFenceError(family, signature)


def compiles_total() -> int:
    with _lock:
        return sum(_totals.values())


def per_family_totals() -> dict[str, int]:
    with _lock:
        return dict(_totals)


def drain_events() -> list[dict]:
    """Pop-and-return the recent compile events (single consumer: the
    decode pump folds them into flight-recorder ticks)."""
    with _lock:
        out = list(_events)
        _events.clear()
    return out
