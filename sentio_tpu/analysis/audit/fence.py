"""Compile fence: post-warmup recompiles become hard, attributable errors.

The engine's latency story assumes every compiled program exists before
traffic arrives; a mid-serving XLA compile is a multi-second stall that
tail latencies cannot hide. The fence makes that class of regression LOUD:

* every compile observed at a registered ``jit_family`` site is counted
  here (per-family totals + a bounded recent-event ring), feeding the
  ``sentio_tpu_xla_compiles_total`` counter and the flight recorder's
  per-tick ``xla_compiles`` field;
* every compile of the PROCESS is timed into the same account, by program
  and part (:func:`note_compile_time`; infra/tracing.py's ``jax.monitoring``
  listeners are the writer): seconds of tracing, lowering and the backend's
  share, the last split by whether JAX's persistent cache had the program.
  ``program`` is the jitted function's own name for the registered families
  and the encoders' ``fwd``, ``other`` for the rest: bounded. A family's
  event gains the ``seconds`` and ``cache`` of the compile it counted;
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
    "note_compile_time",
    "register_program",
    "program_label",
    "compile_summary",
    "compiles_total",
    "per_family_totals",
    "drain_events",
    "reset",
]

_lock = threading.Lock()
_totals: dict[str, int] = {}  # guarded-by: _lock
_events: deque = deque(maxlen=256)  # guarded-by: _lock
_armed = False  # guarded-by: _lock

# the timed account: what a compile cost, by program and part, and what the
# persistent cache answered. A program outside ``_programs`` is ``other``
OTHER_PROGRAM = "other"
MAX_PROGRAMS = 32
_programs: set[str] = {"fwd"}  # guarded-by: _lock
_seconds: dict[tuple[str, str], float] = {}  # guarded-by: _lock
_cache: dict[tuple[str, str], int] = {}  # guarded-by: _lock
# per thread: the compiles that finished here and no family has counted yet
# (``FamilyFn`` counts a compile when its call returns, on the thread the
# compile ran on): ``(program, seconds, cache outcome or None)``
_local = threading.local()
_MAX_UNCOUNTED = 32


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
        _seconds.clear()
        _cache.clear()
        _armed = False
    _local.__dict__.clear()


def register_program(name: str) -> None:
    """``name`` (a jitted function's ``__name__``) keeps its own label in
    the timed account; at most ``MAX_PROGRAMS`` do."""
    with _lock:
        if len(_programs) < MAX_PROGRAMS:
            _programs.add(name)


def program_label(fun_name: str) -> str:
    """JAX's name of what it compiles (``step_n`` while tracing,
    ``jit(step_n)`` from lowering on) → the account's label."""
    if fun_name.endswith(")") and "(" in fun_name:
        fun_name = fun_name[fun_name.index("(") + 1:-1]
    with _lock:
        return fun_name if fun_name in _programs else OTHER_PROGRAM


def note_compile_time(program: str, part: str, seconds: float,
                      cache: str | None = None) -> None:
    """``seconds`` of one compile's ``part`` (``phases.COMPILE_PARTS``) at
    ``program`` (a :func:`program_label`); the backend's part says what the
    persistent cache answered (``hit`` / ``miss``). Kept for the family that
    counts this compile when its call returns, on this thread."""
    with _lock:
        _seconds[(program, part)] = _seconds.get((program, part), 0.0) + seconds
        if cache is not None:
            _cache[(program, cache)] = _cache.get((program, cache), 0) + 1
    uncounted = getattr(_local, "uncounted", None)
    if uncounted is None:
        uncounted = _local.uncounted = deque(maxlen=_MAX_UNCOUNTED)
    uncounted.append((program, seconds, cache))
    try:  # telemetry is best-effort
        from sentio_tpu.infra.metrics import get_metrics

        get_metrics().record_compile_time(program, part, seconds, cache)
    except Exception:  # noqa: BLE001 — compile-timing telemetry must never break a compile
        pass


def _take_uncounted(program: str | None) -> dict:
    """The timed parts of ``program``'s compiles on this thread since the
    last take → ``{"seconds", "cache"}`` (``cache``: ``miss`` if any backend
    part compiled, else ``hit``); empty where nothing was timed."""
    uncounted = getattr(_local, "uncounted", None)
    if not uncounted or program is None:
        return {}
    mine = [row for row in uncounted if row[0] == program]
    if not mine:
        return {}
    for row in mine:
        uncounted.remove(row)
    out: dict = {"seconds": round(sum(row[1] for row in mine), 6)}
    outcomes = {row[2] for row in mine if row[2]}
    if outcomes:
        out["cache"] = "miss" if "miss" in outcomes else "hit"
    return out


def note_compile(family: str, signature: str, n: int = 1,
                 exempt: bool = False, program: str | None = None) -> None:
    """Record ``n`` compiles at ``family`` (called by ``FamilyFn`` on jit
    cache growth). Raises :class:`CompileFenceError` when armed — unless
    ``exempt`` (a supervised replica rebuild marks the NEW engine's
    FamilyFn instances exempt for the duration of its warmup, so its cold
    compiles pass while a steady-state recompile on any OTHER engine still
    trips the fence). Exempt compiles are still counted and evented.
    ``program`` is the family's function name: the event then carries the
    ``seconds`` that compile took (trace + lower + backend) and what the
    persistent ``cache`` answered, where the listeners timed it."""
    timed = _take_uncounted(program_label(program) if program else None)
    with _lock:
        _totals[family] = _totals.get(family, 0) + n
        _events.append({"family": family, "signature": signature, "n": n, **timed})
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


def compile_summary() -> dict:
    """The timed account, as ``/info``'s ``startup.compile`` gives it:
    seconds of the process's compiles by part — ``trace_lower_s`` (Python
    tracing and lowering), ``backend_miss_s`` (XLA compiled),
    ``backend_hit_s`` (the persistent cache had it) —, the cache's ``hits``
    and ``misses``, and the same ``by_program``."""
    with _lock:
        seconds, cache = dict(_seconds), dict(_cache)

    def part(rows: dict, *parts: str) -> float:
        return round(sum(v for (_p, name), v in rows.items() if name in parts), 6)

    by_program: dict[str, dict] = {}
    for (program, name), value in sorted(seconds.items()):
        by_program.setdefault(program, {})[f"{name}_s"] = round(value, 6)
    for (program, outcome), n in sorted(cache.items()):
        by_program.setdefault(program, {})["hits" if outcome == "hit" else "misses"] = n
    return {
        "trace_lower_s": part(seconds, "trace", "lower"),
        "backend_miss_s": part(seconds, "backend_miss"),
        "backend_hit_s": part(seconds, "backend_hit"),
        "hits": sum(n for (_p, outcome), n in cache.items() if outcome == "hit"),
        "misses": sum(n for (_p, outcome), n in cache.items() if outcome == "miss"),
        "by_program": by_program,
    }


def drain_events() -> list[dict]:
    """Pop-and-return the recent compile events (single consumer: the
    decode pump folds them into flight-recorder ticks)."""
    with _lock:
        out = list(_events)
        _events.clear()
    return out
