"""What a start cost: process start → ready, tiled by phase.

The ``startup`` flight record is the account (no second recorder): every
phase is a span of infra/tracing.py under the id ``startup``, on the process
recorder's clock, whose zero IS the process's start (the OS's own start time
of the process, not a module's import time). :func:`phase` is the one way a
phase is written — ``cli`` main's imports, the first device enumeration, every
component at the ONE seam it is built through
(``serve/dependencies.py::DependencyContainer._get``) — and the spans written
where the work happens (``weights.read``, ``weights.place``, ``pool.alloc``,
``prefix.warm``) find the record through the running context, as a request's
stages find theirs.

:func:`mark_ready` closes the record when the server listens and tiles it
(:func:`tile`): SELF time a phase — a component built inside another's
``build()`` is its child and is not counted twice —, ``other`` taking what no
named span covers, so the phases sum to ``ready_s`` by construction, as the
nine stages sum to a first token's time. Published as
``sentio_tpu_startup_seconds{phase}`` and ``/info``'s ``startup``; the record
itself is ``/debug/flight/startup`` and the ``startup`` track of
``/debug/flight?format=chrome``. After ``mark_ready`` a phase is a span of
the running context's record: a component built lazily later is no part of
the start.

No JAX at import (the span layer is imported where a phase is written): the
process's start is read from the operating system at the first call.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from typing import Any, Optional

from sentio_tpu.infra.flight import STARTUP_ID, get_flight_recorder
from sentio_tpu.infra.phases import STARTUP_PHASES

logger = logging.getLogger(__name__)

__all__ = ["STARTUP_ID", "info", "listening_from", "mark_ready", "phase",
           "process_start", "process_start_unix", "reset", "stamp_phase", "tile",
           "uptime_s"]

# spans that are a phase under a name of their own, written where the work is
_CHILD_PHASES = {"weights.read": "weights", "weights.place": "weights",
                 "pool.alloc": "pool.alloc", "prefix.warm": "prefix.warm"}
_SLACK_S = 2e-6  # a span's ends are kept to the microsecond


@functools.lru_cache(maxsize=1)
def _process_start() -> tuple[float, float]:
    """``(unix time, perf_counter value)`` of the OS's start of this process:
    ``/proc/self/stat``'s start time (clock ticks since boot, 10 ms fine)
    against the boot clock now. Where that cannot be read, the first call's
    own time. Read once, at the first call, not at import."""
    now_unix, now_perf = time.time(), time.perf_counter()  # wall-clock: the epoch IS what is asked for
    age = 0.0
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    if not 0.0 <= age < 3e7:
        age = 0.0
    return now_unix - age, now_perf - age


_lock = threading.Lock()
_opened = False  # guarded-by: _lock
_tile: Optional[dict] = None  # guarded-by: _lock — set once, by mark_ready
_t_listen0: Optional[float] = None  # the container was built: `listen` begins


def process_start() -> float:
    """The process's start as a raw ``perf_counter`` value."""
    return _process_start()[1]


def process_start_unix() -> float:
    return _process_start()[0]


def uptime_s() -> float:
    return time.perf_counter() - process_start()


def _open() -> None:
    global _opened
    if _opened:  # lint: allow(lock-discipline) — GIL-atomic peek; the open is under the lock
        return
    with _lock:
        if _opened:
            return
        recorder = get_flight_recorder()
        recorder.start_request(STARTUP_ID, t_received=process_start(),
                               process_start_unix=round(process_start_unix(), 3))
        recorder.pin(STARTUP_ID)
        _opened = True


def phase(name: str, **fields: Any):
    """``with startup.phase("embedder"): ...`` — a ``startup.<name>`` span on
    the ``startup`` record, child of the phase it is opened in. Once the
    start is over, a span of whatever record the running context names: a
    request that built a component lazily shows what that cost it."""
    from sentio_tpu.infra import tracing

    if _tile is not None:  # lint: allow(lock-discipline) — GIL-atomic peek of a value set once
        return tracing.span(f"startup.{name}", **fields)
    _open()
    return tracing.span(f"startup.{name}", request_id=STARTUP_ID, **fields)


def stamp_phase(name: str, t0: float, t1: float, **fields: Any) -> None:
    """A phase whose ends are raw ``perf_counter`` values taken elsewhere
    (``import``: the process's start → ``cli`` main entered)."""
    from sentio_tpu.infra import tracing

    if _tile is not None:  # lint: allow(lock-discipline) — GIL-atomic peek of a value set once
        return
    _open()
    tracing.stamp(f"startup.{name}", t0, t1, STARTUP_ID, **fields)


def listening_from(t: Optional[float] = None) -> None:
    """Everything is built (``on_startup`` is over): what follows until
    :func:`mark_ready` is the ``listen`` phase."""
    global _t_listen0
    _t_listen0 = time.perf_counter() if t is None else t


def tile(spans: list[dict], ready_s: float, t_start_s: float = 0.0) -> dict:
    """The ``startup`` record's spans (timeline seconds; the process started
    at ``t_start_s``, 0 on the process's own recorder) →
    ``{"phases": {phase: seconds}, "weights": {...}}``. A span's
    SELF time (its length less the spans it holds) goes to its phase: the
    name after ``startup.`` where ``STARTUP_PHASES`` has it, ``weights`` for
    ``weights.read`` / ``weights.place``, and for any other span (an encoder's
    warm forward, a component that is no phase of its own) the phase of the
    span that holds it. ``other`` takes what no span covers, so the phases
    sum to ``ready_s``. The start is built on one thread at a time: a span
    that straddles its holder's end (another thread's) is counted whole."""
    phases = dict.fromkeys(STARTUP_PHASES, 0.0)
    weights = {"read_s": 0.0, "place_s": 0.0, "bytes_read": 0, "bytes_placed": 0,
               "leaves_cast": 0}
    rows = sorted((sp for sp in spans if sp["t0_s"] - t_start_s < ready_s),
                  key=lambda sp: (sp["t0_s"], -sp["t1_s"]))
    open_: list[list] = []  # [t1, phase key, self seconds] of the spans that hold the next

    def close(entry: list) -> None:
        phases[entry[1]] += max(entry[2], 0.0)

    for sp in rows:
        t0 = max(sp["t0_s"] - t_start_s, 0.0)
        t1 = min(sp["t1_s"] - t_start_s, ready_s)
        while open_ and open_[-1][0] <= t0 + _SLACK_S:
            close(open_.pop())
        name = sp["name"]
        key = _CHILD_PHASES.get(name)
        if key is None and name.startswith("startup.") and name[8:] in phases:
            key = name[8:]
        if key is None or key == "other":
            key = open_[-1][1] if open_ else "other"
        if open_:
            open_[-1][2] -= t1 - t0
        open_.append([t1, key, t1 - t0])
        if name in ("weights.read", "weights.place"):
            kind = name[8:]
            got = sp.get("fields") or {}
            weights[f"{kind}_s"] += t1 - t0
            weights["bytes_read" if kind == "read" else "bytes_placed"] += int(got.get("bytes", 0))
            weights["leaves_cast"] += int(got.get("leaves_cast", 0))
    while open_:
        close(open_.pop())
    phases["other"] += ready_s - sum(phases.values())
    return {"phases": {k: round(v, 6) for k, v in phases.items()},
            "weights": {k: round(v, 6) if isinstance(v, float) else v
                        for k, v in weights.items()}}


def mark_ready() -> dict:
    """The server listens: close the ``startup`` record, tile it, publish
    the gauge. The first call decides; later calls return its tile."""
    global _tile
    from sentio_tpu.infra.metrics import get_metrics

    t_ready = time.perf_counter()
    if _tile is not None:  # lint: allow(lock-discipline) — GIL-atomic peek of a value set once
        return _tile
    if _t_listen0 is not None:
        stamp_phase("listen", _t_listen0, t_ready)
    _open()
    with _lock:
        if _tile is not None:
            return _tile
        ready_s = t_ready - process_start()
        recorder = get_flight_recorder()
        recorder.finish_request(STARTUP_ID, latency_ms=round(ready_s * 1e3, 3))
        record = recorder.get(STARTUP_ID) or {}
        spans = [sp for sp in record.get("spans", ()) if sp.get("parent") is not None]
        _tile = {"ready_s": round(ready_s, 6),
                 **tile(spans, ready_s, record.get("t_start_s", 0.0))}
        recorder.annotate(STARTUP_ID, ready_s=_tile["ready_s"], phases=_tile["phases"])
    get_metrics().set_startup_phases(_tile["phases"])
    logger.info("ready %.1fs after the process started: %s", ready_s, ", ".join(
        f"{k} {v:.1f}" for k, v in sorted(_tile["phases"].items(), key=lambda kv: -kv[1])
        if v >= 0.05))
    return _tile


def info() -> dict:
    """``/info``'s ``startup`` block, less ``ingest`` (the server adds the
    ingestor's): the start's tile and the process's compile account so far
    (analysis/audit/fence.py: the warm-up's compiles follow ``ready``)."""
    from sentio_tpu.analysis.audit import fence

    done = _tile or {}  # lint: allow(lock-discipline) — GIL-atomic read of a value set once
    return {
        "process_start_unix": round(process_start_unix(), 3),
        "ready_s": done.get("ready_s"),
        "phases": done.get("phases", {}),
        "weights": done.get("weights", {}),
        "compile": fence.compile_summary(),
    }


def reset() -> None:
    """Tests: a start of their own (with a flight recorder of their own,
    ``set_flight_recorder``: the old record stays on the old one)."""
    global _opened, _tile, _t_listen0
    with _lock:
        _opened, _tile, _t_listen0 = False, None, None
