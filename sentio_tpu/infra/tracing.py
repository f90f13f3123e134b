"""The span layer: one primitive from the HTTP handler to the harvest.

A span is a ``jax.profiler.TraceAnnotation``, so inside an armed
``/debug/profile`` window it is an event on ``/host:CPU`` of the xplane, on
the clock the device trace is on, carrying ``request_id`` and its fields as
stats; outside a window an annotation costs about half a microsecond. With
a ``request_id`` it also appends ``{name, t0_s, t1_s, parent, fields}`` to
that request's flight record (infra/flight.py), so the spans of one request
share its id and each names the span that caused it. Nothing else: no
exporter, no flag, no second clock.

The request id and the enclosing span travel in a context variable, so a
stage written where the work happens (``ops/embedder.py``) needs no
``request_id`` parameter threaded through every layer above it. A thread
hop must carry the context (``asyncio.to_thread`` and the server's request
threads do); where it does not,
pass ``request_id`` and ``parent`` explicitly.

Names in :data:`sentio_tpu.infra.phases.REQUEST_STAGES` are request stages;
the stages that follow the first token are observed into
``sentio_tpu_request_stage_seconds`` when they close here, the ones that
tile it when the first token lands (:func:`close_ttft`).
"""

from __future__ import annotations

import contextvars
import logging
import threading
import time
from typing import Any, Optional

from sentio_tpu.infra.flight import AUDIT_SPAN, get_flight_recorder
from sentio_tpu.infra.metrics import get_metrics
from sentio_tpu.infra.phases import REQUEST_STAGES, TTFT_STAGES, tile_ttft

logger = logging.getLogger(__name__)

__all__ = ["annotation", "close_ttft", "current", "parent_for",
           "profile_window", "span", "stamp", "stream_written",
           "tick_annotation"]

# (request id, name of the innermost open span) of the running context
_current: contextvars.ContextVar = contextvars.ContextVar(
    "sentio_span", default=(None, None))

# stages the request's first token does not wait for: observed where they close
_LATE_STAGES = frozenset(REQUEST_STAGES) - frozenset(TTFT_STAGES)


def current() -> tuple:
    """``(request_id, span name)`` of the innermost open span, or Nones."""
    return _current.get()


def _inherit(request_id: Optional[str], parent: Optional[str]) -> tuple:
    """What is not given comes from the running context: the request id,
    and as parent the innermost open span if it belongs to that request."""
    ctx_id, ctx_span = _current.get()
    if request_id is None:
        request_id = ctx_id
    if parent is None and request_id is not None and request_id == ctx_id:
        parent = ctx_span
    return request_id, parent


def parent_for(request_id: Optional[str]) -> Optional[str]:
    """The open span a new child of ``request_id`` hangs under."""
    return _inherit(request_id, None)[1] if request_id else None


def annotation(name: str, **fields: Any):
    """A bare profiler annotation (no flight span): a context manager."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **fields)


def tick_annotation(step: int):
    """The pump's per-iteration step marker: the device trace groups what
    ran under it by ``step_num``, the flight recorder's tick number."""
    from jax.profiler import StepTraceAnnotation

    return StepTraceAnnotation("decode_tick", step_num=int(step))


def _close(name: str, request_id: Optional[str], parent: Optional[str],
           t0: float, t1: float, fields: dict) -> None:
    if name in _LATE_STAGES and parent != AUDIT_SPAN:
        get_metrics().record_request_stage(name, t1 - t0)
    if request_id:
        get_flight_recorder().add_span(request_id, name, t0, t1, parent, fields)


class span:
    """``with span("rerank"): ...`` — an annotation for the profiler and,
    when a request id is given or inherited, a span on its flight record.
    The body's exception leaves as itself: the pump's crash containment
    keys off the original type."""

    __slots__ = ("name", "request_id", "parent", "fields", "_ann", "_token", "_t0")

    def __init__(self, name: str, request_id: Optional[str] = None,
                 parent: Optional[str] = None, **fields: Any) -> None:
        self.name = name
        self.request_id = request_id
        self.parent = parent
        self.fields = fields

    def __enter__(self) -> "span":
        self.request_id, self.parent = _inherit(self.request_id, self.parent)
        self._token = _current.set((self.request_id, self.name))
        self._ann = annotation(
            self.name, **({"request_id": self.request_id} if self.request_id else {}),
            **self.fields)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _current.reset(self._token)
        _close(self.name, self.request_id, self.parent, self._t0, t1, self.fields)
        return False


def stamp(name: str, t0: float, t1: float, request_id: Optional[str] = None,
          parent: Optional[str] = None, **fields: Any) -> None:
    """A span whose ends were taken on two threads (``perf_counter``
    values): recorded when it closes. The profiler gets a zero-length
    event at the close that carries the duration."""
    request_id, parent = _inherit(request_id, parent)
    with annotation(name, **({"request_id": request_id} if request_id else {}),
                    dur_ms=round((t1 - t0) * 1e3, 3)):
        pass
    _close(name, request_id, parent, t0, t1, fields)


def stream_written(request_id: str) -> None:
    """Tokens of this stream just reached the socket: one ``stream_lag``
    sample, from the oldest put of the pump no write had covered. Only the
    largest lag is kept on the record (a stream writes tens of events)."""
    lag = get_flight_recorder().take_stream_lag(request_id, time.perf_counter())
    if lag is not None:
        with annotation("stream_lag", request_id=request_id,
                        dur_ms=round(lag * 1e3, 3)):
            pass
        get_metrics().record_request_stage("stream_lag", lag)


def close_ttft(request_id: Optional[str], t_first: float,
               engine_spans: list, parent: Optional[str] = None) -> None:
    """The first token of an admission is host-visible at ``t_first``.
    ``engine_spans`` are its ``(name, t0, t1, fields)`` stamps (inbox_wait,
    slot_wait, prefill). For the user-facing admission the stages from
    receipt to now, ``other`` taking the residual, are observed once each,
    zeros included; the audit's admission only records its spans."""
    for name, t0, t1, fields in engine_spans:
        stamp(name, t0, t1, request_id, parent, **fields)
    if parent == AUDIT_SPAN:
        return
    if request_id:
        tile = get_flight_recorder().close_ttft(request_id, t_first)
        if tile is None:
            return  # a second admission under this id: the request was observed
    else:  # untraced caller: the engine's stages are all there is
        tile = tile_ttft({name: t1 - t0 for name, t0, t1, _f in engine_spans},
                         t_first - engine_spans[0][1])
    metrics = get_metrics()
    for stage, seconds in tile.items():
        metrics.record_request_stage(stage, seconds)


# ------------------------------------------------------- windowed profiler

_profile_lock = threading.Lock()
_profile_active = False  # guarded-by: _profile_lock


def profile_window(seconds: float, log_dir: str, python_tracer: bool = False) -> dict:
    """Arm ``jax.profiler`` for a bounded window and stop it — the
    ``/debug/profile?seconds=N`` implementation. Single-flight: the jax
    profiler is process-global, so a second concurrent window is refused
    rather than corrupting the first's trace. Blocking (sleeps for the
    window) — callers run it on a worker thread. Returns what happened;
    never raises (an unprofileable backend is an operator answer, not a
    500). The Python tracer stays off unless asked for: the program's own
    annotations name what the host was doing, and a 4 s window with it on
    carries 176k frame events."""
    global _profile_active
    with _profile_lock:
        if _profile_active:
            return {"started": False,
                    "error": "a profile window is already active"}
        _profile_active = True
    try:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        try:
            jax.profiler.start_trace(log_dir, profiler_options=options)
        except Exception as exc:  # noqa: BLE001 — surface, don't crash
            return {"started": False, "error": f"start_trace failed: {exc}"}
        try:
            time.sleep(max(float(seconds), 0.0))
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                logger.warning("jax profiler stop failed", exc_info=True)
        return {"started": True, "seconds": float(seconds),
                "log_dir": log_dir, "python_tracer": bool(python_tracer)}
    finally:
        with _profile_lock:
            _profile_active = False
