"""The span layer: one primitive from the HTTP handler to the harvest.

A span is a ``jax.profiler.TraceAnnotation``, so inside an armed
``/debug/profile`` window it is an event on ``/host:CPU`` of the xplane, on
the clock the device trace is on, carrying ``request_id`` and its fields as
stats; outside a window an annotation costs about half a microsecond. With
a ``request_id`` it also appends ``{name, t0_s, t1_s, parent, fields}`` to
that request's flight record (infra/flight.py), so the spans of one request
share its id and each names the span that caused it. Nothing else: no
exporter, no flag, no second clock.

**The device's time, by program** (:class:`DeviceStamper`): every dispatch
site takes its program's place in the stamper's order before the jit call and
hands it one small output array when the call returns (:func:`dispatching`);
one daemon thread a process waits on each array in order and takes the time
it was done. The device runs
programs in dispatch order, so a program held the device from the later of
its dispatch and its predecessor's completion to its own completion: that
interval is booked under its program
(``sentio_tpu_device_program_seconds_total{program}``, the tick ring's
``device_ms``, a ``device.<program>`` annotation in the xplane), what lies
between two intervals is idle and is booked nowhere. An encoder forward's
interval splits into queued and running
(``sentio_tpu_encoder_forward_seconds_total{part}``), and the span a
program was dispatched in gains ``device_queued_ms`` and ``device_ms``
(infra/flight.py::note_device_time).

**Every compile, timed** (:func:`install_compile_listeners`): JAX's own
``jax.monitoring`` events — tracing, lowering and the backend's share of every
compile, each with the function's name, and the persistent cache's answer,
which fires on the same thread inside the backend's share — are booked by
program into the compile fence's account (analysis/audit/fence.py:
``sentio_tpu_compile_seconds_total{program, part}``,
``sentio_tpu_compile_cache_total{program, outcome}``). Each part runs under a
``compile.<program>`` annotation, and the span the compile ran in — the
dispatch's, else the running context's — gains ``compile_ms`` and
``compile_cache`` (infra/flight.py::note_compile_time). In steady state no
such event fires and no listener is called.

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
import itertools
import logging
import queue
import threading
import time
from typing import Any, Optional, Sequence

from sentio_tpu.analysis.audit import fence
from sentio_tpu.infra.flight import AUDIT_SPAN, get_flight_recorder
from sentio_tpu.infra.metrics import get_metrics
from sentio_tpu.infra.phases import (
    DEVICE_PROGRAMS,
    REQUEST_STAGES,
    TTFT_STAGES,
    phases_to_ms,
    tile_ttft,
)

logger = logging.getLogger(__name__)

__all__ = ["DeviceStamper", "annotation", "close_ttft", "current",
           "dispatching", "get_stamper", "harvested", "install_compile_listeners",
           "parent_for",
           "profile_window", "set_stamper", "span", "stamp",
           "stream_written", "tick_annotation"]

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


# ------------------------------------------------- the device's time, by program

# a completion that follows what the device had to do by more than this, or
# a harvest that follows its tick's completion by more, is a stall; a dispatch
# call that has not returned after as long is compiling or hung, and the
# stamper sets its entry aside (a call of a warm program returns in
# milliseconds, or in a tick's length where the device's queue is full)
STALL_S = 1.0
# how often the stamper looks again at a call it has set aside
SET_ASIDE_POLL_S = 0.05


class _Dispatch:
    """One dispatch's place in the stamper's order, taken BEFORE the jit
    call (``with dispatching(...) as stamp``) and armed when the block ends:
    ``stamp.out`` is then one output of the program (small, donated to no
    later program), or None if the call raised."""

    __slots__ = ("program", "tick", "spans", "seq", "out", "t_dispatch", "armed",
                 "set_aside")

    def __init__(self, program: str, tick: Optional[int], spans: list, seq: int) -> None:
        self.program, self.tick, self.spans, self.seq = program, tick, spans, seq
        self.out: Any = None
        self.t_dispatch = 0.0
        self.set_aside = False
        self.armed = threading.Event()

    def __enter__(self) -> "_Dispatch":
        # a compile inside this call is booked on the spans it is dispatched for
        _compiling.dispatch = self
        return self

    def __exit__(self, *exc) -> bool:
        self.t_dispatch = time.perf_counter()
        _compiling.dispatch = None
        self.armed.set()
        return False


class _Harvest:
    """The pump fetched the results of the tick stamped ``seq``."""

    __slots__ = ("seq", "t_harvest")

    def __init__(self, seq: int, t_harvest: float) -> None:
        self.seq, self.t_harvest = seq, t_harvest


class DeviceStamper:
    """Completion stamps for every device dispatch, taken on one daemon
    thread a process.

    A dispatching thread pays one queue put before its jit call and one
    ``perf_counter`` after it; no lock is taken round a dispatch. The place
    in the order is taken BEFORE the call because the call is where the
    program is enqueued on the device, somewhere inside its milliseconds: a
    place taken after it would let a 3 ms encoder forward, dispatched while
    the pump's call was returning, be waited for first and be given the
    whole tick's time. Taken before, two threads whose calls overlap may
    still be stamped in the other order than the device ran them, and the
    booking then gives one of the pair the other's time — now the encoder's
    3 ms to the tick; the sum is unmoved.

    The thread waits on each array in order (``block_until_ready`` releases
    the GIL), books the interval and drops its reference. It takes the time
    when it holds the GIL again, so a stamp can lag its program by up to
    the interpreter's switch interval (5 ms) while other threads compute;
    what one stamp books too much its successor books too little.

    Two things make the account miss, and both are counted where the rest
    of it goes — the flight tick record, as ``stamps_dropped`` and
    ``stamps_set_aside`` since the previous record, beside ``device_ms`` —
    and logged when they move. DROPPED: an array that was deleted, or whose
    wait raises, or a call that raised, or what a reset drained; the
    program is booked nowhere, the stamper never raises into the pump or a
    request thread, and one WARNING names the program. SET ASIDE: a call
    that has not returned after ``STALL_S`` (it compiles, or it hangs)
    loses its place, so that what was dispatched behind it is not held
    back, and is stamped when it has returned. If its program did run on
    the device before those stamped ahead of it, THEY are booked its time
    (the first of them from its predecessor's completion through the
    program's whole run) and it books about nothing: the six labels still
    sum to the device's busy time, two of them are wrong by that program's
    length. Where nothing compiles and nothing hangs — a measured window —
    no entry is set aside, and the ring's count says so.

    A stall says which side it was on: a program that held the device for
    more than ``STALL_S`` (its completion follows its predecessor's, or its
    own dispatch, by more) is the DEVICE's; a tick whose completion was
    stamped and whose harvest (:meth:`harvested`) came more than ``STALL_S``
    later, with no program compiled between the two (a warm-up compiles
    the next tick's programs with a tick in flight: the pump was working),
    is the PUMP's. Either leaves one ``stall`` event on the flight tick
    ring and one WARNING line with the ring's last eight ticks.

    ONE DEVICE A PROCESS: there is one queue and one "previous completion",
    so the account holds where the process's programs run one after the
    other — one chip, or one mesh that every program spans. In-process
    replicas on slices of a dp mesh (runtime/replica.py) run their ticks at
    the same time on different chips: each tick is then cut by the other's
    completion, ``sentio_tpu_device_program_seconds_total`` under-books,
    and one replica's reset drains the others' stamps too. Nothing detects
    that; read the series of such a process as a lower bound.

    In a worker process (runtime/worker.py) the stamps book into the
    worker's own registry and flight ring; the totals do not ride the stats
    row beside ``phase_seconds``, so a router's ``/metrics`` has them only
    for in-process engines (which is what the benchmark's cells run)."""

    def __init__(self) -> None:
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._seq = itertools.count(1)  # next() is one bytecode: no lock
        self._thread: Optional[threading.Thread] = None  # guarded-by: _lock
        # taken by the stamper's thread, by a reset's drain and by the pump
        # where it writes a tick record (and once, to start the thread):
        # never round a dispatch
        self._lock = threading.Lock()
        # since the previous tick record: the booked seconds, and the two
        # ways the account misses
        self._since = dict.fromkeys(DEVICE_PROGRAMS, 0.0)  # guarded-by: _lock
        self._dropped = 0  # guarded-by: _lock
        self._set_aside = 0  # guarded-by: _lock
        # the stamper thread's own: the last completion, and of each tick
        # not yet harvested its completion and the compiles counted by then
        self._t_prev_done = 0.0
        self._tick_done: dict[int, tuple[float, int]] = {}

    # ------------------------------------------------------ dispatching side

    def dispatching(self, program: str, tick: Optional[int] = None,
                    spans: Sequence[tuple] = ()) -> _Dispatch:
        """Take ``program``'s place in the order: ``with
        stamper.dispatching("decode", tick) as stamp: ...; stamp.out =
        packed``. ``tick`` is the pump's step number, ``spans`` the
        ``(request id, span name)`` pairs the program is dispatched for;
        ``stamp.seq`` is what a tick's harvest hands back
        (:meth:`harvested`). A program outside ``DEVICE_PROGRAMS`` raises
        here, where it is written."""
        if program not in DEVICE_PROGRAMS:
            raise KeyError(f"unknown program {program!r} (bounded set: {DEVICE_PROGRAMS})")
        entry = _Dispatch(program, tick, [pair for pair in spans if pair[0] and pair[1]],
                          next(self._seq))
        self._queue.put(entry)
        if self._thread is None:  # lint: allow(lock-discipline) — GIL-atomic peek; the start is under the lock
            self._ensure_thread()
        return entry

    def _ensure_thread(self) -> None:
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="device-stamper", daemon=True)
                self._thread.start()

    def harvested(self, seq: int, t_harvest: Optional[float] = None) -> None:
        """The pump fetched the results of the tick stamped ``seq``."""
        self._queue.put(_Harvest(seq, t_harvest or time.perf_counter()))

    def drain(self) -> int:
        """Drop what is queued and not yet stamped (a pump reset: the
        arrays of a failed tick are not worth waiting for). The dropped
        entries are counted; the one being waited on runs to its end. The
        queue is the process's: an encoder forward or another replica's
        tick dispatched in the same instant goes unstamped too."""
        n = 0
        while True:
            try:
                entry = self._queue.get_nowait()
            except queue.Empty:
                break
            if isinstance(entry, threading.Event):
                entry.set()
            n += isinstance(entry, _Dispatch)
        if n:
            self._count_dropped(n, "drained at a reset, not waited for")
        return n

    def _count_dropped(self, n: int, why: str) -> None:
        with self._lock:
            self._dropped += n
        logger.warning(
            "completion stamps dropped: %d (%s): the device's time by program "
            "(sentio_tpu_device_program_seconds_total) misses them", n, why)

    # ---------------------------------------------------------- reading side

    def take_tick_fields(self) -> dict:
        """What a flight tick record carries of the stamper, all since the
        previous record: ``device_ms`` (the bounded dict by program, zeros
        included), ``stamps_dropped`` and ``stamps_set_aside``."""
        with self._lock:
            since, self._since = self._since, dict.fromkeys(DEVICE_PROGRAMS, 0.0)
            dropped, self._dropped = self._dropped, 0
            set_aside, self._set_aside = self._set_aside, 0
        return {"device_ms": phases_to_ms(since), "stamps_dropped": dropped,
                "stamps_set_aside": set_aside}

    def wait_idle(self, timeout_s: float = 10.0) -> bool:
        """Block until everything dispatched so far is stamped or dropped
        (tests, and a reader that wants the totals of a finished run)."""
        reached = threading.Event()  # a queue entry that only says: all before me is done
        self._queue.put(reached)
        self._ensure_thread()
        return reached.wait(timeout_s)

    # --------------------------------------------------- the stamper's thread

    def _run(self) -> None:
        aside: list = []  # taken off the queue and not handled yet, in order
        while True:
            if not aside:
                aside.append(self._queue.get())
            else:
                try:
                    while True:
                        aside.append(self._queue.get_nowait())
                except queue.Empty:
                    pass
            head = aside[0]
            if isinstance(head, _Dispatch) and not head.armed.wait(
                    SET_ASIDE_POLL_S if head.set_aside else STALL_S):
                # its call compiles or hangs: stamp what was dispatched
                # behind it meanwhile, and come back to it
                if not head.set_aside:
                    head.set_aside = True
                    with self._lock:
                        self._set_aside += 1
                    logger.info(
                        "completion stamp set aside: the call of %s (tick %s) has not "
                        "returned after %.1f s (a compile, or a call that hangs); what "
                        "was dispatched behind it is stamped first", head.program,
                        head.tick, STALL_S)
                ready = [e for e in aside[1:] if isinstance(e, _Harvest)
                         or (isinstance(e, _Dispatch) and e.armed.is_set())]
                aside = [e for e in aside if e not in ready]
                for entry in ready:
                    self._handle(entry, ahead=True)
                continue
            self._handle(aside.pop(0))

    def _handle(self, entry: Any, ahead: bool = False) -> None:
        """``ahead``: stamped before a call that was set aside, so possibly
        late (it waited out that call's ``STALL_S``) and possibly booked
        that call's program: its length is no evidence of a stall."""
        try:
            if isinstance(entry, _Dispatch):
                self._stamp(entry, ahead)
            elif isinstance(entry, _Harvest):
                t_done, compiles = self._tick_done.pop(entry.seq, (entry.t_harvest, 0))
                if entry.t_harvest - t_done > STALL_S and compiles == fence.compiles_total():
                    self._stall("pump", entry.t_harvest - t_done, "decode", None)
            else:
                entry.set()
        except Exception:  # noqa: BLE001 — telemetry never raises into serving
            logger.debug("completion stamp failed", exc_info=True)

    def _stamp(self, entry: _Dispatch, ahead: bool) -> None:
        out, entry.out = entry.out, None  # the reference goes with this frame
        try:
            out.block_until_ready()
        except Exception as exc:  # noqa: BLE001 — deleted, poisoned or never dispatched: counted, skipped
            self._count_dropped(1, f"{entry.program}, tick {entry.tick}: {exc!r}")
            return
        t_done = time.perf_counter()
        del out
        program, tick, t_dispatch = entry.program, entry.tick, entry.t_dispatch
        t_start = min(max(self._t_prev_done, t_dispatch), t_done)
        self._t_prev_done = t_done
        seconds = t_done - t_start
        try:
            get_metrics().record_device_program(program, seconds, t_start - t_dispatch)
            fields = {"dur_ms": round(seconds * 1e3, 3)}
            if tick is not None:
                fields["tick"] = int(tick)
            if entry.spans:
                fields["request_id"] = entry.spans[0][0]
            with annotation(f"device.{program}", **fields):
                pass
            recorder = get_flight_recorder()
            for request_id, name in entry.spans:
                recorder.note_device_time(request_id, name, t_dispatch, t_start, t_done)
            if program == "decode":
                self._tick_done[entry.seq] = (t_done, fence.compiles_total())
                while len(self._tick_done) > 8:  # a tick nobody harvested
                    self._tick_done.pop(next(iter(self._tick_done)))
            if seconds > STALL_S and not ahead:
                self._stall("device", seconds, program, tick)
        finally:
            with self._lock:
                self._since[program] += seconds

    @staticmethod
    def _stall(side: str, gap_s: float, program: str, tick: Optional[int]) -> None:
        recorder = get_flight_recorder()
        last = recorder.timeline(last=8)
        recorder.record_tick(event="stall", side=side, gap_ms=round(gap_s * 1e3, 3),
                             program=program, **({} if tick is None else {"step": int(tick)}))
        logger.warning(
            "stall on the %s's side: %.0f ms (%s): %s", side, gap_s * 1e3,
            "the program held the device that long" if side == "device"
            else "the tick was done and its harvest came that much later",
            {"program": program, "tick": tick, "last_ticks": last})


_stamper: Optional[DeviceStamper] = None
_stamper_lock = threading.Lock()


def get_stamper() -> DeviceStamper:
    global _stamper
    if _stamper is None:
        with _stamper_lock:
            if _stamper is None:
                _stamper = DeviceStamper()
    return _stamper


def set_stamper(stamper: Optional[DeviceStamper]) -> None:
    """Tests: a stamper of their own (the old one's thread idles on)."""
    global _stamper
    with _stamper_lock:
        _stamper = stamper


def dispatching(program: str, tick: Optional[int] = None,
                spans: Sequence[tuple] = ()) -> _Dispatch:
    """``with dispatching("rerank", spans=[current()]) as stamp: out =
    stamp.out = fwd(...)`` — the process's stamper books the program's time
    on the device (:class:`DeviceStamper`)."""
    return get_stamper().dispatching(program, tick, spans)


def harvested(seq: int) -> None:
    """The results of the tick stamped ``seq`` are on the host."""
    get_stamper().harvested(seq)


# ------------------------------------------------------- every compile, timed

# JAX's events (jax/_src/dispatch.py): each opens with ``record_scalar(event,
# start, fun_name=)`` and closes with ``record_event_duration_secs(event,
# seconds, fun_name=)``, on the compiling thread
_COMPILE_PARTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _Compiling(threading.local):
    """Per thread: the dispatch whose call is running (``_Dispatch`` sets
    it), and the compile parts open here, outermost first:
    ``[part, program, annotation, cache hit seen]``."""

    def __init__(self) -> None:
        self.dispatch: Optional[_Dispatch] = None
        self.open: list[list] = []


_compiling = _Compiling()
_listeners_lock = threading.Lock()
_listeners_installed = False  # guarded-by: _listeners_lock


def _compile_opened(event: str, _value: float, fun_name: str = "", **_kw: Any) -> None:
    part = _COMPILE_PARTS.get(event)
    if part is None:
        return
    opened = _compiling.open
    ann = None
    if not opened:
        # the outermost part: what opens inside it (the functions a trace
        # calls, 8,500 of them a start; what a lowering rule traces) is its own
        program = fence.program_label(fun_name)
        ann = annotation(f"compile.{program}", part=part)
        ann.__enter__()
    else:
        program = opened[-1][1]
    opened.append([part, program, ann, False])


def _cache_answered(event: str, **_kw: Any) -> None:
    if event == _CACHE_HIT_EVENT and _compiling.open and _compiling.open[-1][0] == "backend":
        _compiling.open[-1][3] = True


def _compile_closed(event: str, seconds: float, **_kw: Any) -> None:
    part = _COMPILE_PARTS.get(event)
    opened = _compiling.open
    if part is None or not opened:
        return
    at = next((i for i in range(len(opened) - 1, -1, -1) if opened[i][0] == part), None)
    if at is None:
        return
    _part, program, ann, hit = opened[at]
    del opened[at:]  # an opening whose close never came goes with it
    if ann is None:
        return  # traced inside another function's trace: that one's seconds
    t_end = time.perf_counter()
    ann.__exit__(None, None, None)
    cache = None
    if part == "backend":
        cache = "hit" if hit else "miss"
        part = f"backend_{cache}"
    try:
        fence.note_compile_time(program, part, seconds, cache)
        dispatch = _compiling.dispatch
        spans = dispatch.spans if dispatch is not None and dispatch.spans else [current()]
        recorder = get_flight_recorder()
        for request_id, name in spans:
            if request_id and name:
                recorder.note_compile_time(request_id, name, t_end, seconds, cache)
    except Exception:  # noqa: BLE001 — telemetry never raises into a compile
        logger.debug("compile timing failed", exc_info=True)


def install_compile_listeners() -> None:
    """Register the three ``jax.monitoring`` listeners, once a process (the
    entry points that call ``ensure_compile_cache`` do, and the container's
    ``initialize_all``). Imports JAX: call it where JAX is about to be
    imported anyway."""
    global _listeners_installed
    with _listeners_lock:
        if _listeners_installed:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_compile_opened)
        monitoring.register_event_listener(_cache_answered)
        monitoring.register_event_duration_secs_listener(_compile_closed)
        _listeners_installed = True


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
