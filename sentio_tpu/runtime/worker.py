"""Process-level replica workers: one engine+service+pump per OS process.

The thread-mode ReplicaSet (runtime/replica.py) made the replica a complete
*logical* failure domain — health state machine, breakers, watchdog, inbox
handoff — but all N pumps share one Python process, so a "replica kill" is
an injected exception and N dispatches contend for one GIL (what that
costs on a TPU host is not measured). This module promotes the replica to a
real **OS-level** failure domain, the way production inference stacks
isolate engine crashes from the frontend (vLLM's engine-per-process
serving, Orca-style continuous-batching workers):

* :func:`worker_main` runs in a child process (**spawn** start method —
  JAX is not fork-safe: a fork duplicates its runtime threads' locks in a
  held state and the child deadlocks on the first dispatch) and owns a
  private ``ContinuousBatchingEngine`` + ``PagedGenerationService`` +
  pump thread. It serves a small RPC protocol over the spawn pipe
  (``multiprocessing.Pipe`` — length-prefixed pickle frames) and pushes
  unsolicited **status frames** (heartbeat age, backlog, breaker signals)
  at a fixed cadence so the router's supervisor probes never pay an RPC
  round trip.
* :class:`ProcessReplica` is the router-side shim: it presents the same
  ``generate / generate_stream / check_admission / peek_prefix / warmup /
  drain / stats / close`` surface as a ``PagedGenerationService``, so
  ``ReplicaSet`` routing, WFQ, affinity, health supervision, and failover
  drive it **unchanged**. Streaming arrives as incremental token frames;
  worker death (``SIGKILL``, OOM-kill, crash) surfaces as broken-pipe /
  ``proc.is_alive()`` and every in-flight RPC fails with a typed
  :class:`ReplicaUnavailable` — callers spend their normal failover
  budget, exactly as if an in-process replica had latched broken.
* the supervisor rebuilds a dead replica by **respawning the process**
  (:meth:`ProcessReplica.respawn` — the ``ReplicaSet._rebuild`` path
  duck-types it), with the existing exponential backoff and rebuild
  worker pool carrying over.
* weights are mapped **once per host**: a checkpoint loaded with
  ``load_pytree(..., mmap=True)`` memory-maps the uncompressed ``.npy``
  members of ``arrays.npz`` in place, so N workers reading the same
  checkpoint share the page cache instead of holding N private host
  copies (runtime/checkpoint.py stores ``np.savez`` zips uncompressed
  precisely so this works).

**Router-side ticket shadowing** — the router mirrors every admitted-but-
not-yet-answered request in a shadow queue of real
:class:`~sentio_tpu.runtime.service._Ticket` objects (the same dataclass
thread mode hands off), keyed by RPC id. A request leaves the shadow the
moment its first answer frame arrives (first token frame for a stream,
the result frame for a generate). When the fronting ReplicaSet enables
handoff (:meth:`ProcessReplica.enable_shadow_handoff` — it does so
whenever it supervises), worker death or stall-quarantine no longer fails
those callers typed: ``extract_inbox``/``abandon`` return the shadowed
tickets and the ReplicaSet's existing ``_handoff_inbox`` re-admits them
on survivors via ``adopt()`` with the PR 10 WFQ recharge semantics —
handoff parity with thread mode. A LIVE but quarantined worker
additionally answers a bounded-timeout ``extract_inbox`` RPC that names
exactly its never-dispatched inbox tickets (by ``shadow_id``), so only
truly queued work moves and mid-decode work keeps its normal typed-
failover path. ``adopt`` re-registers the SAME ticket object against the
survivor's pipe — the blocked caller (event for generates, ``stream_q``
for streams) just wakes with the survivor's answer, spending no failover
budget. Without an enabling ReplicaSet the shadow stays passive and death
keeps its fail-fast typed surface.

**Transports & the multi-host tier** — the pickle-frame protocol runs
behind a transport seam (runtime/transport.py): ``REPLICA_MODE=process``
keeps the spawn pipe, byte-identical; ``REPLICA_MODE=socket`` runs the
SAME frames over length-prefixed TCP with a versioned auth handshake —
spawned workers self-register against the router's ``WorkerRegistry``
listener (:func:`worker_main_socket`), or the router dials workers
already serving on OTHER hosts (``REPLICA_WORKERS`` →
:func:`worker_serve`). Every (re)registration is a fresh **incarnation
epoch** stamped into frame headers; the dispatcher drops stale-epoch
frames, so a worker that vanished behind a partition and later
reconnects can never resurrect dead tickets or double-deliver stream
chunks. Death detection generalizes to a transport-liveness contract —
status-frame staleness past ``partition_timeout_s``, a broken ping
write, EOF — feeding the same quarantine machinery; recovery prefers
**heal** (the live worker re-registers, keeping its warm engine) over
respawn, and duck-types to redial-with-backoff for remote workers the
router cannot spawn.

Deliberate semantic deltas from thread mode, all documented here:

* **stream cancellation propagates at chunk granularity** — closing the
  router-side iterator sends a cancel frame; the worker notices between
  token frames, so an abandoned stream decodes at most one more chunk.
* **compile fences are per-process** — worker compiles never trip the
  router's fence; ``set_fence_exempt`` on the engine facade is a no-op.
* **mid-decode generates may re-execute on handoff** — a dead worker
  cannot report which shadowed generates had already dispatched, so after
  a process death every shadowed (unanswered) ticket is handed off; a
  re-executed generate is idempotent from the caller's view (no partial
  output ever escaped). Streams are exact: delivered-token streams leave
  the shadow at their first token frame and ride the ReplicaSet's
  resume-by-replay path instead.
"""

from __future__ import annotations

import logging
import os
import queue as _queue
import signal
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional

from sentio_tpu.infra import faults
from sentio_tpu.infra.compile_cache import ensure_compile_cache
from sentio_tpu.infra.exceptions import (
    DeadlineExceededError,
    ReplicaUnavailable,
    SentioError,
)
from sentio_tpu.infra.tracing import install_compile_listeners
from sentio_tpu.runtime.paged import PagedResult
from sentio_tpu.runtime.service import (
    StreamProgress,
    _Ticket,
    finish_ticket_error,
)
from sentio_tpu.runtime.transport import (
    DEFAULT_FRAME_TIMEOUT_S,
    DEFAULT_MAX_FRAME_BYTES,
    ClockSync,
    FrameProtocolError,
    PipeTransport,
    SocketTransport,
    TransportClosed,
    TransportError,
    dial,
    expect_hello,
    send_hello,
)

logger = logging.getLogger(__name__)

__all__ = [
    "WorkerSpec",
    "ProcessReplica",
    "worker_main",
    "worker_main_socket",
    "worker_serve",
    "default_service_factory",
    "REPLICA_MODE_THREAD",
    "REPLICA_MODE_PROCESS",
    "REPLICA_MODE_SOCKET",
]

REPLICA_MODE_THREAD = "thread"
REPLICA_MODE_PROCESS = "process"
# socket transport: same worker protocol over length-prefixed TCP frames
# (runtime/transport.py) — spawned workers self-register against the
# router's WorkerRegistry listener; REPLICA_WORKERS=host:port,... makes the
# router dial advertised workers on OTHER hosts instead of spawning
REPLICA_MODE_SOCKET = "socket"

# worker → router frame kinds (req_id 0 is reserved for unsolicited frames)
_F_READY = "ready"
_F_STATUS = "status"
_F_OK = "ok"
_F_ERR = "err"
_F_TOK = "tok"
_F_END = "end"
# fleet telemetry plane (ISSUE 16): low-priority unsolicited frames — a
# telemetry frame ships the worker's cumulative metrics registry + duty
# snapshot at spec.telemetry_interval_s; a pong answers a timestamped ping
# with the worker's clock so the router's ClockSync can estimate the offset
_F_TELEMETRY = "telemetry"
_F_PONG = "pong"
# elastic fleet (ISSUE 20): a voluntary deregister — the worker asks the
# router to retire it gracefully (drain + handoff + close + slot release).
# Unsolicited (req_id 0); serving continues until the router-side
# supervisor drains the replica, so no in-flight work is ever dropped.
_F_DEREGISTER = "deregister"

# the bounded stats subset a telemetry frame carries (full svc.stats() is
# an RPC surface — the cadence frame only ships what the router merges:
# phase/duty for fleet duty gauges, occupancy/pool for {replica} gauges)
_TELEMETRY_STAT_KEYS = (
    "phase_seconds", "duty_elapsed_s", "duty_cycle", "active_slots",
    "queued", "queued_inbox", "free_pages", "total_pages",
    "pool_hbm_bytes",
)


@dataclass(frozen=True)
class WorkerSpec:
    """Everything a spawned worker needs to build its replica. Must be
    picklable: the spawn start method ships it through the process pipe.

    ``factory`` is a ``"module:function"`` path resolved **inside the
    worker** — it returns a ready ``PagedGenerationService``. The default
    (:func:`default_service_factory`) builds a llama/moe engine from a
    checkpoint path (mmap-shared across workers) or a seeded random init;
    tests point it at tiny configs through ``factory_kwargs``."""

    factory: str = "sentio_tpu.runtime.worker:default_service_factory"
    factory_kwargs: dict = field(default_factory=dict)
    # cadence of unsolicited status frames (the router-side supervisor's
    # probe source); also bounds how stale a liveness read can be
    status_interval_s: float = 0.1
    # cadence of unsolicited telemetry frames (metrics-registry snapshot +
    # duty/phase stats + flight high-water marks). 0 DISABLES the plane
    # entirely: no telemetry thread, no pong frames, no clock stamps on
    # pings — the wire protocol is byte-identical to the pre-telemetry
    # baseline (the TELEMETRY_INTERVAL_S=0 parity contract)
    telemetry_interval_s: float = 1.0
    # ---- socket transport (REPLICA_MODE=socket / REPLICA_WORKERS) ----
    # shared secret for the versioned registration handshake; the registry
    # rejects hellos that fail the constant-time compare
    auth_token: str = ""
    # frame bounds: an oversized frame is refused typed on both sides, a
    # partial frame (or a write the peer stopped draining) past the
    # timeout drops the connection instead of hanging a reader
    max_frame_bytes: int = DEFAULT_MAX_FRAME_BYTES
    frame_timeout_s: float = DEFAULT_FRAME_TIMEOUT_S
    # worker-side re-registration: when the router link dies (EOF, broken
    # write, or router silence past router_silence_timeout_s), redial the
    # registry with exponential backoff — the reconnection is a FRESH
    # incarnation (higher epoch); reconnect_deadline_s of continuous dial
    # failure means the router is gone for good and the worker exits
    # rather than orphan itself
    reconnect: bool = False
    reconnect_backoff_s: float = 0.5
    reconnect_max_backoff_s: float = 5.0
    reconnect_deadline_s: float = 60.0
    # a socket worker that has heard NOTHING from the router (requests,
    # pings, anything) for this long treats the link as partitioned and
    # redials; 0 disables (pipe mode never needs it — a dead router is a
    # broken pipe). The router pings at ping_interval_s, so a healthy
    # idle link never trips this.
    router_silence_timeout_s: float = 3.0


def _resolve_factory(path: str):
    import importlib

    mod_name, _, fn_name = path.partition(":")
    if not fn_name:
        raise ValueError(f"factory {path!r} is not 'module:function'")
    return getattr(importlib.import_module(mod_name), fn_name)


def default_service_factory(
    model_family: str = "llama",
    model_config: Optional[dict] = None,
    checkpoint_path: str = "",
    tokenizer_path: str = "",
    draft_checkpoint_path: str = "",
    rng_seed: int = 0,
    engine_kwargs: Optional[dict] = None,
    service_kwargs: Optional[dict] = None,
    warm_prefix_text: str = "",
) -> Any:
    """Build the worker's engine+service. With a ``checkpoint_path`` the
    params are loaded **memory-mapped** so sibling workers on the same host
    share one page-cache copy; without one, a seeded random init keeps all
    replicas' weights identical (the test / offline-dev mode). A
    ``draft_checkpoint_path`` arms paged speculation inside the worker —
    the draft loads here, in the worker process, mmap-shared like the
    target weights."""
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.service import PagedGenerationService

    from sentio_tpu.config import GeneratorConfig
    from sentio_tpu.models.families import family, rebuild_config
    from sentio_tpu.runtime.weights import load_decoder, load_model

    # the family by the name the router's spec carries, its configuration
    # from the ``asdict`` beside it (none: the family's tiny one)
    cfg = None
    if not checkpoint_path:
        cls = family(model_family).config
        cfg = rebuild_config(cls, model_config) if model_config is not None else cls.tiny()
    decoder = load_decoder(
        GeneratorConfig(checkpoint_path=checkpoint_path,
                        tokenizer_path=tokenizer_path),
        model_config=cfg, rng_seed=rng_seed, mmap=True,
    )
    engine_kwargs = dict(engine_kwargs or {})
    if draft_checkpoint_path:
        draft_params, draft_cfg, _ = load_model(
            draft_checkpoint_path, expect_family="llama", mmap=True,
        )
        engine_kwargs.setdefault("draft_params", draft_params)
        engine_kwargs.setdefault("draft_config", draft_cfg)
    engine = ContinuousBatchingEngine(
        model_config=decoder.model_config,
        params=decoder.params,
        tokenizer=decoder.tokenizer,
        rng_seed=rng_seed,
        **engine_kwargs,
    )
    if warm_prefix_text:
        engine.warm_prefix(warm_prefix_text)
    return PagedGenerationService(engine, **(service_kwargs or {}))


# --------------------------------------------------------------------------
# exception codec: typed errors must survive the process boundary

def _encode_exc(exc: BaseException) -> dict:
    data = {
        "cls": type(exc).__name__,
        "module": type(exc).__module__,
        "message": str(exc),
    }
    if isinstance(exc, SentioError):
        data.update(
            status=exc.status,
            details=exc.details,
            retryable=exc.retryable,
            code=exc.code.value,
        )
    return data


def _decode_exc(data: dict) -> BaseException:
    """Rebuild the worker's exception router-side. SentioError subclasses
    reconstruct with their full wire surface (status / details /
    retry_after_s) so HTTP mapping and failover logic behave identically;
    the service's own GenerationTimeout and common builtins round-trip by
    name; anything else degrades to RuntimeError carrying the original
    type — a worker *bug* must not masquerade as a retryable 503."""
    from sentio_tpu.infra import exceptions as exc_mod
    from sentio_tpu.runtime.service import GenerationTimeout

    name, message = data.get("cls", ""), data.get("message", "")
    cls = getattr(exc_mod, name, None)
    if isinstance(cls, type) and issubclass(cls, exc_mod.SentioError):
        err = cls.__new__(cls)
        Exception.__init__(err, message)
        err.message = message
        err.status = data.get("status", 500)
        err.details = data.get("details") or {}
        err.retryable = bool(data.get("retryable", False))
        err.error_id = ""
        err.timestamp = 0.0
        try:
            err.code = exc_mod.ErrorCode(data.get("code", cls.code.value))
        except ValueError:
            pass
        return err
    if name == "GenerationTimeout":
        return GenerationTimeout(message)
    import builtins

    builtin = getattr(builtins, name, None)
    if isinstance(builtin, type) and issubclass(builtin, Exception):
        try:
            return builtin(message)
        except Exception:  # noqa: BLE001 — odd constructor signature
            pass
    return RuntimeError(f"worker raised {name}: {message}")


# --------------------------------------------------------------------------
# worker side

class _WorkerServer:  # frame-emit: worker-to-router
    """Runs inside the child process: one recv loop dispatching RPC frames
    to handler threads, a status thread pushing liveness. Framing and
    send-side locking live in the transport (runtime/transport.py) — the
    server is transport-agnostic, so the spawn pipe and a TCP socket serve
    the identical protocol.

    A server instance covers ONE connection (one incarnation). In socket
    reconnect mode the outer loop (:func:`worker_main_socket`) builds a
    fresh server per connection, handing the already-built service across
    so a reconnection is a fresh incarnation of the LINK, not of the
    engine."""

    def __init__(self, transport, spec: WorkerSpec, svc=None) -> None:
        self.transport = transport
        self.spec = spec
        self.svc = svc
        self._stop = threading.Event()
        # why this run() returned: "shutdown" (router asked), "link_lost"
        # (transport died / router silent), or "fatal" (factory failed)
        self.outcome = ""
        # stream cancellation flags by req_id (checked between token frames)
        self._cancelled: set[int] = set()  # guarded-by: _cancel_lock
        self._cancel_lock = threading.Lock()

    def _send(self, req_id: int, kind: str, payload: Any) -> None:
        try:
            self.transport.send((req_id, kind, payload))
        except TransportError:
            # router link gone (EOF, broken write, frame refused): stop
            # this incarnation; the outer loop decides whether to redial
            self._stop.set()

    # ------------------------------------------------------------- handlers

    def _status_loop(self) -> None:
        interval = max(self.spec.status_interval_s, 0.02)
        while not self._stop.wait(interval):
            svc = self.svc
            if svc is None:
                continue
            try:
                status = {
                    "heartbeat_age": svc.heartbeat_age(),
                    "backlog": svc.backlog(),
                    "projected_wait": svc.projected_wait(),
                    "broken": svc.broken,
                    "closed": svc.closed,
                    "tick_failure_count": svc.tick_failure_count,
                    "pump_leaked": svc.pump_leaked_count,
                    "duty_cycle": svc.duty_cycle(),
                    "pid": os.getpid(),
                }
            except Exception:  # noqa: BLE001 — status is best-effort
                continue
            self._send(0, _F_STATUS, status)

    def _telemetry_loop(self) -> None:
        """Ship the fleet-telemetry frame at ``spec.telemetry_interval_s``:
        the worker's CUMULATIVE metrics registry (the router differences
        consecutive snapshots into deltas — cumulative-on-the-wire makes a
        dropped frame lossless, the next one carries everything), the
        bounded duty/occupancy stats subset, the flight ring's high-water
        marks, and the clock stamps (pid / perf_counter / recorder origin)
        the merge fence and trace re-basing need. Runs only when the
        interval is > 0 — the hot path pays nothing either way (one extra
        unsolicited frame per second rides the same transport send lock
        status frames already take)."""
        from sentio_tpu.infra.flight import get_flight_recorder
        from sentio_tpu.infra.metrics import get_metrics

        interval = max(self.spec.telemetry_interval_s, 0.05)
        recorder = get_flight_recorder()
        while not self._stop.wait(interval):
            svc = self.svc
            if svc is None:
                continue
            try:
                stats = svc.stats()
            except Exception:  # noqa: BLE001 — stats mid-teardown
                stats = {}
            try:
                payload = {
                    "series": get_metrics().export_worker_series(),
                    "stats": {k: stats[k] for k in _TELEMETRY_STAT_KEYS
                              if k in stats},
                    "flight": recorder.highwater(),
                    "pid": os.getpid(),
                    "origin_s": recorder.origin(),
                    "t_worker": time.perf_counter(),
                }
            except Exception:  # noqa: BLE001 — telemetry is best-effort
                continue
            self._send(0, _F_TELEMETRY, payload)

    # frame-dispatch: router-to-worker via=pipe,socket
    def _handle(self, req_id: int, method: str, kwargs: dict) -> None:
        svc = self.svc
        try:
            if method == "generate":
                self._send(req_id, _F_OK, svc.generate(**kwargs))
            elif method == "stream_open":
                self._handle_stream(req_id, kwargs)
            elif method == "check_admission":
                rel = kwargs.get("deadline_rel_s")
                svc.check_admission(
                    time.perf_counter() + rel if rel is not None else None
                )
                self._send(req_id, _F_OK, None)
            elif method == "peek_prefix":
                self._send(req_id, _F_OK,
                           svc.engine.peek_prefix(kwargs["toks"]))
            elif method == "stats":
                self._send(req_id, _F_OK, svc.stats())
            elif method == "warmup":
                self._send(req_id, _F_OK, svc.warmup(**kwargs))
            elif method == "drain":
                self._send(req_id, _F_OK, svc.drain(**kwargs))
            elif method == "abandon":
                tickets = svc.abandon(kwargs.get("reason",
                                                 "abandoned by router"))
                # never-dispatched inbox tickets come back by shadow id so
                # the router can hand EXACTLY them to survivors; the
                # admitted tickets abandon() failed typed are reaching
                # their callers as _F_ERR frames right now
                self._send(req_id, _F_OK, self._shadow_ids(tickets))
            elif method == "extract_inbox":
                # breaker-flavor quarantine of a LIVE worker: name the
                # never-dispatched inbox tickets (by shadow id) back to
                # the router's shadow queue; only truly queued work moves
                self._send(req_id, _F_OK,
                           self._shadow_ids(svc.extract_inbox()))
            elif method == "duty_cycle":
                self._send(req_id, _F_OK, svc.duty_cycle())
            elif method == "fetch_flight":
                # on-demand flight shipping: the detailed per-request tick/
                # phase/verify data moves ONLY when asked (the 1 Hz frame
                # carries counters; /debug/flight and `sentio trace --fleet`
                # pay one RPC each) — the hot path never ships a tick
                from sentio_tpu.infra.flight import get_flight_recorder

                recorder = get_flight_recorder()
                payload = {
                    "pid": os.getpid(),
                    "origin_s": recorder.origin(),
                    "t_worker": time.perf_counter(),
                }
                if kwargs.get("t_tx") is not None:
                    # echo the router's transmit stamp: the reply doubles
                    # as a clock sample (pipe mode has no ping loop, so
                    # this is its only offset source)
                    payload["t_tx"] = kwargs["t_tx"]
                rid = kwargs.get("request_id")
                if rid is not None:
                    payload["record"] = recorder.get(rid)
                else:
                    payload["ticks"] = recorder.timeline(kwargs.get("last"))
                    payload["records"] = recorder.records()
                self._send(req_id, _F_OK, payload)
            elif method == "reset_duty_cycle":
                svc.reset_duty_cycle()
                self._send(req_id, _F_OK, None)
            elif method == "inject_fault":
                from sentio_tpu.infra import faults

                point = kwargs.pop("point")
                faults.arm(point, faults.FaultRule(**kwargs))
                self._send(req_id, _F_OK, None)
            elif method == "reset_faults":
                from sentio_tpu.infra import faults

                faults.reset()
                self._send(req_id, _F_OK, None)
            elif method == "ping":
                self._send(req_id, _F_OK, os.getpid())
            elif method == "leave":
                # voluntary deregister trigger (operator CLI / drills): the
                # worker emits the unsolicited deregister frame and KEEPS
                # SERVING — the router's supervisor owns the graceful
                # retire (drain, handoff, close); shutting down here would
                # drop in-flight work the retire path exists to save
                self._send(0, _F_DEREGISTER, {
                    "reason": kwargs.get("reason", "leave"),
                    "pid": os.getpid(),
                })
                self._send(req_id, _F_OK, None)
            else:
                raise ValueError(f"unknown worker method {method!r}")
        except BaseException as exc:  # noqa: BLE001 — everything goes typed  # lint: allow(baseexception-swallow) — converted to a typed wire frame
            self._send(req_id, _F_ERR, _encode_exc(exc))

    @staticmethod
    def _shadow_ids(tickets: list) -> list:
        return [t.shadow_id for t in tickets if t.shadow_id is not None]

    def _handle_stream(self, req_id: int, kwargs: dict) -> None:
        """Token frames for one stream. The iterator is created (call-time
        validation) BEFORE the ok frame, so the router-side caller sees
        validation errors synchronously — the SSE pre-200 contract.

        Each token frame carries ``(piece, token_id_delta)`` — the exact
        ids behind the piece, mirrored from the service's
        :class:`StreamProgress` — so the router can accumulate the
        delivered prefix a mid-flight resume re-admits. The
        ``worker.stream_chunk`` fault point fires BETWEEN delivered
        chunks: chaos drills arm ``kill_process`` (a real mid-stream
        SIGKILL) or a stall there via the ``inject_fault`` RPC."""
        stats_out: dict = {}
        progress = StreamProgress()
        it = self.svc.generate_stream(stats_out=stats_out,
                                      progress=progress, **kwargs)
        self._send(req_id, _F_OK, None)
        sent = 0
        delivered = False
        try:
            for piece in it:
                if delivered:
                    faults.hit("worker.stream_chunk")
                with self._cancel_lock:
                    if req_id in self._cancelled:
                        self._cancelled.discard(req_id)
                        it.close()  # marks the ticket cancelled in finally
                        return
                toks = list(progress.tokens)
                self._send(req_id, _F_TOK, (piece, toks[sent:]))
                sent = len(toks)
                delivered = True
            # the end frame carries the AUTHORITATIVE final token ids:
            # tokens whose text the UTF-8 withholding never flushed ride
            # no token frame, and the router's delivered-state mirror must
            # still converge on the service's final sequence
            self._send(req_id, _F_END, (stats_out, list(progress.tokens)))
        except BaseException as exc:  # noqa: BLE001  # lint: allow(baseexception-swallow) — converted to a typed wire frame
            self._send(req_id, _F_ERR, _encode_exc(exc))
        finally:
            with self._cancel_lock:
                self._cancelled.discard(req_id)

    # ----------------------------------------------------------------- main

    # frame-dispatch: router-to-worker via=pipe,socket
    def run(self) -> str:
        """Serve this connection until shutdown / link loss. Returns the
        outcome (also latched on ``self.outcome``); the SERVICE is left
        open — the caller owns its lifetime (a socket reconnection reuses
        it across incarnations)."""
        if self.svc is None:
            try:
                factory = _resolve_factory(self.spec.factory)
                self.svc = factory(**self.spec.factory_kwargs)
            except BaseException as exc:  # noqa: BLE001 — report, then die  # lint: allow(baseexception-swallow) — reported as a typed wire frame
                self._send(0, _F_ERR, _encode_exc(exc))
                self.outcome = "fatal"
                return self.outcome
        eng = self.svc.engine
        self._send(0, _F_READY, {
            "pid": os.getpid(),
            "page_size": eng.page_size,
            "max_slots": eng.max_slots,
            "max_queue": self.svc.max_queue,
            "default_timeout_s": self.svc.default_timeout_s,
            "default_deadline_s": self.svc.default_deadline_s,
            "retry_budget": self.svc.retry_budget,
            "tick_stall_budget_s": self.svc.tick_stall_budget_s,
        })
        status = threading.Thread(target=self._status_loop,
                                  name="worker-status", daemon=True)
        status.start()
        if self.spec.telemetry_interval_s > 0:
            threading.Thread(target=self._telemetry_loop,
                             name="worker-telemetry", daemon=True).start()
        # router-silence watch (socket links only): a half-open partition
        # can leave this side's reads idle forever while its writes still
        # land — no error will ever arrive, so silence IS the signal
        silence_s = (self.spec.router_silence_timeout_s
                     if isinstance(self.transport, SocketTransport) else 0.0)
        poll_s = 0.25 if silence_s > 0 else None
        last_rx = time.perf_counter()
        self.outcome = "link_lost"
        while not self._stop.is_set():
            try:
                got = self.transport.recv(timeout_s=poll_s)
            except FrameProtocolError:
                if isinstance(self.transport, PipeTransport):
                    # a pipe preserves message boundaries: one undecodable
                    # frame does not poison the next (pre-transport parity)
                    logger.exception("worker dropped an undecodable frame")
                    continue
                logger.exception("worker dropped the connection on a "
                                 "protocol error")
                break
            except TransportError:
                break  # router died or closed: this incarnation is over
            if got is None:
                if (silence_s > 0
                        and time.perf_counter() - last_rx > silence_s):
                    logger.warning(
                        "router silent for %.1fs; treating the link as "
                        "partitioned", time.perf_counter() - last_rx)
                    break
                continue
            frame, _epoch = got
            last_rx = time.perf_counter()
            try:
                req_id, method, kwargs = frame
            except (TypeError, ValueError):
                # a malformed frame is a peer bug, not a reason to die
                # with a bare unpack traceback: answer typed and move on
                self._send(0, _F_ERR, _encode_exc(FrameProtocolError(
                    f"malformed request frame: {frame!r}")))
                continue
            if method == "__shutdown__":
                self.outcome = "shutdown"
                break
            if method == "__ping__":
                # router liveness probe: receiving it IS the point. A ping
                # carrying a transmit stamp (telemetry plane on) gets a
                # pong with this side's clock — the router's ClockSync
                # turns the exchange into an offset/RTT sample. Bare pings
                # (telemetry off, or an older router) stay answerless:
                # byte-identical to the pre-telemetry protocol.
                t_tx = (kwargs.get("t_tx")
                        if isinstance(kwargs, dict) else None)
                if t_tx is not None:
                    from sentio_tpu.infra.flight import get_flight_recorder

                    self._send(0, _F_PONG, {
                        "t_tx": t_tx,
                        "t_worker": time.perf_counter(),
                        "origin_s": get_flight_recorder().origin(),
                        "pid": os.getpid(),
                    })
                continue
            if method == "stream_cancel":
                with self._cancel_lock:
                    self._cancelled.add(int(kwargs["stream_id"]))
                continue
            threading.Thread(
                target=self._handle, args=(req_id, method, kwargs),
                name=f"worker-rpc-{req_id}", daemon=True,
            ).start()
        self._stop.set()
        return self.outcome


def worker_main(conn, spec: WorkerSpec) -> None:
    """Child-process entry point (spawned by :class:`ProcessReplica`)."""
    ensure_compile_cache()
    install_compile_listeners()  # this process's compiles, timed by program
    # the worker must die with its router even when wedged in XLA: the
    # router holds the other pipe end, so a clean router close() still
    # reaches the recv loop; SIGTERM from terminate() gets a fast exit
    signal.signal(signal.SIGTERM, lambda *_: os._exit(0))
    logging.basicConfig(level=logging.WARNING)
    server = _WorkerServer(PipeTransport(conn), spec)
    server.run()
    if server.svc is not None:
        try:
            server.svc.close()
        except Exception:  # noqa: BLE001 — exiting anyway
            logger.exception("worker service close failed")
    # skip interpreter/static teardown: daemon threads (pump, RPC
    # handlers) may still sit inside XLA, and C++ static destructors
    # running under them abort with "terminate called without an active
    # exception" — the service already closed, nothing left to flush
    os._exit(0)


def worker_main_socket(addr, spec: WorkerSpec, slot: int) -> None:
    """Child-process entry point for SOCKET workers spawned by
    :class:`ProcessReplica` (``REPLICA_MODE=socket``): dial the router's
    registry listener, register (versioned auth handshake → incarnation
    epoch), serve the connection — and, with ``spec.reconnect``, REDIAL
    with exponential backoff whenever the link dies. Each reconnection is
    a fresh incarnation (higher epoch): the engine+service survive, the
    link identity does not — everything sent before the reconnect is
    fenced router-side as stale. A worker that cannot reach the router
    for ``spec.reconnect_deadline_s`` straight exits rather than orphan
    itself.

    ``slot == -1`` is an ELASTIC JOIN: the registry assigns a slot and
    acks it back; the worker adopts the assignment so every redial keeps
    the same fleet identity instead of allocating a new slot per
    reconnect."""
    ensure_compile_cache()
    install_compile_listeners()  # this process's compiles, timed by program
    signal.signal(signal.SIGTERM, lambda *_: os._exit(0))
    logging.basicConfig(level=logging.WARNING)
    svc = None
    backoff = max(spec.reconnect_backoff_s, 0.05)
    give_up_at = None
    while True:
        try:
            transport = dial(
                addr, max_frame_bytes=spec.max_frame_bytes,
                frame_timeout_s=spec.frame_timeout_s, fault_scope="worker",
            )
            ack = send_hello(transport, spec.auth_token, slot, os.getpid())
            acked_slot = ack.get("slot") if isinstance(ack, dict) else None
            if isinstance(acked_slot, int) and acked_slot >= 0 \
                    and acked_slot != slot:
                # elastic join (slot == -1): adopt the registry's
                # assignment so a redial re-registers the SAME identity,
                # and re-label the yet-unbuilt service so worker-side
                # flight/telemetry lanes carry the granted slot
                slot = acked_slot
                skw = spec.factory_kwargs.get("service_kwargs")
                if isinstance(skw, dict):
                    skw["replica_id"] = slot
        except FrameProtocolError as exc:
            # definitive rejection (token/version drift): redialing burns
            # the reconnect deadline on a config error — die loudly; the
            # supervisor's respawn carries the current spec
            logger.error("worker registration rejected: %s", exc)
            break
        except TransportError as exc:
            now = time.perf_counter()
            if give_up_at is None:
                give_up_at = now + max(spec.reconnect_deadline_s, 1.0)
            if svc is None and not spec.reconnect:
                # never connected and no reconnect policy: die loudly; the
                # router's registration wait surfaces the typed timeout
                logger.error("worker registration failed: %s", exc)
                break
            if now >= give_up_at:
                logger.error("router unreachable for %.0fs; worker exiting",
                             spec.reconnect_deadline_s)
                break
            time.sleep(backoff)
            backoff = min(backoff * 2.0, spec.reconnect_max_backoff_s)
            continue
        give_up_at = None
        backoff = max(spec.reconnect_backoff_s, 0.05)
        server = _WorkerServer(transport, spec, svc=svc)
        outcome = server.run()
        svc = server.svc
        transport.close()
        if outcome in ("shutdown", "fatal") or not spec.reconnect:
            break
        logger.warning("worker slot %d lost its router link; redialing",
                       slot)
    if svc is not None:
        try:
            svc.close()
        except Exception:  # noqa: BLE001 — exiting anyway
            logger.exception("worker service close failed")
    os._exit(0)


# frame-emit: worker-to-router via=socket
def _push_final_err(transport, exc: BaseException) -> None:
    """One unsolicited typed err frame (req_id 0) outside any RPC loop —
    worker_serve's factory-failure and supersede notices ride the same
    worker-to-router channel the router's dispatcher already handles."""
    transport.send((0, _F_ERR, _encode_exc(exc)))


# frame-emit: handshake-to-dialer via=socket
def worker_serve(
    bind_host: str,
    bind_port: int,
    spec: WorkerSpec,
    stop_event: Optional[threading.Event] = None,
    bound_cb=None,
) -> None:
    """Advertised-worker entry (``REPLICA_WORKERS=host:port,...``): listen
    on ``bind_host:bind_port`` and serve router connections. The router
    dials in, authenticates (its hello carries the incarnation epoch its
    registry assigned), and drives the same RPC protocol. The accept loop
    KEEPS ACCEPTING while a connection is live: a router that restarted
    (or lost its old socket to a half-open partition) redials and the
    NEWEST handshake wins — the superseded connection gets a typed final
    error frame and closes, its server exits, and the shared service
    (engine, radix cache) carries straight over to the new link with no
    worker restart. A router ``__shutdown__`` closes the CONNECTION only:
    an advertised worker belongs to its operator, not to whichever router
    last dialed it. ``bound_cb`` (tests) receives the bound
    ``(host, port)``."""
    import socket as _socket

    ensure_compile_cache()
    install_compile_listeners()  # this process's compiles, timed by program

    stop = stop_event or threading.Event()
    listener = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
    listener.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
    listener.settimeout(0.2)
    listener.bind((bind_host, int(bind_port)))
    listener.listen(4)
    if bound_cb is not None:
        bound_cb(listener.getsockname())
    # one service shared across router connections, built ON the accept
    # thread exactly once — two racing router dials must never build two
    # engines. The CURRENT connection's server/transport/thread live here;
    # only the accept loop mutates them (single writer, no lock needed).
    svc = None
    current: dict = {"server": None, "transport": None, "thread": None}

    def _serve_conn(server: _WorkerServer, transport) -> None:
        try:
            server.run()
        except Exception:  # noqa: BLE001 — one connection, not the listener
            logger.exception("router connection serving crashed")
        finally:
            transport.close()

    try:
        while not stop.is_set():
            try:
                conn, _peer = listener.accept()
            except _socket.timeout:
                continue
            except OSError:
                break
            transport = SocketTransport(
                conn, max_frame_bytes=spec.max_frame_bytes,
                frame_timeout_s=spec.frame_timeout_s, fault_scope="worker",
            )
            try:
                hello = expect_hello(transport, spec.auth_token,
                                     timeout_s=10.0)
                epoch = int(hello.get("epoch", 0))
                transport.epoch = epoch
                transport.send((0, "hello_ack",
                                {"epoch": epoch, "pid": os.getpid()}))
            except TransportError as exc:
                logger.warning("rejected router connection: %s", exc)
                transport.close()
                continue
            except Exception:  # noqa: BLE001 — a hostile hello must not kill the listener
                logger.exception("router handshake crashed; connection "
                                 "dropped")
                transport.close()
                continue
            if svc is None:
                try:
                    factory = _resolve_factory(spec.factory)
                    svc = factory(**spec.factory_kwargs)
                except BaseException as exc:  # noqa: BLE001 — report, then die  # lint: allow(baseexception-swallow) — reported as a typed wire frame
                    logger.exception("worker service factory failed")
                    try:
                        _push_final_err(transport, exc)
                    except TransportError:
                        pass
                    transport.close()
                    break
            prev = current["server"]
            if prev is not None and not prev._stop.is_set():
                # newest connection wins: the stale link gets a typed
                # final error, then its transport is cut — its server
                # exits link_lost without touching the shared service
                try:
                    _push_final_err(current["transport"], ReplicaUnavailable(
                        "superseded by a newer router connection",
                        retryable=False,
                    ))
                except TransportError:
                    pass  # the stale link is already dead — cutting it anyway
                prev._stop.set()
                current["transport"].close()
            if current["thread"] is not None:
                current["thread"].join(timeout=5.0)
            server = _WorkerServer(transport, spec, svc=svc)
            thread = threading.Thread(
                target=_serve_conn, args=(server, transport),
                name="worker-serve-conn", daemon=True,
            )
            current.update(server=server, transport=transport,
                           thread=thread)
            thread.start()
    finally:
        try:
            listener.close()
        except OSError:
            pass
        server = current["server"]
        if server is not None:
            server._stop.set()
            current["transport"].close()
            if current["thread"] is not None:
                current["thread"].join(timeout=5.0)
        if svc is not None:
            try:
                svc.close()
            except Exception:  # noqa: BLE001 — shutting down anyway
                logger.exception("worker service close failed")


# --------------------------------------------------------------------------
# router side

class _PendingCall:
    __slots__ = ("q", "streaming")

    def __init__(self, streaming: bool = False) -> None:
        self.q: _queue.Queue = _queue.Queue()
        # a streaming call stays registered past its open ack (_F_OK): the
        # token frames that follow reuse the same req_id, and popping on
        # the ack would silently drop every one of them
        self.streaming = streaming


class _EngineFacade:
    """The slice of the engine surface ReplicaSet touches on a replica:
    routing probes and rebuild-warmup hooks. Compiles happen in the worker
    process, outside the router's compile fence, so the fence exemption is
    a no-op here."""

    def __init__(self, owner: "ProcessReplica", tokenizer,
                 page_size: int, max_slots: int) -> None:
        self._owner = owner
        self.tokenizer = tokenizer
        self.page_size = page_size
        self.max_slots = max_slots

    def peek_prefix(self, toks) -> int:
        return self._owner._peek_prefix(toks)

    def set_fence_exempt(self, exempt: bool) -> None:  # noqa: ARG002
        return None


class ProcessReplica:  # frame-emit: router-to-worker
    """Router-process shim over one worker process; presents the
    ``PagedGenerationService`` surface so ReplicaSet drives it unchanged.

    Liveness model: the worker pushes status frames at
    ``spec.status_interval_s``; every read-side probe (``backlog``,
    ``heartbeat_age``, ``broken``…) is served from the cached frame, so
    supervisor passes cost zero RPCs. Worker death is observed three ways,
    any of which flips :attr:`broken`: the dispatcher hits EOF/broken pipe,
    ``proc.is_alive()`` goes false, or the worker itself reports a latched
    ``broken``. All pending RPCs then fail with typed
    :class:`ReplicaUnavailable` — the same caller surface as an in-process
    replica whose engine latched broken."""

    def __init__(
        self,
        spec: WorkerSpec,
        tokenizer,
        replica_id: int = 0,
        build_timeout_s: float = 600.0,
        transport_mode: str = REPLICA_MODE_PROCESS,
        registry=None,
        connect_addr: Optional[tuple] = None,
        partition_timeout_s: float = 2.0,
        ping_interval_s: float = 0.5,
        heal_grace_s: float = 5.0,
        adopt_registration: bool = False,
        _adopt_state: Optional[dict] = None,
    ) -> None:
        self.spec = spec
        self.replica_id = replica_id
        self.build_timeout_s = build_timeout_s
        self._tokenizer = tokenizer
        # transport tier: "process" = spawn pipe (single host, PR 13
        # behavior, the default); "socket" = TCP frames — either a locally
        # spawned worker self-registering against the router's
        # WorkerRegistry listener, or (connect_addr set) an advertised
        # worker on ANOTHER host the router dials (REPLICA_WORKERS)
        self._transport_mode = (REPLICA_MODE_SOCKET
                                if transport_mode == REPLICA_MODE_SOCKET
                                else REPLICA_MODE_PROCESS)
        self._registry = registry
        self._connect_addr = connect_addr
        self.partition_timeout_s = max(float(partition_timeout_s), 0.0)
        self.ping_interval_s = max(float(ping_interval_s), 0.0)
        self.heal_grace_s = max(float(heal_grace_s), 0.0)
        if (self._transport_mode == REPLICA_MODE_SOCKET
                and registry is None):
            raise ValueError(
                "socket transport needs a WorkerRegistry (it owns the "
                "incarnation epochs and the stale-frame fence)")
        self._mutex = threading.Lock()
        self._calls: dict[int, _PendingCall] = {}  # guarded-by: _mutex
        self._next_id = 1  # guarded-by: _mutex
        # router-side ticket shadow (module docstring): every unanswered
        # generate/stream mirrored as a real _Ticket keyed by its RPC id,
        # so worker death or quarantine hands never-answered work to
        # survivors instead of failing it typed. Passive until a
        # supervising ReplicaSet calls enable_shadow_handoff().
        self._handoff_enabled = False  # guarded-by: _mutex
        self._shadow: dict[int, tuple[_Ticket, _PendingCall]] = {}  # guarded-by: _mutex
        # tickets ADOPTED from a dead sibling: this replica executes them
        # via RPC and the dispatcher finishes the ticket itself (the
        # original caller blocks on the ticket, not on a pending call)
        self._adopted: dict[int, dict] = {}  # guarded-by: _mutex
        self._dead = False  # guarded-by: _mutex
        self._death_reason = ""  # guarded-by: _mutex
        self._death_kind = ""  # guarded-by: _mutex
        self._closed = False  # guarded-by: _mutex
        self._status: dict = {}
        self._status_ts = 0.0
        self._last_stats: dict = {}
        # elastic fleet: reason string of a voluntary deregister frame
        # (None until one arrives). Single writer — the dispatcher thread —
        # with GIL-atomic reads from the supervisor, same discipline as
        # _status.
        self._deregister_reason: Optional[str] = None
        # fleet telemetry plane: last ACCEPTED telemetry frame (cached for
        # stats overlays), its arrival stamp (the telemetry-age source),
        # the worker flight recorder's perf_counter origin (trace
        # re-basing), and the NTP-style offset estimator the ping loop
        # feeds. Plain attribute writes from the dispatcher thread —
        # GIL-atomic snapshots, same discipline as _status.
        self._telemetry: dict = {}
        self._telemetry_ts = 0.0
        self._worker_origin_s: Optional[float] = None
        self._clock = ClockSync()
        self.epoch = 0  # incarnation epoch of THIS connection (socket)
        self._proc = None
        self._transport = None
        if _adopt_state is not None:
            # HEAL path (respawn after a partition): a live worker
            # re-registered — adopt the fresh connection + epoch, keep the
            # existing process
            self._proc = _adopt_state.get("proc")
            self._transport = _adopt_state["transport"]
            self.epoch = _adopt_state["epoch"]
        elif self._transport_mode == REPLICA_MODE_PROCESS:
            import multiprocessing

            # JAX is not fork-safe (see module docstring): the worker MUST
            # come up via spawn so its runtime initializes in a clean
            # interpreter
            ctx = multiprocessing.get_context("spawn")
            conn, child_conn = ctx.Pipe()
            self._proc = ctx.Process(  # lint: allow(no-fork) — spawn context
                target=worker_main, args=(child_conn, spec),
                name=f"sentio-replica-worker-{replica_id}", daemon=True,
            )
            self._proc.start()
            child_conn.close()  # the parent's copy; the worker holds its own
            self._transport = PipeTransport(conn)
        elif connect_addr is not None:
            # REPLICA_WORKERS dial-out: the worker runs on another host
            # behind worker_serve(); the router owns the epoch counter and
            # ships it in its hello. Dial failures retry with backoff up
            # to the build timeout — re-registration IS redialing here.
            self._transport, self.epoch = self._dial_advertised(
                build_timeout_s)
        elif adopt_registration:
            # elastic join: the worker ALREADY dialed the registry (hello
            # slot -1) and holds the granted slot — adopt the queued
            # registration instead of spawning anything. The process is
            # not ours to reap (it may live on another host); a broken
            # link is a plain socket death.
            (self._transport, _hello,
             self.epoch) = registry.await_registration(
                replica_id, build_timeout_s)
        else:
            # local socket spawn: the worker connects BACK to the
            # registry's listener and registers; frames then carry the
            # granted epoch
            import multiprocessing

            ctx = multiprocessing.get_context("spawn")
            self._proc = ctx.Process(  # lint: allow(no-fork) — spawn context
                target=worker_main_socket,
                args=(tuple(registry.address), spec, replica_id),
                name=f"sentio-replica-worker-{replica_id}", daemon=True,
            )
            self._proc.start()
            try:
                (self._transport, _hello,
                 self.epoch) = registry.await_registration(
                    replica_id, build_timeout_s)
            except BaseException:
                # the spawned child must not outlive a failed construction
                self._reap(join_timeout_s=5.0)
                raise
        # the handshake call is registered BEFORE the dispatcher starts: a
        # factory that fails instantly would otherwise race its err frame
        # past an unregistered req_id 0 and the build would time out instead
        # of surfacing the real error
        ready_call = _PendingCall()
        self._calls[0] = ready_call
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop,
            name=f"replica-worker-rx-{replica_id}", daemon=True,
        )
        self._dispatcher.start()
        ready = self._wait_ready(ready_call, build_timeout_s)
        self.engine = _EngineFacade(self, tokenizer,
                                    ready["page_size"], ready["max_slots"])
        self.max_queue = ready["max_queue"]
        self.default_timeout_s = ready["default_timeout_s"]
        self.default_deadline_s = ready["default_deadline_s"]
        self.retry_budget = ready["retry_budget"]
        self.tick_stall_budget_s = ready["tick_stall_budget_s"]
        if self._transport_mode == REPLICA_MODE_SOCKET:
            # socket liveness, send side: periodic pings keep the worker's
            # router-silence watch fed, and a ping whose write breaks is
            # the broken-write death signal no status frame can deliver.
            # Stamp the handshake as the first "status" so the partition
            # detector has a baseline before the first status frame lands.
            self._status_ts = time.perf_counter()
            if self.ping_interval_s > 0:
                threading.Thread(
                    target=self._ping_loop,
                    name=f"replica-worker-ping-{replica_id}", daemon=True,
                ).start()

    def _dial_advertised(self, build_timeout_s: float):
        """Dial a REPLICA_WORKERS-advertised worker with backoff; the
        registry assigns the incarnation epoch the hello carries."""
        deadline = time.perf_counter() + max(build_timeout_s, 1.0)
        backoff = 0.25
        last: Optional[Exception] = None
        while time.perf_counter() < deadline:
            transport = None
            try:
                transport = dial(
                    self._connect_addr,
                    max_frame_bytes=self.spec.max_frame_bytes,
                    frame_timeout_s=self.spec.frame_timeout_s,
                    fault_scope=f"r{self.replica_id}",
                )
                epoch = self._registry.assign_epoch(self.replica_id)
                send_hello(transport, self.spec.auth_token, self.replica_id,
                           os.getpid(), epoch=epoch)
                return transport, epoch
            except FrameProtocolError as exc:
                # a DEFINITIVE rejection (bad token, version mismatch):
                # redialing cannot fix configuration — fail fast so the
                # operator sees the real error instead of a 10-minute
                # build timeout
                if transport is not None:
                    transport.close()
                raise ReplicaUnavailable(
                    f"advertised worker {self._connect_addr} rejected the "
                    f"handshake: {exc}",
                    retryable=False,
                    details={"replica": self.replica_id,
                             "reason": "handshake_rejected"},
                ) from exc
            except TransportError as exc:
                last = exc
                if transport is not None:
                    transport.close()
                time.sleep(min(backoff,
                               max(deadline - time.perf_counter(), 0.0)))
                backoff = min(backoff * 2.0, 5.0)
        raise ReplicaUnavailable(
            f"advertised worker {self._connect_addr} unreachable within "
            f"{build_timeout_s:.0f}s: {last}",
            retry_after_s=2.0,
            details={"replica": self.replica_id, "reason": "dial_failed"},
        )

    def _ping_loop(self) -> None:
        # with the telemetry plane on, pings carry a transmit stamp and the
        # worker pongs with its clock — each round trip is one ClockSync
        # offset sample. Telemetry off keeps the bare {} payload: the wire
        # stays byte-identical to the pre-telemetry protocol.
        stamp = self.spec.telemetry_interval_s > 0
        while True:
            time.sleep(self.ping_interval_s)
            with self._mutex:
                if self._dead or self._closed:
                    return
            try:
                self._send_frame((0, "__ping__",
                                  {"t_tx": time.perf_counter()}
                                  if stamp else {}))
            except (TransportError, OSError):
                self._on_death(
                    "worker link broken on ping (broken write)",
                    kind="partition",
                )
                return

    # ------------------------------------------------------------- plumbing

    # frame-dispatch: worker-to-router via=pipe,socket
    def _wait_ready(self, call: "_PendingCall", timeout_s: float) -> dict:
        try:
            kind, payload = call.q.get(timeout=timeout_s)
        except _queue.Empty:
            self.close()
            raise ReplicaUnavailable(
                f"worker did not come up within {timeout_s:.0f}s",
                retryable=False,
            ) from None
        if kind == _F_ERR:
            self.close()
            raise _decode_exc(payload)
        if kind != _F_READY:
            self.close()
            raise ReplicaUnavailable(
                f"worker handshake sent {kind!r} before ready",
                retryable=False,
            )
        return payload

    # frame-dispatch: worker-to-router via=pipe,socket
    def _dispatch_loop(self) -> None:
        transport = self._transport
        while True:
            try:
                got = transport.recv()
            except TransportError as exc:
                # the dispatcher owns the read side: when it exits, the
                # connection is spent — close it so a dead incarnation
                # never parks an open fd (the partition-heal window keeps
                # the transport open precisely BECAUSE this loop is still
                # draining it; once it errors out, the drain is over)
                transport.close()
                self._on_death(f"worker connection lost: {exc}")
                return
            frame, epoch = got
            if (self._registry is not None
                    and epoch != self._registry.current_epoch(
                        self.replica_id)):
                # incarnation fence: this frame was sent by a PREVIOUS
                # incarnation of the slot's worker (e.g. buffered behind a
                # partition that later healed). Its tickets are already
                # terminal router-side — delivering it could resurrect a
                # dead ticket or double-deliver a stream chunk, so it is
                # dropped and counted instead.
                self._registry.note_stale_frame(self.replica_id)
                continue
            req_id, kind, payload = frame
            if kind == _F_STATUS:
                # plain attribute writes: GIL-atomic snapshot for probes
                self._status = payload
                self._status_ts = time.perf_counter()
                continue
            if kind == _F_TELEMETRY:
                self._ingest_telemetry(payload, epoch)
                continue
            if kind == _F_PONG:
                self._ingest_pong(payload)
                continue
            if kind == _F_DEREGISTER:
                # voluntary leave: latch the request (GIL-atomic write, one
                # writer — this dispatcher); the ReplicaSet supervisor
                # observes `deregister_requested` and runs the graceful
                # retire on its own cadence
                reason = (payload or {}).get("reason", "deregister") \
                    if isinstance(payload, dict) else "deregister"
                self._deregister_reason = str(reason)
                logger.info("replica %d worker requested deregistration "
                            "(%s)", self.replica_id, reason)
                continue
            call = None
            with self._mutex:
                adopted = self._adopted.get(req_id)
                if adopted is not None:
                    if kind in (_F_ERR, _F_END) or (
                        kind == _F_OK and not adopted["streaming"]
                    ):
                        self._adopted.pop(req_id, None)
                else:
                    call = self._calls.get(req_id)
                    if call is not None and (
                        kind in (_F_ERR, _F_END, _F_READY)
                        or (kind == _F_OK and not call.streaming)
                    ):
                        self._calls.pop(req_id, None)
                    # a request leaves the shadow at its first ANSWER
                    # frame: result/err for generates, first token frame
                    # (or end/err) for streams — the open ack only means
                    # the worker built the iterator, not that it admitted
                    if kind in (_F_TOK, _F_END, _F_ERR) or (
                        kind == _F_OK
                        and call is not None and not call.streaming
                    ):
                        self._shadow.pop(req_id, None)
            if adopted is not None:
                self._finish_adopted(adopted, kind, payload)
            elif call is not None:
                call.q.put((kind, payload))

    def _on_death(self, reason: str, *, process_death: bool = True,
                  keep_shadow: Optional[bool] = None,
                  kind: str = "") -> None:
        """Latch dead and wake every waiter. Shadowed tickets are the
        exception: with handoff enabled (and the replica not closing),
        they are KEPT for the supervisor's quarantine pass to extract and
        re-admit on survivors — their callers stay blocked on the pending
        queue until the handoff sentinel arrives. ``keep_shadow=False``
        (abandon, close) fails the remainder typed instead.
        ``kind="partition"`` marks a LINK death of a possibly-live worker:
        the rebuild path then waits for re-registration (heal) before
        reaching for the reap-and-respawn hammer."""
        with self._mutex:
            if self._dead:
                return
            self._dead = True
            self._death_reason = reason
            self._death_kind = kind
            keep = (self._handoff_enabled and not self._closed
                    if keep_shadow is None else keep_shadow)
            shadow_entries: list[tuple[_Ticket, _PendingCall]] = []
            if keep:
                # shadowed callers must NOT get the typed death error —
                # their tickets are about to move to a survivor
                for rid in self._shadow:
                    self._calls.pop(rid, None)
            else:
                for rid, entry in list(self._shadow.items()):
                    self._calls.pop(rid, None)
                    shadow_entries.append(entry)
                self._shadow.clear()
            adopted = list(self._adopted.values())
            self._adopted.clear()
            pending = list(self._calls.values())
            self._calls.clear()
            closed = self._closed
        exc = self._death_error()
        payload = _encode_exc(exc)
        for call in pending:
            call.q.put((_F_ERR, payload))
        for ticket, call in shadow_entries:
            call.q.put((_F_ERR, payload))
            finish_ticket_error(ticket, exc, "failed_over")
        for state in adopted:
            # the adopting ReplicaSet already finished its handoff pass;
            # a typed terminal outcome is all the remote caller needs
            finish_ticket_error(state["ticket"], exc, "failed_over")
        if not closed:
            logger.warning("replica %d worker died: %s", self.replica_id,
                           reason)
            if process_death:
                # the worker_deaths counter feeds the respawn-loop alert
                # (SentioTpuReplicaWorkerDead) — only actual process deaths
                # count; a stall-quarantine abandon of a live worker is the
                # stall watchdog's story, not a death
                try:
                    from sentio_tpu.infra.metrics import get_metrics

                    get_metrics().record_worker_death(self.replica_id)
                except Exception:  # noqa: BLE001 — telemetry is best-effort
                    pass

    def _death_error(self) -> ReplicaUnavailable:
        # _death_reason is written exactly once (under _mutex, before _dead
        # latches true) and only read after; the lock-free read is a
        # GIL-atomic str fetch
        reason = self._death_reason or "killed"  # lint: allow(lock-discipline) — GIL-atomic read after latch
        return ReplicaUnavailable(
            f"replica worker process died: {reason}",
            retry_after_s=2.0,
            details={"replica": self.replica_id, "reason": "worker_dead"},
        )

    def _send_frame(self, frame: tuple) -> None:
        self._transport.send(frame)

    def _call(self, method: str, kwargs: dict,
              timeout_s: Optional[float],
              shadow_ticket: Optional[_Ticket] = None) -> Any:
        """One blocking RPC. A dead worker — before or during the call —
        raises the typed death error; an unresponsive worker past
        ``timeout_s`` does too (a wedged RPC loop is indistinguishable
        from a dead one, and both are replica failures the caller should
        fail over from).

        With a ``shadow_ticket`` (generates, handoff enabled) the call is
        mirrored in the shadow queue: on worker death the supervisor's
        quarantine extracts the ticket and re-admits it on a survivor —
        the ``("handoff", ticket)`` sentinel tells this caller to wait on
        the ticket's event instead, spending no failover budget."""
        call = _PendingCall()
        shadowed = False
        with self._mutex:
            if self._dead:
                raise self._death_error()
            req_id = self._next_id
            self._next_id += 1
            self._calls[req_id] = call
            if shadow_ticket is not None and self._handoff_enabled:
                shadow_ticket.shadow_id = req_id
                self._shadow[req_id] = (shadow_ticket, call)
                kwargs = {**kwargs, "shadow_id": req_id}
                shadowed = True
        t0 = time.perf_counter()
        try:
            self._send_frame((req_id, method, kwargs))
        except (TransportClosed, BrokenPipeError, OSError):
            self._on_death("worker pipe broken on send")
            if not shadowed:
                with self._mutex:
                    self._calls.pop(req_id, None)
                raise self._death_error() from None
            # shadowed: fall through to the wait — the worker never saw
            # this request, so the dead-worker extraction hands it off
            # wholesale and the sentinel below wakes us
        wait = timeout_s if timeout_s and timeout_s > 0 else None
        try:
            kind, payload = call.q.get(timeout=wait)
        except _queue.Empty:
            with self._mutex:
                self._calls.pop(req_id, None)
                # unanswered AND un-handed-off: drop the shadow so a late
                # handoff cannot execute work whose caller already left
                self._shadow.pop(req_id, None)
            raise ReplicaUnavailable(
                f"worker RPC {method!r} unanswered after {timeout_s:.0f}s",
                retry_after_s=2.0,
                details={"replica": self.replica_id, "reason": "rpc_timeout"},
            ) from None
        if kind == "handoff":
            ticket: _Ticket = payload
            remaining = (max(wait - (time.perf_counter() - t0), 1.0)
                         if wait is not None else None)
            if not ticket.event.wait(remaining):
                raise ReplicaUnavailable(
                    f"handed-off {method!r} unanswered after "
                    f"{timeout_s:.0f}s",
                    retry_after_s=2.0,
                    details={"replica": self.replica_id,
                             "reason": "handoff_timeout"},
                )
            if ticket.error is not None:
                raise ticket.error
            return ticket.result
        if kind == _F_ERR:
            raise _decode_exc(payload)
        return payload

    @staticmethod
    def _rel_deadline(deadline_s: Optional[float],
                      deadline_ts: Optional[float]) -> Optional[float]:
        """perf_counter clocks do not compare across processes: absolute
        router deadlines cross the boundary as remaining seconds. An
        ALREADY-expired deadline raises here, router-side — shipping a
        non-positive remainder would read as ``deadline_s=0``, the
        explicit no-deadline opt-out, and silently un-expire the
        request (thread mode sheds it typed at admission)."""
        if deadline_ts is not None:
            rel = deadline_ts - time.perf_counter()
            if rel <= 0:
                raise DeadlineExceededError("deadline expired before submit")
            return rel
        return deadline_s

    # ------------------------------------------------------------------ api

    def generate(
        self,
        prompt: str,
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        timeout_s: Optional[float] = None,
        request_id: Optional[str] = None,
        deadline_s: Optional[float] = None,
        deadline_ts: Optional[float] = None,
        top_k: int = 0,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        cost_tokens: int = 0,
        seed: Optional[int] = None,
    ):
        wait = (timeout_s or self.default_timeout_s) + 30.0
        rel = self._rel_deadline(deadline_s, deadline_ts)
        shadow = None
        if self._handoff_enabled:  # lint: allow(lock-discipline) — GIL-atomic bool; _call re-checks under _mutex
            # the shadow mirror a dead-worker handoff re-admits on a
            # survivor; _call stamps shadow_id once the RPC id is known
            shadow = _Ticket(
                prompt, max_new_tokens, temperature, top_k=top_k,
                request_id=request_id, t_submit=time.perf_counter(),
                deadline_ts=(time.perf_counter() + rel
                             if rel is not None else None),
                tenant=tenant, priority=priority,
                cost_tokens=int(cost_tokens), seed=seed,
            )
        result = self._call("generate", dict(
            prompt=prompt, max_new_tokens=max_new_tokens,
            temperature=temperature, timeout_s=timeout_s,
            request_id=request_id,
            deadline_s=rel,
            top_k=top_k, tenant=tenant, priority=priority,
            cost_tokens=cost_tokens, seed=seed,
        ), timeout_s=wait, shadow_ticket=shadow)
        if shadow is not None and shadow.result is result:
            # handed off: the SURVIVOR already stamped its own replica_id
            return result
        result.replica_id = self.replica_id
        return result

    def generate_stream(
        self,
        prompt: str,
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        timeout_s: Optional[float] = None,
        request_id: Optional[str] = None,
        deadline_s: Optional[float] = None,
        deadline_ts: Optional[float] = None,
        top_k: int = 0,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        cost_tokens: int = 0,
        stats_out: Optional[dict] = None,
        prior_tokens: Optional[list] = None,
        seed: Optional[int] = None,
        progress: Optional[StreamProgress] = None,
    ) -> Iterator[str]:
        """Lazy, matching thread mode: the ``stream_open`` RPC — which
        admits AND starts decoding in the worker — defers to the first
        ``next()``. ``ReplicaSet._stream_impl`` discards and re-creates
        not-yet-started iterators (WFQ overflow re-bucketing, failover) on
        the promise that doing so costs nothing; an eager open here would
        leak a phantom decode per discarded iterator. The process-mode
        delta: thread mode's CALL-time validation (top_k vs speculation)
        also moves to the first ``next()`` — the SSE handler's admission
        pre-check still runs before its 200, and a validation error past
        that surfaces as the typed mid-stream error.

        ``progress`` mirrors the token ids behind every yielded piece
        (accumulated from the worker's per-frame token-id deltas), and
        ``prior_tokens``/``seed`` ride the RPC into the worker's service —
        the full resume-by-replay surface works across the boundary."""
        wait = (timeout_s or self.default_timeout_s) + 30.0
        return self._stream_open_and_pump(dict(
            prompt=prompt, max_new_tokens=max_new_tokens,
            temperature=temperature, timeout_s=timeout_s,
            request_id=request_id,
            deadline_s=deadline_s, deadline_ts=deadline_ts,
            top_k=top_k, tenant=tenant, priority=priority,
            cost_tokens=cost_tokens,
            prior_tokens=(list(prior_tokens) if prior_tokens else None),
            seed=seed,
        ), wait, stats_out, progress)

    def _stream_open_and_pump(self, req: dict, wait: float,
                              stats_out: Optional[dict],
                              progress: Optional[StreamProgress],
                              ) -> Iterator[str]:
        # generator body: nothing below runs until the first next()
        abs_deadline = req["deadline_ts"]
        req["deadline_s"] = self._rel_deadline(
            req.pop("deadline_s"), req.pop("deadline_ts"))
        if abs_deadline is None and req["deadline_s"]:
            abs_deadline = time.perf_counter() + req["deadline_s"]
        call = _PendingCall(streaming=True)
        shadowed = False
        with self._mutex:
            if self._dead:
                raise self._death_error()
            req_id = self._next_id
            self._next_id += 1
            self._calls[req_id] = call
            if self._handoff_enabled:
                # the stream's shadow mirror: it leaves the shadow at its
                # first token frame (a delivered-token stream rides the
                # ReplicaSet resume path, not the handoff)
                ticket = _Ticket(
                    req["prompt"], req["max_new_tokens"],
                    req["temperature"], top_k=req["top_k"],
                    stream_q=_queue.Queue(),
                    request_id=req.get("request_id"),
                    t_submit=time.perf_counter(),
                    deadline_ts=abs_deadline,
                    tenant=req.get("tenant"), priority=req.get("priority"),
                    cost_tokens=int(req.get("cost_tokens") or 0),
                    prior_tokens=req.get("prior_tokens"),
                    seed=req.get("seed"), shadow_id=req_id,
                )
                self._shadow[req_id] = (ticket, call)
                req["shadow_id"] = req_id
                shadowed = True
        try:
            self._send_frame((req_id, "stream_open", req))
        except (TransportClosed, BrokenPipeError, OSError):
            self._on_death("worker pipe broken on send")
            if not shadowed:
                with self._mutex:
                    self._calls.pop(req_id, None)
                raise self._death_error() from None
            # shadowed: the dead-worker extraction hands the ticket off;
            # the sentinel arrives on the pending queue below
        try:
            kind, payload = call.q.get(timeout=wait)
        except _queue.Empty:
            with self._mutex:
                self._calls.pop(req_id, None)
                self._shadow.pop(req_id, None)
            raise ReplicaUnavailable(
                f"worker stream open unanswered after {wait:.0f}s",
                retry_after_s=2.0,
                details={"replica": self.replica_id, "reason": "rpc_timeout"},
            ) from None
        if kind == "handoff":
            yield from self._drain_adopted_stream(payload, wait,
                                                  stats_out, progress)
            return
        if kind == _F_ERR:
            raise _decode_exc(payload)
        yield from self._stream_frames(req_id, call, wait, stats_out,
                                       progress)

    def _stream_frames(self, req_id: int, call: _PendingCall, wait: float,
                       stats_out: Optional[dict],
                       progress: Optional[StreamProgress],
                       ) -> Iterator[str]:
        done = False
        emitted: list[int] = []
        try:
            while True:
                try:
                    kind, payload = call.q.get(timeout=wait)
                except _queue.Empty:
                    raise ReplicaUnavailable(
                        f"worker stream stalled for {wait:.0f}s",
                        retry_after_s=2.0,
                        details={"replica": self.replica_id,
                                 "reason": "rpc_timeout"},
                    ) from None
                if kind == "handoff":
                    # never-dispatched stream moved to a survivor before
                    # any token frame: nothing delivered, clean switch
                    done = True
                    yield from self._drain_adopted_stream(
                        payload, wait, stats_out, progress)
                    return
                if kind == _F_TOK:
                    piece, delta = payload
                    emitted.extend(delta)
                    if progress is not None:
                        # rebound BEFORE the yield, like the service's own
                        # mirror: a consumer observing this piece (or the
                        # death exception) reads the delivered prefix
                        progress.tokens = list(emitted)
                    yield piece
                elif kind == _F_END:
                    done = True
                    stats, final_toks = payload
                    if progress is not None and final_toks is not None:
                        progress.tokens = list(final_toks)
                    if stats_out is not None and isinstance(stats, dict):
                        stats["replica_id"] = self.replica_id
                        stats_out.update(stats)
                    return
                else:  # _F_ERR
                    done = True
                    raise _decode_exc(payload)
        finally:
            with self._mutex:
                self._calls.pop(req_id, None)
                self._shadow.pop(req_id, None)
                dead = self._dead
            if not done and not dead:
                # consumer abandoned mid-stream: tell the worker (it cancels
                # the ticket between token frames — chunk-granular)
                try:
                    self._send_frame((0, "stream_cancel",
                                      {"stream_id": req_id}))
                except (TransportClosed, BrokenPipeError, OSError):
                    pass

    def _drain_adopted_stream(self, ticket: _Ticket, wait: float,
                              stats_out: Optional[dict],
                              progress: Optional[StreamProgress],
                              ) -> Iterator[str]:
        """Consume a stream ticket a survivor adopted: the survivor's pump
        (thread mode) or this class's adopt dispatcher (process mode)
        feeds ``ticket.stream_q`` with the service queue vocabulary. Only
        never-dispatched tickets are handed off, so nothing was delivered
        yet and decoding starts clean — same UTF-8 withholding as the
        service's own stream impl."""
        tokenizer = self._tokenizer
        emitted: list[int] = []
        flushed = ""
        done = False
        try:
            while True:
                try:
                    kind, payload = ticket.stream_q.get(timeout=wait)
                except _queue.Empty:
                    raise ReplicaUnavailable(
                        f"handed-off stream stalled for {wait:.0f}s",
                        retry_after_s=2.0,
                        details={"replica": self.replica_id,
                                 "reason": "handoff_timeout"},
                    ) from None
                if kind == "err":
                    done = True
                    raise payload
                if kind == "toks":
                    emitted.extend(payload)
                else:  # "done"
                    done = True
                    result = payload
                    if result.finish_reason == "error":
                        raise ReplicaUnavailable(
                            "paged decode failed mid-stream",
                            retry_after_s=2.0,
                            details={"replica": self.replica_id,
                                     "reason": "mid_stream"},
                        )
                    emitted = list(result.tokens)
                    if stats_out is not None:
                        stats_out.update(result.stats_dict())
                if progress is not None:
                    progress.tokens = list(emitted)
                text = tokenizer.decode(emitted)
                if kind == "done":
                    if len(text) > len(flushed):
                        yield text[len(flushed):]
                    return
                safe = text[:-1] if text.endswith("�") else text
                if len(safe) > len(flushed):
                    yield safe[len(flushed):]
                    flushed = safe
        finally:
            # consumer abandoned: in thread mode the adopting service's
            # pump reads this flag at its next loop; in process mode the
            # adopting replica's dispatcher observes it at the next token
            # frame and forwards a chunk-granular stream_cancel to its
            # worker. An EXPIRED ticket is left for the deadline sweep,
            # which counts it as expired — marking it cancelled here
            # would misfile a deadline miss under caller-abandoned (same
            # rule as the service's own stream impl)
            if not done and not (
                ticket.deadline_ts is not None
                and time.perf_counter() >= ticket.deadline_ts
            ):
                ticket.cancelled = True

    def check_admission(self, deadline_ts: Optional[float] = None) -> None:
        self._call("check_admission", {
            "deadline_rel_s": self._rel_deadline(None, deadline_ts),
        }, timeout_s=10.0)

    def _peek_prefix(self, toks) -> int:
        """Routing probe; MUST never fail OR stall a request — unlike
        thread mode's in-memory radix read this is a pipe RPC, and it sits
        on every incoming request's routing path. A worker whose status
        frames have gone stale is slow or wedged, so skip the RPC entirely
        (reads as a cold cache and the router routes elsewhere); a healthy
        worker answers from a handler thread in milliseconds, so the short
        timeout bounds the set-wide routing cost of a not-yet-detected
        wedge instead of stacking multi-second waits per replica."""
        stale_after = max(10 * self.spec.status_interval_s, 0.5)
        if (self._status_ts <= 0.0
                or time.perf_counter() - self._status_ts > stale_after):
            return 0
        try:
            return int(self._call("peek_prefix", {"toks": list(toks)},
                                  timeout_s=0.5))
        except Exception:  # noqa: BLE001 — prefix peek is an optional admission hint
            return 0

    def warmup(self, max_new_tokens: int = 4) -> dict:
        return self._call("warmup", {"max_new_tokens": max_new_tokens},
                          timeout_s=self.build_timeout_s)

    def backlog(self) -> int:
        return int(self._status.get("backlog") or 0)

    def projected_wait(self) -> Optional[float]:
        return self._status.get("projected_wait")

    def heartbeat_age(self) -> Optional[float]:
        """Worker-reported pump heartbeat age plus the status frame's own
        staleness. A worker whose status frames STOPPED while RPCs are in
        flight is itself wedged — that staleness is the age (the router's
        watchdog must detect a dead worker-side loop exactly like a dead
        pump)."""
        with self._mutex:
            if self._dead:
                return None
            pending = len(self._calls)
        if self._status_ts <= 0.0:
            return None
        stale = time.perf_counter() - self._status_ts
        age = self._status.get("heartbeat_age")
        if age is not None:
            return float(age) + stale
        interval = max(self.spec.status_interval_s, 0.02)
        if pending > 0 and stale > max(10 * interval, 2.0):
            return stale
        return None

    def duty_cycle(self) -> dict:
        return self._status.get("duty_cycle") or {
            "host": 0.0, "device": 0.0, "idle": 1.0,
        }

    def reset_duty_cycle(self) -> None:
        try:
            self._call("reset_duty_cycle", {}, timeout_s=10.0)
        except Exception:  # noqa: BLE001 — telemetry re-basing, best-effort
            pass

    @property
    def broken(self) -> bool:
        with self._mutex:
            if self._dead:
                return True
        if self._proc is not None and not self._proc.is_alive():
            self._on_death(f"worker exited (code {self._proc.exitcode})")
            return True
        if (self._transport_mode == REPLICA_MODE_SOCKET
                and self.partition_timeout_s > 0 and self._status_ts > 0):
            # transport-liveness leg the pipe never needed: a half-open
            # partition delivers no EOF and no broken write on THIS side —
            # the only observable is the worker's status stream going
            # silent. Staleness past the budget latches the same typed
            # death the supervisor's quarantine machinery already handles;
            # the (possibly live) worker rejoins as a fresh incarnation.
            stale = time.perf_counter() - self._status_ts
            if stale > self.partition_timeout_s:
                self._on_death(
                    f"partition suspected: no worker frames for "
                    f"{stale:.1f}s (budget {self.partition_timeout_s:.1f}s)",
                    process_death=False, kind="partition",
                )
                return True
        return bool(self._status.get("broken"))

    @property
    def closed(self) -> bool:
        with self._mutex:
            if self._closed:
                return True
        return bool(self._status.get("closed"))

    @property
    def deregister_requested(self) -> Optional[str]:
        """Reason string of this worker's voluntary deregister frame, or
        None. The ReplicaSet supervisor polls it to trigger a graceful
        retire (GIL-atomic read of a single-writer attribute)."""
        return self._deregister_reason

    def request_leave(self, reason: str = "leave") -> None:
        """Ask the worker to emit its voluntary deregister frame (drills /
        operator scale-in through the worker): the worker keeps serving;
        the supervisor's retire pass does the drain + handoff + close."""
        self._call("leave", {"reason": reason}, timeout_s=10.0)

    @property
    def tick_failure_count(self) -> int:
        return int(self._status.get("tick_failure_count") or 0)

    @property
    def pump_leaked_count(self) -> int:
        return int(self._status.get("pump_leaked") or 0)

    @property
    def pid(self) -> Optional[int]:
        if self._proc is not None:
            return self._proc.pid
        # dialed remote worker: no local process handle — the worker
        # reported its pid in the handshake/status stream
        return self._status.get("pid")

    def _proc_alive(self) -> bool:
        """Best liveness guess for the WORKER (not the link): a local
        process handle answers exactly; a dialed remote worker is presumed
        alive until its link death says otherwise."""
        if self._proc is not None:
            return self._proc.is_alive()
        with self._mutex:
            return not self._dead

    def _transport_stats(self) -> dict:
        if self._transport_mode != REPLICA_MODE_SOCKET:
            return {}
        out = {"transport": "socket", "incarnation": self.epoch}
        if self._registry is not None:
            out["stale_frames"] = self._registry.stale_frames(
                self.replica_id)
        return out

    def stats(self) -> dict:
        try:
            self._last_stats = self._call("stats", {}, timeout_s=10.0)
        except Exception:  # noqa: BLE001 — dead replica: last known stats
            out = {**self._last_stats, **self._transport_stats(),
                   "replica": self.replica_id, "worker_dead": 1}
            # a dead/partitioned worker's last telemetry frame still holds
            # its cumulative phase ledger — fleet duty math keeps counting
            # the seconds it actually burned instead of zeroing them
            cached = (self._telemetry.get("stats")
                      if self._telemetry else None) or {}
            for key in ("phase_seconds", "duty_elapsed_s", "duty_cycle"):
                if key not in out and key in cached:
                    out[key] = cached[key]
            return out
        self._last_stats.update(self._transport_stats())
        self._last_stats.update(self._clock_stats())
        return self._last_stats

    # ------------------------------------------------ fleet telemetry plane

    def _ingest_telemetry(self, payload: dict, epoch: int) -> None:
        """Dispatcher-thread sink for unsolicited telemetry frames: merge
        the worker's cumulative series snapshot into the router collector
        (epoch-fenced there — a healed worker's pre-partition buffer must
        not double-count), then cache the frame for stats overlays and
        zero the telemetry-age clock."""
        from sentio_tpu.infra.metrics import get_metrics

        metrics = get_metrics()
        try:
            res = metrics.merge_worker_series(
                self.replica_id, payload.get("series") or {},
                epoch=epoch, pid=payload.get("pid"))
        except Exception:  # noqa: BLE001 — telemetry must not kill dispatch
            logger.debug("replica %d telemetry merge failed",
                         self.replica_id, exc_info=True)
            return
        if not res.get("accepted"):
            return
        self._telemetry = payload
        self._telemetry_ts = time.perf_counter()
        origin = payload.get("origin_s")
        if origin is not None:
            # baselined cross-thread-race: dispatcher (telemetry/pong) and
            # caller (fetch_flight) both stamp this; it is a last-write-wins
            # float consumed only for trace re-basing, where the freshest
            # origin is always acceptable and a torn update is impossible
            # (attribute stores are GIL-atomic)
            self._worker_origin_s = float(origin)
        try:
            metrics.record_telemetry_age(self.replica_id, 0.0)
            stats = payload.get("stats") or {}
            for key in ("pool_hbm_bytes", "free_pages", "active_slots",
                        "queued"):
                if stats.get(key) is not None:
                    metrics.set_replica_stat(self.replica_id, key,
                                             float(stats[key]))
        except Exception:  # noqa: BLE001 — gauges are best-effort
            pass

    def _ingest_pong(self, payload: dict) -> None:
        """Pong for a timestamped ping: one NTP-style clock sample.
        ``offset = t_worker − (t_tx + rtt/2)`` inside ClockSync; the
        worker's flight origin rides along for trace re-basing."""
        try:
            self._clock.add_sample(float(payload["t_tx"]),
                                   time.perf_counter(),
                                   float(payload["t_worker"]))
            origin = payload.get("origin_s")
            if origin is not None:
                self._worker_origin_s = float(origin)
        except (KeyError, TypeError, ValueError):
            pass

    def clock_sync(self) -> Optional[dict]:
        """Current clock-offset estimate (min-RTT sample) or None before
        the first pong/fetch round trip."""
        return self._clock.estimate()

    def telemetry_age(self) -> Optional[float]:
        """Seconds since the last ACCEPTED telemetry frame, or None if the
        worker never shipped one (telemetry off, or pre-first-frame)."""
        if self._telemetry_ts <= 0:
            return None
        return time.perf_counter() - self._telemetry_ts

    def _clock_stats(self) -> dict:
        out: dict = {}
        age = self.telemetry_age()
        if age is not None:
            out["telemetry_age_s"] = round(age, 3)
        est = self._clock.estimate()
        if est is not None:
            out["clock_offset_s"] = round(est["offset_s"], 6)
            out["clock_uncertainty_s"] = round(est["uncertainty_s"], 6)
        return out

    def fetch_flight(self, request_id: Optional[str] = None,
                     last: Optional[int] = None,
                     timeout_s: float = 5.0) -> dict:
        """Pull flight data from the worker on demand: one request's
        record (``request_id``) or the whole tick window + record table.
        The reply echoes our transmit stamp, so every fetch doubles as a
        clock sample — pipe mode (no ping loop) gets its alignment here.
        Raises the replica's typed death error when the worker is gone."""
        reply = self._call(
            "fetch_flight",
            {"request_id": request_id, "last": last,
             "t_tx": time.perf_counter()},
            timeout_s=timeout_s)
        t_rx = time.perf_counter()
        try:
            if reply.get("t_tx") is not None:
                self._clock.add_sample(float(reply["t_tx"]), t_rx,
                                       float(reply["t_worker"]))
            if reply.get("origin_s") is not None:
                self._worker_origin_s = float(reply["origin_s"])
        except (TypeError, ValueError, KeyError):
            pass
        reply["replica"] = self.replica_id
        reply["epoch"] = self.epoch
        reply["clock"] = self._clock.estimate()
        return reply

    def cached_flight_lane(self, router_origin_s: float,
                           status: str) -> dict:
        """Fleet-trace lane for THIS incarnation built from the cached
        last telemetry frame — used when the worker is DEAD or RETIRED
        and ``fetch_flight`` can no longer answer. The 1 Hz telemetry
        frame ships counters rather than tick tables, so the lane is
        usually name-only; the point is that the incarnation still
        appears on the fleet timeline, marked ``(retired)``/``(dead)``,
        instead of silently vanishing from history."""
        shift, bound = self.flight_shift_s(router_origin_s)
        flight = (self._telemetry or {}).get("flight")
        ticks: list = []
        records: list = []
        if isinstance(flight, dict):
            ticks = list(flight.get("ticks") or [])
            records = list(flight.get("records") or [])
        return {
            "replica": self.replica_id,
            "epoch": self.epoch,
            "shift_s": shift,
            "uncertainty_s": bound,
            "ticks": ticks,
            "records": records,
            "status": status,
        }

    def flight_shift_s(self, router_origin_s: float) -> tuple:
        """``(shift_s, uncertainty_s)`` mapping this worker's flight
        timeline onto the router's: ``t_router = t_worker_timeline +
        shift``. Both recorders stamp relative to their own perf_counter
        origin, so the shift is ``worker_origin − offset − router_origin``
        (offset = worker clock minus router clock). Same-host Linux
        processes share CLOCK_MONOTONIC, so offset ≈ 0 and the shift is
        dominated by the origin difference. Uncertainty is None until a
        clock sample exists (shift then assumes offset 0)."""
        if self._worker_origin_s is None:
            return 0.0, None
        est = self._clock.estimate()
        offset = est["offset_s"] if est else 0.0
        shift = self._worker_origin_s - offset - router_origin_s
        return shift, (est["uncertainty_s"] if est else None)

    # ------------------------------------------------ quarantine / handoff

    def enable_shadow_handoff(self) -> None:
        """Arm router-side ticket shadowing (module docstring). Called by a
        SUPERVISING ReplicaSet: without a supervisor nobody would ever
        extract the shadow queue, so the default stays passive and worker
        death keeps its fail-fast typed surface."""
        with self._mutex:
            self._handoff_enabled = True

    def _pop_shadow(self, ids: Optional[list] = None) -> list:
        """Remove shadowed tickets (all of them, or exactly ``ids``) for
        handoff, wake their callers with the ``("handoff", ticket)``
        sentinel, and drop their pending-call registrations so a straggler
        frame from the old worker cannot double-answer."""
        entries: list[tuple[_Ticket, _PendingCall]] = []
        with self._mutex:
            take = (list(self._shadow.keys()) if ids is None
                    else [i for i in ids if i in self._shadow])
            for rid in take:
                entries.append(self._shadow.pop(rid))
                self._calls.pop(rid, None)
        out = []
        for ticket, call in entries:
            call.q.put(("handoff", ticket))
            out.append(ticket)
        return out

    def _fail_shadow(self, exc: ReplicaUnavailable) -> None:
        """Terminal typed outcome for any shadow/adopted residue — close()
        safety net for a death that latched with the shadow kept but whose
        handoff never came."""
        with self._mutex:
            entries = list(self._shadow.values())
            self._shadow.clear()
            adopted = list(self._adopted.values())
            self._adopted.clear()
        payload = _encode_exc(exc)
        for ticket, call in entries:
            call.q.put((_F_ERR, payload))
            finish_ticket_error(ticket, exc, "failed_over")
        for state in adopted:
            finish_ticket_error(state["ticket"], exc, "failed_over")

    def abandon(self, reason: str) -> list:
        """Stall-quarantine surface: ask the worker (its RPC loop survives a
        wedged pump) to abandon — admitted tickets fail typed in-worker,
        which unblocks their router-side RPCs with the typed error, and the
        never-dispatched inbox tickets come back BY SHADOW ID for handoff —
        then latch dead locally so every later call fails fast. Remaining
        shadowed work (mid-decode on the wedged worker) keeps its normal
        typed-failover path."""
        with self._mutex:
            dead = self._dead
            enabled = self._handoff_enabled
        ids: Optional[list] = None
        if not dead:
            try:
                ids = self._call("abandon", {"reason": reason},
                                 timeout_s=10.0)
            except Exception as exc:  # noqa: BLE001 — latch + hand off below
                # a systematically failing abandon RPC must be diagnosable,
                # not silent: one WARNING naming the worker (satellite fix)
                logger.warning(
                    "replica %d worker abandon RPC failed (%s: %s); "
                    "latching dead and handing off every shadowed ticket",
                    self.replica_id, type(exc).__name__, exc,
                )
        # RPC failed or worker already dead: ids=None hands off EVERY
        # unanswered shadowed ticket (a dead worker cannot say which had
        # dispatched; re-executed generates are idempotent caller-side)
        tickets = self._pop_shadow(ids) if enabled else []
        alive = self._proc_alive()
        self._on_death(f"abandoned: {reason}", process_death=not alive,
                       keep_shadow=False)
        return tickets

    def extract_inbox(self) -> list:
        """Quarantine handoff surface. A LIVE worker answers a
        bounded-timeout ``extract_inbox`` RPC naming exactly its
        never-dispatched inbox tickets (mid-decode work keeps its typed
        failover path); a dead (or unresponsive) worker hands off every
        unanswered shadowed ticket wholesale — the module-docstring
        re-execution contract."""
        with self._mutex:
            enabled = self._handoff_enabled
            dead = self._dead
        if not enabled:
            return []
        alive = not dead and self._proc_alive()
        ids: Optional[list] = None
        if alive:
            try:
                ids = self._call("extract_inbox", {}, timeout_s=10.0)
            except Exception:  # noqa: BLE001 — unresponsive == dead here
                logger.warning(
                    "replica %d extract_inbox RPC failed; handing off "
                    "every shadowed ticket", self.replica_id,
                )
                ids = None
        return self._pop_shadow(ids)

    def adopt(self, ticket: _Ticket) -> None:
        """Admit a ticket handed off from a quarantined sibling replica:
        re-register it against THIS worker's pipe. The original caller
        still blocks on the ticket (event for generates, ``stream_q`` for
        streams); the adopt dispatcher finishes the ticket from the
        worker's answer frames — no failover budget spent caller-side.
        Typed sheds surface synchronously (the handoff layer turns them
        into the ticket's terminal outcome)."""
        # the worker's own admission rules, checked without reserving —
        # raises the same typed errors a fresh submit would
        self.check_admission(ticket.deadline_ts)
        streaming = ticket.stream_q is not None
        req = dict(
            prompt=ticket.prompt, max_new_tokens=ticket.max_new_tokens,
            temperature=ticket.temperature, top_k=ticket.top_k,
            timeout_s=None, request_id=ticket.request_id,
            deadline_s=self._rel_deadline(None, ticket.deadline_ts),
            tenant=ticket.tenant, priority=ticket.priority,
            cost_tokens=ticket.cost_tokens, seed=ticket.seed,
        )
        if streaming:
            req["prior_tokens"] = ticket.prior_tokens
        with self._mutex:
            if self._dead:
                raise self._death_error()
            req_id = self._next_id
            self._next_id += 1
            req["shadow_id"] = req_id
            self._adopted[req_id] = {
                "ticket": ticket, "emitted": [], "streaming": streaming,
                "req_id": req_id,
            }
        try:
            self._send_frame(
                (req_id, "stream_open" if streaming else "generate", req))
        except (TransportClosed, BrokenPipeError, OSError):
            with self._mutex:
                self._adopted.pop(req_id, None)
            self._on_death("worker pipe broken on adopt send")
            raise self._death_error() from None

    def _finish_adopted(self, state: dict, kind: str, payload) -> None:
        """Adopt-dispatcher leg of :meth:`adopt`: translate the worker's
        answer frames into the ticket's terminal state. Runs on the
        dispatcher thread; the ticket is exclusively this replica's (its
        old service is dead), so no lock applies."""
        ticket: _Ticket = state["ticket"]
        if kind == _F_OK:
            if state["streaming"]:
                return  # stream open ack: admission is still in flight
            result = payload
            result.replica_id = self.replica_id
            if ticket.event.is_set():
                return
            ticket.result = result
            ticket.event.set()
        elif kind == _F_TOK:
            _piece, delta = payload
            state["emitted"].extend(delta)
            if ticket.cancelled and not state.get("cancel_sent"):
                # the consumer abandoned the adopted stream: no pump on
                # THIS side ever reads ticket.cancelled (the flag is set
                # by the dead replica's drain loop), so forward the
                # worker's chunk-granular stream cancel — same frame a
                # directly-owned abandoned stream sends — instead of
                # decoding the rest of the budget for nobody
                state["cancel_sent"] = True
                try:
                    self._send_frame((0, "stream_cancel",
                                      {"stream_id": state["req_id"]}))
                except (TransportClosed, BrokenPipeError, OSError):
                    pass
            if ticket.stream_q is not None:
                ticket.stream_q.put(("toks", list(delta)))
        elif kind == _F_END:
            stats, final_toks = payload
            stats = stats if isinstance(stats, dict) else {}
            result = PagedResult(
                request_id=-1, text="",
                tokens=list(final_toks if final_toks is not None
                            else state["emitted"]),
                prompt_tokens=0,
                finish_reason=str(stats.get("finish_reason") or "stop"),
                logprob_sum=float(stats.get("logprob_sum") or 0.0),
                logprob_min=float(stats.get("logprob_min") or 0.0),
                logprob_count=int(stats.get("logprob_count") or 0),
                replica_id=self.replica_id,
            )
            if ticket.event.is_set():
                return
            ticket.result = result
            if ticket.stream_q is not None:
                ticket.stream_q.put(("done", result))
            ticket.event.set()
        else:  # _F_ERR
            exc = _decode_exc(payload)
            if not isinstance(exc, Exception):
                exc = RuntimeError(str(exc))
            finish_ticket_error(ticket, exc, "failed_over")

    # ------------------------------------------------------------ lifecycle

    def respawn(self) -> "ProcessReplica":
        """A fresh worker incarnation — the supervisor's rebuild path
        (``ReplicaSet._rebuild`` duck-types this instead of
        ``engine.spawn_fresh()``). Pipe mode always spawns a fresh
        process. Socket mode decides:

        * **heal** — the (possibly live, link-partitioned) worker already
          re-registered, or does so within ``heal_grace_s``: adopt the new
          connection + epoch and keep the process (its engine, radix
          cache, and warm compiles survive the partition);
        * **respawn** — no re-registration in time: reap the old process
          (SIGTERM→SIGKILL) and spawn a fresh one, which self-registers;
        * **reconnected** — a dialed ``REPLICA_WORKERS`` worker: the
          router cannot spawn remotely, so 'respawn' duck-types to
          redialing with backoff (re-registration from the router's
          side); a still-unreachable worker surfaces the typed error and
          rides the supervisor's existing rebuild backoff."""
        if self._transport_mode == REPLICA_MODE_SOCKET:
            fresh = self._respawn_socket()
        else:
            fresh = ProcessReplica(
                self.spec, self._tokenizer, replica_id=self.replica_id,
                build_timeout_s=self.build_timeout_s,
            )
        with self._mutex:
            enabled = self._handoff_enabled
        if enabled:
            # the supervising set armed shadowing at construction; the
            # respawned incarnation inherits it (the set only enables
            # replicas it was BUILT with)
            fresh.enable_shadow_handoff()
        return fresh

    def _respawn_socket(self) -> "ProcessReplica":
        common = dict(
            replica_id=self.replica_id,
            build_timeout_s=self.build_timeout_s,
            transport_mode=REPLICA_MODE_SOCKET,
            registry=self._registry,
            partition_timeout_s=self.partition_timeout_s,
            ping_interval_s=self.ping_interval_s,
            heal_grace_s=self.heal_grace_s,
        )
        if self._connect_addr is not None:
            if self._transport is not None:
                self._transport.close()  # the dead link's fd, if still open
            fresh = ProcessReplica(self.spec, self._tokenizer,
                                   connect_addr=self._connect_addr, **common)
            outcome = "reconnected"
        else:
            adopt = None
            if (self._proc is not None and self._proc.is_alive()
                    and self.heal_grace_s > 0):
                try:
                    transport, _hello, epoch = (
                        self._registry.await_registration(
                            self.replica_id, self.heal_grace_s))
                    adopt = {"proc": self._proc, "transport": transport,
                             "epoch": epoch}
                except ReplicaUnavailable:
                    adopt = None
            if adopt is not None:
                fresh = ProcessReplica(self.spec, self._tokenizer,
                                       _adopt_state=adopt, **common)
                outcome = "heal"
                logger.info(
                    "replica %d healed: worker pid %s re-registered at "
                    "epoch %d", self.replica_id, fresh.pid, fresh.epoch)
            else:
                # the heal never came: the old link is spent for good —
                # close it (a dispatcher wedged in a silent recv would
                # otherwise park the fd forever) and reap the process
                if self._transport is not None:
                    self._transport.close()
                self._reap(join_timeout_s=5.0)
                fresh = ProcessReplica(self.spec, self._tokenizer, **common)
                outcome = "respawn"
        try:
            from sentio_tpu.infra.metrics import get_metrics

            get_metrics().record_worker_reconnect(outcome)
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            pass
        return fresh

    def _reap(self, join_timeout_s: float = 5.0) -> None:
        """Make sure the local worker process is gone: join a corpse,
        SIGTERM→SIGKILL a survivor. No-op for dialed remote workers."""
        proc = self._proc
        if proc is None:
            return
        if proc.is_alive():
            proc.terminate()
            proc.join(timeout=max(join_timeout_s, 0.5))
        if proc.is_alive():
            proc.kill()
            proc.join(timeout=max(join_timeout_s, 0.5))
        if not proc.is_alive():
            proc.join(timeout=0.1)  # reap the zombie entry

    def kill(self) -> None:
        """SIGKILL the worker — the chaos drill's real replica death. The
        dispatcher observes the broken pipe and fails all in-flight RPCs
        typed; the supervisor sees ``broken`` and respawns."""
        if self._proc is not None and self._proc.pid:
            try:
                os.kill(self._proc.pid, signal.SIGKILL)
            except (ProcessLookupError, OSError):
                pass

    def inject_fault(self, point: str, **rule_kwargs) -> None:
        """Arm a fault rule INSIDE the worker process (its faults registry
        is process-private). ``kill_process=True`` at e.g. ``paged.step``
        makes the next decode tick a real SIGKILL mid-dispatch."""
        self._call("inject_fault", {"point": point, **rule_kwargs},
                   timeout_s=10.0)

    def reset_faults(self) -> None:
        try:
            self._call("reset_faults", {}, timeout_s=10.0)
        except Exception:  # noqa: BLE001 — the worker may already be dead
            pass

    def _heal_candidate(self) -> bool:
        """True when the right rebuild move is to AWAIT this live,
        link-partitioned worker's re-registration instead of reaping it:
        socket-spawned, reconnect-armed, died of a partition, and the
        process is demonstrably still alive."""
        with self._mutex:
            dead, kind = self._dead, self._death_kind
        return (self._transport_mode == REPLICA_MODE_SOCKET
                and self._connect_addr is None
                and self.spec.reconnect
                and dead and kind == "partition"
                and self._proc is not None and self._proc.is_alive())

    def drain(self, deadline_s: float = 30.0) -> dict:
        """Worker-side graceful drain, then local close. A dead worker
        drains vacuously (its backlog died with it). A PARTITIONED worker
        that may heal is special: no shutdown frame (the half-open link
        may still deliver it and kill a worker about to re-register), no
        reap, transport left open so the dispatcher can drain — and
        stale-count — the pre-partition frames when the link unwedges."""
        heal = self._heal_candidate()
        result = {"drained": False, "abandoned": 0}
        if not heal:
            try:
                result = self._call("drain", {"deadline_s": deadline_s},
                                    timeout_s=deadline_s + 30.0)
            except Exception:  # noqa: BLE001 — dead worker: nothing to drain
                pass
        self.close(join_timeout_s=max(deadline_s, 1.0), reap=not heal)
        return result

    def close(self, join_timeout_s: float = 10.0, reap: bool = True) -> None:
        """Shut the worker down and REAP it: graceful shutdown frame, then
        SIGTERM, then SIGKILL — close() never returns with the child still
        runnable, so a closed set cannot leak orphan processes. (Dialed
        remote workers have no local process: their shutdown frame closes
        the CONNECTION; ``worker_serve`` keeps the worker alive for its
        operator.)

        ``reap=False`` is the rebuild path's partition-heal window: the
        worker process stays alive to re-register, and the old transport
        stays open so buffered pre-partition frames drain into the stale-
        frame fence instead of vanishing. ``respawn()`` reaps if the heal
        never comes; a later full ``close()`` reaps regardless."""
        with self._mutex:
            self._closed = True
        proc = self._proc
        if reap:
            try:
                self._send_frame((0, "__shutdown__", {}))
            except (TransportClosed, BrokenPipeError, OSError):
                pass
            if proc is not None:
                proc.join(timeout=max(join_timeout_s, 0.5))
                if proc.is_alive():
                    proc.terminate()
                    proc.join(timeout=5.0)
                if proc.is_alive():
                    proc.kill()
                    proc.join(timeout=5.0)
            if self._transport is not None:
                self._transport.close()
        self._on_death("closed", keep_shadow=False)
        # a death that latched EARLIER kept the shadow for a handoff that
        # never came — a closed replica can never hand off, so fail the
        # residue typed instead of leaving callers to their timeouts
        self._fail_shadow(ReplicaUnavailable(
            "replica worker closed before handoff",
            retry_after_s=2.0,
            details={"replica": self.replica_id, "reason": "closed"},
        ))
