"""PagedGenerationService: continuous batching as the live decode path.

Bridges the synchronous serving pipeline (graph nodes run on worker
threads, one per in-flight ``/chat``) onto ONE shared
:class:`~sentio_tpu.runtime.paged.ContinuousBatchingEngine`: every caller's
``generate`` drops its request into an inbox and blocks on its own event; a
single pump thread owns the engine outright — drain inbox → admit → fused
decode step → retire — for as long as any slot is live. Staggered requests
therefore share decode ticks (the whole point of continuous batching):
request B joins the compiled decode program at whatever step request A has
reached, no recompilation, no waiting for A to finish.

This replaces the reference's one-request-per-HTTP-call generation
(/root/reference/src/api/handlers/chat.py:148 — each graph.ainvoke owns its
LLM call end to end) and closes the round-1 gap where the paged engine
existed but nothing in the serving path used it.

Thread-safety: the engine is single-threaded by design and is touched ONLY
by the pump thread (no lock held across device ticks — an engine-wide lock
would let the pump starve submitters, since a hot loop reacquires an
uncontended lock before waiters wake). Submitters and the pump meet at
``_mutex``, held only for quick inbox/bookkeeping operations.

Overload & failure semantics (the request-lifecycle robustness layer):

* **admission control** — the inbox + admitted set is bounded by
  ``max_queue``; a submit over the bound (or while draining, or whose
  deadline the projected wait already exceeds) raises a typed
  :class:`~sentio_tpu.infra.exceptions.ServiceOverloaded` that the HTTP
  layer maps to 429/503 + ``Retry-After`` — shed fast, don't time out slow;
* **deadlines** — a per-request absolute deadline rides the ticket and the
  engine ``_Request``; the pump drops expired tickets before admission and
  cancels expired in-flight slots every tick, so the fused decode batch
  never spends sub-steps on a caller that already gave up;
* **crash containment** — a failed decode tick resets the engine and, when
  the reset succeeds, REQUEUES innocent waiters (each ticket carries a
  retry budget) instead of failing all of them; only exhausted-budget
  tickets see an error result, and ``_broken`` still latches when the
  reset itself fails;
* **graceful drain** — :meth:`drain` stops admitting, lets in-flight slots
  finish within a deadline, then closes (the serve app's shutdown hook).
"""

from __future__ import annotations

import logging
import queue as _queue
import threading
import time
from dataclasses import dataclass, field
from typing import Iterator, Optional

from sentio_tpu.analysis.sanitizer import (
    assert_held,
    bind_engine_owner,
    guard_locksets,
    make_lock,
)
from sentio_tpu.infra.exceptions import (
    DeadlineExceededError,
    ReplicaUnavailable,
    ServiceOverloaded,
)
from sentio_tpu.infra import tracing
from sentio_tpu.infra.flight import get_flight_recorder
from sentio_tpu.infra.metrics import get_metrics
from sentio_tpu.infra.phases import TICK_PHASES, duty_fractions, phases_to_ms
from sentio_tpu.runtime.paged import ContinuousBatchingEngine, PagedResult

logger = logging.getLogger(__name__)

__all__ = [
    "PagedGenerationService",
    "StreamProgress",
    "GenerationTimeout",
    "ServiceOverloaded",
    "DeadlineExceededError",
    "ReplicaUnavailable",
]


class GenerationTimeout(Exception):
    pass


class StreamProgress:
    """Delivered-state mirror for ONE streaming request: the exact token
    ids behind every text piece the iterator has yielded so far.

    The stream iterator REBINDS ``tokens`` right before each yield (and to
    the authoritative ``result.tokens`` at completion), so a consumer that
    observes a yield — or catches the iterator's mid-stream exception —
    reads the precise delivered prefix. That prefix is what the resume-by-
    replay path (ReplicaSet._stream_impl, runtime/replica.py) re-admits on
    a surviving replica as a prior context suffix after the prompt: the
    splice point for a mid-flight failover with zero duplicated and zero
    missing tokens. Single-threaded by contract: the producer (the stream
    iterator) and the consumer run on the SAME caller thread, interleaved
    by the yields themselves — no lock needed or taken."""

    __slots__ = ("tokens",)

    def __init__(self) -> None:
        self.tokens: list[int] = []

    def reset(self) -> None:
        self.tokens = []


def finish_ticket_error(ticket: "_Ticket", exc: Exception,
                        finish_reason: str) -> None:
    """THE terminal typed-error sequence for a ticket, shared by every
    path that ends one: result-free error, flight-record close, stream
    ``("err", exc)``, event set — exactly once (the event guard makes it
    idempotent). Caller must own the ticket: either hold the owning
    service's ``_mutex`` (``_finish_error_locked``) or hold it exclusively
    off any service's books (the ReplicaSet's quarantine handoff)."""
    if ticket.event.is_set():
        return
    ticket.error = exc
    if ticket.request_id:
        get_flight_recorder().finish_engine(
            ticket.request_id, finish_reason=finish_reason, error=str(exc)
        )
    if ticket.stream_q is not None:
        ticket.stream_q.put(("err", exc))
    ticket.event.set()


@dataclass
class _Ticket:
    prompt: str
    max_new_tokens: int
    temperature: float
    # per-request top-k (0 = off) — traced data on the fused decode
    # dispatch, so any k shares the engine's one compiled tick program
    top_k: int = 0
    event: threading.Event = field(default_factory=threading.Event)
    result: Optional[PagedResult] = None
    # terminal typed failure (deadline expiry, shed) — raised to the caller
    # instead of a result; exactly one of result/error is set at event time
    error: Optional[Exception] = None
    # streaming callers: the pump pushes ("toks", [ids...]) deltas after each
    # tick, ("done", result) at retirement, and ("err", exc) on a typed
    # failure; None for plain generate()
    stream_q: Optional[_queue.Queue] = None
    sent_tokens: int = 0  # how many emitted tokens were already pushed
    # caller abandoned (timeout / disconnected stream): the pump cancels the
    # engine request instead of decoding to max_new for nobody
    cancelled: bool = False
    # absolute time.perf_counter() deadline: expired tickets are dropped
    # before admission and cancelled mid-decode (None = no deadline)
    deadline_ts: Optional[float] = None
    # crash-containment budget: how many more times this ticket may be
    # requeued after a failed tick (with a successful engine reset) before
    # it gets the error result instead
    retries_left: int = 0
    # flight-recorder trace id (the serving layer's query_id) — None for
    # untraced callers; telemetry is still recorded to /metrics either way
    request_id: Optional[str] = None
    # submit / first-token wall clocks for TTFT+TPOT (0.0 = not yet seen)
    t_submit: float = 0.0
    t_first: float = 0.0
    # tokens already host-visible when t_first was stamped: TPOT divides the
    # post-first-tick interval by the tokens produced IN that interval (a
    # fused tick emits up to steps_per_tick tokens at once)
    tokens_first: int = 0
    # opaque fair-queueing metadata stamped by a fronting ReplicaSet
    # (runtime/replica.py): the service itself never reads these — they ride
    # the ticket so a quarantine-time inbox handoff can release/re-charge
    # the owning tenant's WFQ reservation on the surviving replica
    tenant: Optional[str] = None
    priority: Optional[str] = None
    cost_tokens: int = 0
    # resume-by-replay (runtime/replica.py): token ids spliced in as a
    # prior context suffix AFTER the tokenized prompt — the delivered
    # prefix of a stream that died mid-flight on a sibling replica. The
    # engine prefills (or radix-matches) prompt + prior and decode
    # continues from the splice point; emitted tokens are post-splice only
    prior_tokens: Optional[list] = None
    # sampling seed stamped at call time (None = engine RNG stream as-is):
    # folded once into the engine's SHARED RNG at admission — best-effort
    # reproducibility for a lone sampled request, not a per-request pinned
    # stream (a resumed sampled continuation is distribution-correct by
    # conditioning on the replayed prefix, with or without the seed)
    seed: Optional[int] = None
    # process-mode shadow key (runtime/worker.py): the router-side RPC id
    # this ticket is mirrored under, so a worker-side extract_inbox can
    # name its never-dispatched tickets back to the router's shadow queue
    shadow_id: Optional[int] = None
    # request stages (infra/phases.py): the span this admission hangs under
    # ("verify" for the audit's, whose stages are recorded but not observed
    # a second time), when the pump handed the ticket to engine.submit, and
    # the flight ticks of its admission and of its first token
    parent: Optional[str] = None
    t_engine: float = 0.0
    tick_admit: int = 0
    tick_first: int = 0

    @property
    def path(self) -> str:
        """Metric label for the TTFT/TPOT series: blocking vs streaming."""
        return "stream" if self.stream_q is not None else "paged"


@guard_locksets
class PagedGenerationService:
    """Thread-safe submit/wait facade + pump thread over the paged engine."""

    def __init__(
        self,
        engine: ContinuousBatchingEngine,
        default_timeout_s: float = 600.0,
        max_queue: Optional[int] = None,
        default_deadline_s: Optional[float] = None,
        retry_budget: int = 1,
        replica_id: int = 0,
        tick_stall_budget_s: float = 120.0,
        warmup_budget_s: float = 600.0,
    ) -> None:
        self.engine = engine
        self.default_timeout_s = default_timeout_s
        # position of this service in a ReplicaSet (runtime/replica.py) —
        # stamped onto flight-recorder tick events and engine records so
        # per-replica behavior is attributable; 0 for a standalone service
        self.replica_id = int(replica_id)
        # admission bound on waiting work (inbox + admitted, not yet done);
        # a submit past it sheds with 429 instead of queueing unboundedly.
        # The default is deliberately deep (8x slot depth): shedding is tail
        # protection against pathological pileups, not routine backpressure
        self.max_queue = (
            int(max_queue) if max_queue is not None
            else max(8 * engine.max_slots, 64)
        )
        # deadline applied to requests that carry none of their own
        # (None = requests without a deadline never expire)
        self.default_deadline_s = default_deadline_s
        # crash containment: requeues granted per ticket across failed ticks
        self.retry_budget = max(int(retry_budget), 0)
        # wall-clock budget one pump loop iteration may take before a
        # watchdog (ReplicaSet._supervise_once) declares the replica
        # STALLED: a tick blocked inside a wedged device dispatch raises
        # nothing, so heartbeat age is the only observable. Must comfortably
        # exceed the slowest legitimate tick INCLUDING a cold XLA compile;
        # 0 disables stall detection for this service.
        self.tick_stall_budget_s = max(float(tick_stall_budget_s), 0.0)
        # watchdog stand-down bound for WARMING: warmup ticks legitimately
        # run cold XLA compiles far past any sane stall budget, so the
        # heartbeat watchdog is exempted while ``_warming`` — but the
        # exemption EXPIRES after this many seconds, or a wedge DURING
        # warmup would only ever be caught by caller timeouts and hang the
        # spawn/rebuild path for minutes. Must comfortably exceed the
        # slowest legitimate full warmup sweep; 0 = exempt forever (the
        # pre-budget behavior).
        self.warmup_budget_s = max(float(warmup_budget_s), 0.0)
        # inbox + bookkeeping ONLY, never device work
        self._mutex = make_lock("PagedGenerationService._mutex")
        self._inbox: list[_Ticket] = []  # guarded-by: _mutex
        self._tickets: dict[int, _Ticket] = {}  # guarded-by: _mutex
        self._pump: Optional[threading.Thread] = None  # guarded-by: _mutex
        self._pump_running = False  # guarded-by: _mutex
        self._closed = False  # guarded-by: _mutex
        self._broken = False  # guarded-by: _mutex
        self._draining = False  # guarded-by: _mutex
        # overload/robustness telemetry (lifetime totals; /metrics publishes
        # them via stats() and the pump stamps them onto tick events)
        self._shed = 0  # guarded-by: _mutex
        self._expired = 0  # guarded-by: _mutex
        self._cancelled = 0  # guarded-by: _mutex
        self._requeued = 0  # guarded-by: _mutex
        self._tick_failures = 0  # guarded-by: _mutex
        self._pump_leaked = 0  # guarded-by: _mutex
        # stamped by the pump each loop iteration (perf_counter); 0.0 until
        # the first pump starts. The watchdog reads it through
        # heartbeat_age(): a running pump with pending work whose stamp
        # goes stale is wedged inside a dispatch — no exception to catch
        self._heartbeat_ts = 0.0  # guarded-by: _mutex
        # latched by abandon(): the replica layer gave up on a wedged pump
        self._abandoned = False  # guarded-by: _mutex
        # warmup in progress: ticks legitimately run cold XLA compiles far
        # past any sane stall budget, so the watchdog stands down — until
        # warmup_budget_s expires (see above)
        self._warming = False  # guarded-by: _mutex
        self._warming_since = 0.0  # guarded-by: _mutex
        # EMA of recent TTFT seconds, updated by the pump — the projected-
        # wait estimate admission control weighs against a deadline
        self._ttft_ema = 0.0  # guarded-by: _mutex
        # occupancy telemetry (the serving-path answer to BatcherStats):
        # ticks with >1 active slot are decode steps shared across requests
        self._ticks = 0  # guarded-by: _mutex
        self._active_sum = 0  # guarded-by: _mutex
        self._max_active = 0  # guarded-by: _mutex
        self._completed = 0  # guarded-by: _mutex
        # tick-phase attribution (infra/phases.py): cumulative seconds per
        # phase across every pump iteration, single-writer (the pump);
        # readers (stats/duty_cycle, any thread) take GIL-atomic snapshots
        # of float values — slight skew between keys is acceptable for a
        # duty-cycle gauge, and a mutex here would put a lock acquisition
        # on every pump iteration for telemetry's sake
        self._phase_totals = dict.fromkeys(TICK_PHASES, 0.0)  # guarded-by: pump-thread
        # duty-cycle wall-clock origin; reset_duty_cycle() re-bases it so
        # bench windows exclude warmup compiles
        self._duty_t0 = time.perf_counter()  # guarded-by: pump-thread

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
        shadow_id: Optional[int] = None,
    ) -> PagedResult:
        """Submit one request and block until its tokens are done. Safe to
        call from any number of threads concurrently — that concurrency IS
        the batch. A ``request_id`` ties this generation into the flight
        recorder's per-request trace (TTFT/TPOT + its decode-tick window).

        ``deadline_ts`` (absolute ``time.perf_counter()``) or ``deadline_s``
        (relative) bound how long the caller will wait: admission sheds when
        the deadline is unmeetable, and the pump cancels the request the
        tick its deadline passes. Raises :class:`ServiceOverloaded` (shed),
        :class:`DeadlineExceededError` (expired), or
        :class:`GenerationTimeout` (no deadline, plain timeout).

        ``tenant``/``priority``/``cost_tokens`` are opaque WFQ metadata a
        fronting ReplicaSet stamps for quarantine-time inbox handoff; a
        bare service ignores them."""
        self._check_top_k(top_k)
        deadline_ts = self._resolve_deadline(deadline_s, deadline_ts)
        ticket = _Ticket(prompt, max_new_tokens, temperature, top_k=top_k,
                         request_id=request_id, t_submit=time.perf_counter(),
                         deadline_ts=deadline_ts,
                         retries_left=self.retry_budget,
                         tenant=tenant, priority=priority,
                         cost_tokens=int(cost_tokens),
                         seed=seed, shadow_id=shadow_id,
                         parent=tracing.parent_for(request_id))
        if request_id:
            get_flight_recorder().note_engine_submit(
                request_id, t_submit=ticket.t_submit, replica_id=self.replica_id)
        try:
            with self._mutex:
                self._admit_ticket_locked(ticket)
        except Exception:
            # note_engine_submit already opened the tick window — close it,
            # or the record absorbs every unrelated future tick
            if request_id:
                get_flight_recorder().finish_engine(
                    request_id, finish_reason="rejected")
            raise
        wait_s = self._wait_budget(timeout_s, deadline_ts)
        if not ticket.event.wait(wait_s):
            # completion happens under _mutex, so deciding under the same
            # mutex is race-free: an event set between wait()'s timeout and
            # this check means the work FINISHED — return it instead of
            # raising a timeout that cancels completed work
            expired = (deadline_ts is not None
                       and time.perf_counter() >= deadline_ts)
            with self._mutex:
                finished = ticket.event.is_set()
                # an expired ticket is left for the pump's deadline sweep
                # (which cancels it AND counts it as expired); marking it
                # cancelled here would misfile it under caller-abandoned
                if not finished and not expired:
                    ticket.cancelled = True  # pump frees the slot next loop
            if not finished:
                if expired:
                    raise DeadlineExceededError(
                        "deadline expired before the result was ready"
                    )
                raise GenerationTimeout(
                    f"generation did not finish within {wait_s:.0f}s"
                )
        if ticket.error is not None:
            raise ticket.error
        assert ticket.result is not None
        return ticket.result

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
        shadow_id: Optional[int] = None,
        progress: Optional[StreamProgress] = None,
    ) -> Iterator[str]:
        """Streaming variant: yields decoded text increments as the shared
        decode batch produces them (chunks of up to steps_per_tick tokens —
        the streaming request STAYS in the continuous batch). UTF-8 safe: bytes buffer
        until they decode cleanly. Deadline semantics match
        :meth:`generate`; a deadline that passes mid-stream raises
        :class:`DeadlineExceededError` from the iterator.

        ``stats_out``: optional caller-owned dict filled with the finished
        request's logprob accumulators (logprob_mean/min/count, tokens)
        right before the final yield — a text iterator cannot return the
        PagedResult, and the confidence gate needs the numbers after the
        stream drains.

        ``prior_tokens``: resume-by-replay splice (ReplicaSet failover of a
        delivered-token stream): these token ids are admitted as a prior
        context suffix after the prompt, and the stream yields ONLY the
        post-splice continuation. ``progress``: caller-owned
        :class:`StreamProgress` mirroring the token ids behind every yield
        — the delivered state a router needs to build the NEXT splice."""
        # validated HERE, not in the generator body: a generator function
        # defers its body to the first next(), which would surface this
        # after an SSE handler already committed its 200
        self._check_top_k(top_k)
        return self._generate_stream_impl(
            prompt, max_new_tokens, temperature, timeout_s, request_id,
            deadline_s, deadline_ts, top_k, tenant, priority, cost_tokens,
            stats_out, prior_tokens, seed, shadow_id, progress,
        )

    def _generate_stream_impl(
        self,
        prompt: str,
        max_new_tokens: int,
        temperature: float,
        timeout_s: Optional[float],
        request_id: Optional[str],
        deadline_s: Optional[float],
        deadline_ts: Optional[float],
        top_k: int,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        cost_tokens: int = 0,
        stats_out: Optional[dict] = None,
        prior_tokens: Optional[list] = None,
        seed: Optional[int] = None,
        shadow_id: Optional[int] = None,
        progress: Optional[StreamProgress] = None,
    ) -> Iterator[str]:
        # NB: admission below is still deferred to the first next() (the
        # long-standing stream contract — SSE handlers pre-check via
        # check_admission before committing their 200)
        deadline_ts = self._resolve_deadline(deadline_s, deadline_ts)
        ticket = _Ticket(prompt, max_new_tokens, temperature, top_k=top_k,
                         stream_q=_queue.Queue(),
                         request_id=request_id, t_submit=time.perf_counter(),
                         deadline_ts=deadline_ts,
                         retries_left=self.retry_budget,
                         tenant=tenant, priority=priority,
                         cost_tokens=int(cost_tokens),
                         prior_tokens=(list(prior_tokens)
                                       if prior_tokens else None),
                         seed=seed, shadow_id=shadow_id,
                         parent=tracing.parent_for(request_id))
        if request_id:
            get_flight_recorder().note_engine_submit(
                request_id, t_submit=ticket.t_submit, replica_id=self.replica_id)
        try:
            with self._mutex:
                self._admit_ticket_locked(ticket)
        except Exception:
            if request_id:
                get_flight_recorder().finish_engine(
                    request_id, finish_reason="rejected")
            raise

        tokenizer = self.engine.tokenizer
        deadline = self._wait_budget(timeout_s, deadline_ts)
        emitted: list[int] = []
        flushed = ""
        try:
            while True:
                try:
                    kind, payload = ticket.stream_q.get(timeout=deadline)
                except _queue.Empty:
                    if (ticket.deadline_ts is not None
                            and time.perf_counter() >= ticket.deadline_ts):
                        raise DeadlineExceededError(
                            "deadline expired before the stream produced "
                            "anything"
                        ) from None
                    raise GenerationTimeout(
                        f"stream produced nothing for {deadline:.0f}s"
                    ) from None
                if kind == "err":
                    # typed terminal failure (deadline expiry, shed at
                    # requeue time) — surface it as the iterator's exception
                    raise payload
                if kind == "toks":
                    emitted.extend(payload)
                else:  # "done"
                    result: PagedResult = payload
                    if result.finish_reason == "error":
                        # typed mid-stream death: THIS service cannot
                        # restart a delivered-token stream without
                        # duplicating output, but a fronting ReplicaSet can
                        # resume it on a sibling by replay-prefilling the
                        # delivered prefix (progress carries the splice)
                        raise ReplicaUnavailable(
                            "paged decode failed mid-stream", retry_after_s=2.0,
                            details={"replica": self.replica_id,
                                     "reason": "mid_stream"},
                        )
                    emitted = list(result.tokens)  # authoritative final sequence
                    if stats_out is not None:
                        # filled BEFORE the final yield so the consumer sees
                        # the numbers as soon as the iterator is exhausted
                        stats_out.update(result.stats_dict())
                if progress is not None:
                    # delivered-state mirror, rebound BEFORE the yield so a
                    # consumer observing this piece (or this iteration's
                    # exception) reads exactly the tokens behind it
                    progress.tokens = emitted
                text = tokenizer.decode(emitted)
                if kind == "done":
                    # final flush is unconditional: the finished answer may
                    # genuinely end in a replacement char
                    if len(text) > len(flushed):
                        yield text[len(flushed):]
                    return
                # mid-stream: withhold AT MOST the final char — a trailing
                # '�' may be an incomplete UTF-8 sequence that the next token
                # resolves (a genuine replacement char flushes next round;
                # holding the whole tail would stall streams whose chunks
                # keep ending in replacement chars)
                safe = text[:-1] if text.endswith("�") else text
                if len(safe) > len(flushed):
                    yield safe[len(flushed):]
                    flushed = safe
        finally:
            # abandoned mid-decode (timeout, consumer disconnect → generator
            # close): tell the pump to cancel instead of decoding for nobody.
            # An EXPIRED stream is left for the pump's deadline sweep, which
            # counts it as expired — marking it cancelled here would misfile
            # a deadline miss under caller-abandoned (same rule as generate)
            if ticket.result is None and ticket.error is None and not (
                ticket.deadline_ts is not None
                and time.perf_counter() >= ticket.deadline_ts
            ):
                ticket.cancelled = True

    # ------------------------------------------------------------ admission

    def _check_top_k(self, top_k: int) -> None:
        """Mirror of the engine's submit-time rule (same ``top_k > 0``
        condition — k <= 0 means off everywhere), raised at the service API
        instead of inside the pump loop."""
        if top_k > 0 and getattr(self.engine, "_spec_tick", None) is not None:
            raise ValueError(
                "top_k sampling is not supported with paged speculation "
                "(the spec tick's accept/correct rule is temperature-only)"
            )

    def _resolve_deadline(
        self, deadline_s: Optional[float], deadline_ts: Optional[float]
    ) -> Optional[float]:
        """Absolute perf_counter deadline from the caller's absolute or
        relative form, falling back to the service default (None = none)."""
        if deadline_ts is not None:
            return deadline_ts
        rel = deadline_s if deadline_s is not None else self.default_deadline_s
        if rel is None or rel <= 0:
            return None
        return time.perf_counter() + rel

    def _wait_budget(
        self, timeout_s: Optional[float], deadline_ts: Optional[float]
    ) -> float:
        """How long the caller blocks: its timeout, capped near the deadline
        (+ grace for the pump to deliver the typed deadline error rather
        than a generic timeout racing it)."""
        wait = timeout_s or self.default_timeout_s
        if deadline_ts is not None:
            wait = min(wait, max(deadline_ts - time.perf_counter(), 0.0) + 5.0)
        return wait

    def backlog(self) -> int:
        """Requests waiting on this replica (inbox + admitted, not yet
        done) — the router's load signal."""
        with self._mutex:
            return len(self._inbox) + len(self._tickets)

    def projected_wait(self) -> Optional[float]:
        """Projected first-token wait for a request submitted NOW (TTFT-EMA
        scaled by backlog; None while cold) — the router's least-loaded
        key, the same estimate admission control weighs against deadlines."""
        with self._mutex:
            return self._projected_wait_locked(
                len(self._inbox) + len(self._tickets)
            )

    def heartbeat_age(self) -> Optional[float]:
        """Seconds since the pump last completed a loop iteration, or None
        when there is nothing to detect: no pump running, or no pending
        work (an idle service is never stalled). A non-None age past
        ``tick_stall_budget_s`` means the pump is wedged inside a dispatch
        that raises nothing — the watchdog's only observable for the hang
        fault class."""
        with self._mutex:
            if not self._pump_running or self._abandoned:
                return None
            if self._warming:
                # warmup stand-down — bounded by warmup_budget_s: past the
                # budget a stale heartbeat with pending work reads as a
                # stalled WARMUP, the blind spot the budget exists to close
                # (without it, a wedge during warmup hangs the spawn or
                # rebuild path until caller timeouts fire)
                over_budget = (
                    self.warmup_budget_s > 0
                    and self._warming_since > 0.0
                    and time.perf_counter() - self._warming_since
                    > self.warmup_budget_s
                )
                if not over_budget:
                    return None
            if not self._inbox and not self._tickets:
                return None
            if self._heartbeat_ts <= 0.0:
                return None
            return max(time.perf_counter() - self._heartbeat_ts, 0.0)

    def extract_inbox(self) -> list[_Ticket]:
        """Remove and return every never-dispatched inbox ticket (the
        quarantine handoff: these hold NO engine or KV state, so a
        surviving replica can adopt them wholesale). Cancelled/expired
        stragglers are closed out here rather than handed off. Safe against
        a wedged pump — it blocks OUTSIDE ``_mutex``, inside the device
        dispatch."""
        now = time.perf_counter()
        out: list[_Ticket] = []
        with self._mutex:
            for ticket in self._inbox:
                if ticket.event.is_set():
                    continue
                if ticket.cancelled:
                    self._close_cancelled_locked(ticket)
                    continue
                if ticket.deadline_ts is not None and now >= ticket.deadline_ts:
                    self._expired += 1
                    get_metrics().record_shed("expired")
                    self._finish_error_locked(
                        ticket,
                        DeadlineExceededError(
                            "deadline expired before admission"),
                        "expired",
                    )
                    continue
                out.append(ticket)
            self._inbox.clear()
        return out

    def adopt(self, ticket: _Ticket) -> None:
        """Admit a ticket object handed off from a quarantined sibling
        replica. Runs the normal admission checks (closed/broken/queue
        bound/deadline projection) — raises the same typed errors a fresh
        submit would, which the handoff layer turns into the ticket's
        terminal outcome."""
        with self._mutex:
            self._admit_ticket_locked(ticket)

    def abandon(self, reason: str) -> list[_Ticket]:
        """Give up on this service because its pump is wedged inside a
        device dispatch (stall-quarantine). A thread blocked in XLA cannot
        be killed, so recovery is abandonment: latch ``_broken`` (typed 503
        admissions from now on), fail every ADMITTED ticket with a typed
        :class:`ReplicaUnavailable` (their KV state dies with the wedged
        engine — generate callers fail over, delivered-token streams get
        the typed mid-stream error), and return the never-dispatched inbox
        tickets for handoff. Never joins the pump — ``close()`` does the
        bounded join and accounts the leak in ``pump_leaked``."""
        exc = ReplicaUnavailable(
            f"replica abandoned: {reason}", retry_after_s=2.0,
            details={"replica": self.replica_id, "reason": "stalled"},
        )
        with self._mutex:
            self._abandoned = True
            self._broken = True
            for ticket in list(self._tickets.values()):
                self._finish_error_locked(ticket, exc, "stalled")
            self._tickets.clear()
        return self.extract_inbox()

    @property
    def broken(self) -> bool:
        """Latched after a failed tick whose ``engine.reset()`` ALSO failed
        (or after :meth:`abandon` gave up on a wedged pump): the engine's
        device state is unrecoverable in place. A ReplicaSet supervisor
        reads this as the trip-immediately breaker signal."""
        with self._mutex:
            return self._broken

    @property
    def closed(self) -> bool:
        with self._mutex:
            return self._closed

    @property
    def tick_failure_count(self) -> int:
        """Lifetime failed decode ticks — the ReplicaSet supervisor's burst
        breaker polls this (cheaper than a full stats() snapshot)."""
        with self._mutex:
            return self._tick_failures

    @property
    def pump_leaked_count(self) -> int:
        """Pumps that outlived their close() join (usually a wedged device
        dispatch). A rebuild reads this off the incarnation it replaces so
        the ReplicaSet's summed count survives the swap."""
        with self._mutex:
            return self._pump_leaked

    def check_admission(self, deadline_ts: Optional[float] = None) -> None:
        """Raise the shed error a submit right now would raise, WITHOUT
        enqueuing. The SSE path calls this before committing a 200 status
        line — after ``response.prepare`` a shed can only degrade, not 429."""
        with self._mutex:
            self._check_available_locked()
            self._check_admission_locked(deadline_ts)

    def _check_available_locked(self) -> None:  # lock-held: _mutex
        """Closed / broken-engine admissions raise a TYPED 503 + Retry-After
        (ReplicaUnavailable) instead of the old bare RuntimeError → 500: a
        supervised replica rebuilds in place, so the honest answer to a
        caller is \"retry shortly\", not \"internal error\"."""
        assert_held(self._mutex)
        if self._closed:
            raise ReplicaUnavailable(
                "generation service is closed", retry_after_s=5.0,
                details={"replica": self.replica_id, "reason": "closed"},
            )
        if self._broken:
            raise ReplicaUnavailable(
                "paged decode engine is down (reset failed; awaiting "
                "supervised rebuild)", retry_after_s=5.0,
                details={"replica": self.replica_id, "reason": "broken"},
            )

    def _admit_ticket_locked(self, ticket: _Ticket) -> None:  # lock-held: _mutex
        assert_held(self._mutex)
        self._check_available_locked()
        self._check_admission_locked(ticket.deadline_ts)
        self._inbox.append(ticket)
        self._ensure_pump()

    def _check_admission_locked(
        self, deadline_ts: Optional[float]
    ) -> None:  # lock-held: _mutex
        """Admission control: shed (typed, fast) instead of queueing work
        the service cannot finish. Counts every rejection."""
        assert_held(self._mutex)
        now = time.perf_counter()
        if self._draining:
            self._shed += 1
            get_metrics().record_shed("draining")
            raise ServiceOverloaded(
                "generation service is draining", status=503,
                retry_after_s=5.0,
            )
        pending = len(self._inbox) + len(self._tickets)
        if pending >= self.max_queue:
            self._shed += 1
            get_metrics().record_shed("queue_full")
            raise ServiceOverloaded(
                f"decode queue full ({pending}/{self.max_queue} waiting)",
                status=429,
                retry_after_s=max(self._projected_wait_locked(pending) or 0.0, 1.0),
            )
        if deadline_ts is not None:
            remaining = deadline_ts - now
            if remaining <= 0:
                self._shed += 1
                get_metrics().record_shed("deadline")
                raise DeadlineExceededError("deadline expired before submit")
            projected = self._projected_wait_locked(pending)
            if projected is not None and projected > remaining:
                self._shed += 1
                get_metrics().record_shed("deadline")
                raise ServiceOverloaded(
                    f"projected wait {projected:.2f}s exceeds remaining "
                    f"deadline budget {remaining:.2f}s",
                    status=503, retry_after_s=1.0,
                )

    def _projected_wait_locked(
        self, pending: int
    ) -> Optional[float]:  # lock-held: _mutex
        """Crude first-token wait estimate: recent TTFT (EMA, pump-updated)
        scaled by backlog depth relative to the slot count. None until the
        first completion — a cold service never sheds on projection."""
        assert_held(self._mutex)
        if self._ttft_ema <= 0.0:
            return None
        return self._ttft_ema * (1.0 + pending / max(self.engine.max_slots, 1))

    # ------------------------------------------------------------ lifecycle

    def drain(self, deadline_s: float = 30.0) -> dict:
        """Graceful shutdown: stop admitting (new submits shed with 503),
        let in-flight and queued work finish for up to ``deadline_s``, then
        close. Waiters still pending at the deadline get the closed-service
        error result from the exiting pump. The final pump join inside
        ``close()`` is bounded by whatever remains of THIS deadline — a
        pump wedged in a device dispatch must not stretch a 5s drain into
        5s + a hardcoded join window. Returns what happened."""
        with self._mutex:
            self._draining = True
        t_end = time.perf_counter() + max(deadline_s, 0.0)
        pending = 0
        while True:
            with self._mutex:
                pending = len(self._inbox) + len(self._tickets)
            if pending == 0 or time.perf_counter() >= t_end:
                break
            time.sleep(0.02)
        # the join budget is the drain deadline's remainder (floor 1s so a
        # fully-consumed window still gives a HEALTHY exiting pump one
        # beat to fail its waiters and die instead of being miscounted as
        # leaked on a busy scheduler)
        self.close(join_timeout_s=max(t_end - time.perf_counter(), 1.0))
        return {"drained": pending == 0, "abandoned": pending}

    def close(self, join_timeout_s: float = 10.0) -> None:
        with self._mutex:
            self._closed = True
            pump = self._pump
        # join OUTSIDE the mutex: the exiting pump needs it to fail waiters
        if pump is None:
            return
        pump.join(timeout=max(join_timeout_s, 0.0))
        if pump.is_alive():
            # a pump that won't die is a leaked thread pinning the engine —
            # surface it (stats()['pump_leaked']) instead of silently
            # dropping the reference like the join's return value invites
            logger.warning(
                "paged decode pump %r did not exit within %.1fs "
                "(alive=%s, daemon=%s); thread leaked — see stats()",
                pump.name, join_timeout_s, pump.is_alive(), pump.daemon,
            )
            with self._mutex:
                self._pump_leaked += 1
        # drop the ref either way: close() is called twice on shutdown
        # (drain, then container cleanup) — re-joining a leaked (possibly
        # wedged) pump would stall another join window and double-count
        # the same leak; it is counted and logged exactly once above
        with self._mutex:
            if self._pump is pump:
                self._pump = None

    def duty_cycle(self) -> dict:
        """host/device/idle fractions of wall time since construction (or
        the last :meth:`reset_duty_cycle`), summing to 1. ``host`` is every
        phase that burns the pump thread — with N replicas in one process,
        host-fraction x N is the direct GIL ceiling ROADMAP item 1 argues
        from. Reads the pump-thread-owned totals GIL-atomically; per-key
        skew of at most one in-flight tick is acceptable for a gauge."""
        totals = dict(self._phase_totals)
        return duty_fractions(totals, time.perf_counter() - self._duty_t0)

    def reset_duty_cycle(self) -> None:
        """Re-base the duty-cycle window (e.g. after warmup, whose
        compile-dominated ticks would otherwise swamp the host fraction).
        Telemetry-grade: a tick racing the reset may leak one iteration's
        phases into the new window."""
        for key in list(self._phase_totals):
            self._phase_totals[key] = 0.0
        self._duty_t0 = time.perf_counter()

    def stats(self) -> dict:
        # engine fields are read without a lock: the pump owns the engine,
        # and these are GIL-atomic reads of ints/lists used for telemetry
        engine_stats = self.engine.stats()
        # phase totals are pump-thread-owned (see duty_cycle): snapshot
        # outside the mutex like the engine fields
        phase_seconds = {k: round(v, 6) for k, v in self._phase_totals.items()}
        duty = self.duty_cycle()
        duty_elapsed = round(time.perf_counter() - self._duty_t0, 6)
        with self._mutex:
            return {
                **engine_stats,
                "replica": self.replica_id,
                "queued_inbox": len(self._inbox),
                "ticks": self._ticks,
                "completed": self._completed,
                "avg_active_slots": (
                    round(self._active_sum / self._ticks, 3) if self._ticks else 0.0
                ),
                "max_active_slots": self._max_active,
                # overload / robustness surface
                "max_queue": self.max_queue,
                "draining": int(self._draining),
                "shed": self._shed,
                "expired": self._expired,
                "cancelled": self._cancelled,
                "requeued": self._requeued,
                "tick_failures": self._tick_failures,
                "pump_leaked": self._pump_leaked,
                "abandoned": int(self._abandoned),
                "tick_stall_budget_s": self.tick_stall_budget_s,
                "warmup_budget_s": self.warmup_budget_s,
                # tick-phase attribution: cumulative seconds per phase and
                # the host/device/idle duty cycle over the current window
                # (bench diffs phase_seconds snapshots for per-level duty)
                "phase_seconds": phase_seconds,
                "duty_elapsed_s": duty_elapsed,
                "duty_cycle": duty,
            }

    def warmup(self, max_new_tokens: int = 4) -> dict:
        """Compile the paged serving families before traffic (and before
        the compile fence arms — serve startup and bench call this under
        ``SENTIO_COMPILE_FENCE=1``). Coverage, all through the normal
        submit path so the pump keeps sole engine ownership:

        * one cold admission per achievable prefill-width bucket;
        * a radix head chain, then one admission per feasible
          (prior-bucket x suffix-width) pair sharing exactly that many
          pages with the head — any later request's radix hit lands on a
          compiled ``prior_prefill_scatter`` variant;
        * every tick-ladder rung, pinned deterministically via the
          engine's ``force_tick_steps`` hint (one short generation per
          rung);
        * a concurrent short-prompt burst sized to fill the multi-row
          admission buckets (best-effort: row grouping depends on drain
          timing).

        The full declared variant space remains the compile manifest's
        job (``sentio audit``); a fence error after this warmup names the
        residual variant to add here. Returns the prompt count and the
        XLA compiles the burst triggered."""
        with self._mutex:
            # stall watchdog stands down for the duration: warmup ticks
            # include multi-second cold compiles that would otherwise read
            # as a wedged pump (heartbeat stale + pending work). The
            # stand-down expires at warmup_budget_s (heartbeat_age) so a
            # wedge DURING warmup still quarantines instead of hanging the
            # spawn/rebuild path.
            self._warming = True
            self._warming_since = time.perf_counter()
        try:
            return self._warmup_impl(max_new_tokens)
        finally:
            with self._mutex:
                self._warming = False
                self._warming_since = 0.0

    def _warmup_impl(self, max_new_tokens: int) -> dict:
        import threading

        from sentio_tpu.analysis.audit import fence

        eng = self.engine
        before = fence.compiles_total()
        page = eng.page_size
        window = eng.max_pages_per_seq * page
        reserve = max_new_tokens + 2  # admission keeps this much headroom
        space = eng.compile_variant_space()
        widths = sorted({d["width"] for d in space["paged.prefill_scatter"]})
        pnbs = sorted({d["pnb"]
                       for d in space.get("paged.prior_prefill_scatter", [])
                       if d.get("pnb")})
        prompts = 0

        def run(text: str) -> None:
            nonlocal prompts
            # deadline_s=0 opts OUT of the service default deadline: warmup
            # generations include multi-second cold compiles, and expiring
            # them would abort startup (and leave fence variants uncompiled)
            self.generate(text, max_new_tokens=max_new_tokens,
                          temperature=0.0, deadline_s=0)
            prompts += 1

        # ByteTokenizer: 1 char = 1 token, +1 for BOS — a (w - 1)-char
        # prompt admits at exactly width bucket w. Each width uses a
        # DISTINCT digit: same-char prompts would radix-match the previous
        # width's inserted pages and take the prior path, leaving the cold
        # prefill_scatter variant uncompiled.
        for i, width in enumerate(widths):
            n = min(width, window - reserve) - 1
            if n >= 1:
                run(str(i % 10) * n)
        if pnbs:
            head_chars = min(window - reserve, max(pnbs) * page + 2) - 1
            if head_chars >= page:
                head = "h" * head_chars
                run(head)  # seeds the radix chain the combos match into
                run(head)  # full-match re-admission: deepest-prior variant
                combo = 0
                for pnb in pnbs:
                    # share exactly pnb pages with the head (BOS + chars),
                    # then diverge into a width-bucket suffix; the cycled
                    # suffix char (never 'h') keeps combos from matching
                    # EACH OTHER deeper than the intended prior
                    keep = pnb * page - 1
                    if keep < 1 or keep > len(head):
                        continue
                    for width in widths:
                        if pnb * page + width > window - reserve:
                            continue
                        fill = "abcdefgijklmnopqrstuvwxyz"[combo % 25]
                        run(head[:keep] + fill * width)
                        combo += 1
        # every declared fused-scan length, pinned via force_tick_steps so
        # rung coverage never races backlog timing (each rung decodes at
        # least max_new_tokens steps only if the rung allows — one short
        # generation per rung suffices to compile it)
        n_short = max(min(widths[0], window - reserve) - 1, 1)
        try:
            for rung in eng.tick_step_sizes():
                eng.force_tick_steps = rung
                run("r" * n_short)
        finally:
            eng.force_tick_steps = None
        # concurrent burst for the >1-row admission buckets; capped — row
        # grouping needs only max(ADMIT_BUCKETS)-deep backlog, not one
        # thread per production slot (run() is not used here — the count
        # is added after the join, avoiding a cross-thread race)
        burst_n = min(3 * eng.max_slots, 4 * max(eng.ADMIT_BUCKETS))
        threads = [
            threading.Thread(
                target=self.generate, args=("b" * n_short,),
                kwargs={"max_new_tokens": max_new_tokens,
                        "temperature": 0.0, "deadline_s": 0},
                name=f"paged-warmup-{k}", daemon=True,
            )
            for k in range(burst_n)
        ]
        for t in threads:
            t.start()
        for t in threads:
            # each burst generate bounds itself at default_timeout_s; the
            # join only outwaits that, never blocks forever on a wedged pump
            t.join(timeout=self.default_timeout_s + 60.0)
        prompts += len(threads)
        with self._mutex:
            # warmup TTFTs are compile-dominated — seeding the admission
            # EMA with them would shed the first real deadline-carrying
            # requests on a wildly inflated projected wait
            self._ttft_ema = 0.0
        return {"prompts": prompts,
                "xla_compiles": fence.compiles_total() - before}

    # ----------------------------------------------------------------- pump

    def _ensure_pump(self) -> None:  # lock-held: _mutex
        assert_held(self._mutex)
        if not self._pump_running:
            self._pump_running = True
            # fresh burst, fresh liveness: without this stamp the watchdog
            # would read the PREVIOUS burst's last heartbeat against the
            # new burst's pending work and false-positive a stall in the
            # spawn window
            self._heartbeat_ts = time.perf_counter()
            self._pump = threading.Thread(
                target=self._run, name="paged-decode-pump", daemon=True
            )
            self._pump.start()

    def _run(self) -> None:
        # sanitizer: pump threads are born per burst — each new pump is an
        # authorized ownership transfer of the single-driver engine
        bind_engine_owner(self.engine)
        # short ticks while callers wait in OUR inbox, not just the engine
        # queue (len() reads are GIL-atomic; this is a hint, not a lock)
        # depth, not a bool: the engine scales its tick size by backlog
        self.engine.pressure_hint = lambda: len(self._inbox)  # lint: allow(lock-discipline)
        recorder = get_flight_recorder()
        metrics = get_metrics()
        # baselines for diffing the engine's lifetime counters into per-tick
        # attributions (pump-local: a restarted pump re-baselines, so the
        # first tick of a new burst never inherits the previous burst's work)
        from sentio_tpu.analysis.audit import fence

        def paged_compiles() -> int:
            # per-ENGINE attribution: sum the cache-miss counts of this
            # engine's own FamilyFn instances (their `_seen` fields) — a
            # concurrent contiguous-engine compile, train step, or a
            # second paged service in the same process must not be pinned
            # on an innocent tick of THIS pump
            total = 0
            for attr in ContinuousBatchingEngine.FAMILY_ATTRS:
                fn = getattr(self.engine, attr, None)
                total += getattr(fn, "_seen", 0) or 0
            return total

        last_prefill = self.engine.prefill_tokens_total
        last_decode = self.engine.decode_tokens_total
        last_spec = self.engine.spec_emitted_total
        last_compiles = paged_compiles()
        fence.drain_events()  # events before this burst belong to no tick
        last_hit_toks = self.engine.prefix_hit_tokens_total
        last_miss_toks = self.engine.prefix_miss_tokens_total
        while True:
            # the whole iteration runs under the profiler's step marker:
            # inside an armed /debug/profile window the device trace groups
            # what ran by the flight tick number it carries
            step_num = recorder.next_tick()
            with tracing.tick_annotation(step_num):
                t_iter = now = time.perf_counter()
                with tracing.annotation("tick.inbox_drain"), self._mutex:
                    # heartbeat: the watchdog's liveness signal. Stamped at the
                    # top of EVERY loop iteration, so a tick wedged inside the
                    # device dispatch below leaves the stamp aging while the
                    # backlog grows — exactly the stall signature
                    self._heartbeat_ts = now
                    for ticket in self._inbox:
                        if ticket.cancelled:
                            # abandoned before admission
                            self._close_cancelled_locked(ticket)
                            continue
                        if (ticket.deadline_ts is not None
                                and now >= ticket.deadline_ts):
                            # expired before admission: never pay prefill for a
                            # caller that already gave up
                            self._expired += 1
                            metrics.record_shed("expired")
                            self._finish_error_locked(
                                ticket,
                                DeadlineExceededError(
                                    "deadline expired before admission"),
                                "expired",
                            )
                            continue
                        ticket.t_engine = time.perf_counter()
                        rid = self.engine.submit(
                            ticket.prompt,
                            max_new_tokens=ticket.max_new_tokens,
                            temperature=ticket.temperature,
                            deadline_ts=ticket.deadline_ts,
                            top_k=ticket.top_k,
                            prior_tokens=ticket.prior_tokens,
                            seed=ticket.seed,
                            trace_id=ticket.request_id,
                        )
                        self._tickets[rid] = ticket
                    self._inbox.clear()
                    # abandoned or expired callers: stop decoding for nobody,
                    # free the slot for live traffic
                    for rid, ticket in list(self._tickets.items()):
                        if ticket.cancelled:
                            self.engine.cancel(rid)
                            self._tickets.pop(rid, None)
                            self._close_cancelled_locked(ticket)
                        elif (ticket.deadline_ts is not None
                              and now >= ticket.deadline_ts):
                            self.engine.cancel(rid)
                            self._tickets.pop(rid, None)
                            self._expired += 1
                            metrics.record_shed("expired")
                            self._finish_error_locked(
                                ticket,
                                DeadlineExceededError(
                                    "deadline expired mid-decode; request "
                                    "cancelled"),
                                "expired",
                            )
                    if self._closed or not self.engine.has_work:
                        # flag flips inside the mutex: a racing submit either
                        # lands in the inbox before this check (we continue) or
                        # sees _pump_running=False and starts a fresh pump
                        self._pump_running = False
                        if self._closed:
                            self._fail_all_locked("service closed")
                        return
                # device work runs WITHOUT any lock: the pump is the engine's
                # only driver, and submitters must never wait on a decode tick
                t_drain = time.perf_counter()
                # what this iteration dispatches is stamped with its number
                self.engine.tick_step = step_num
                try:
                    finished = self.engine.step()
                    tick_dur_s = time.perf_counter() - t_drain
                except Exception:
                    t_fail = time.perf_counter()
                    logger.exception(
                        "paged decode tick failed; attempting crash containment")
                    # flush the FAILED iteration's partial phase snapshot
                    # (residual folded into "other"): the success path's
                    # record/amend never runs on this branch, and without the
                    # flush a chaos round's Perfetto trace holes every failed
                    # tick and the duty-cycle gauge under-counts host time.
                    # sum(phase_ms) == pump_ms holds here too, by construction.
                    try:
                        # full bounded key shape (zeros included): the tier-1
                        # conservation gate pins phase_ms records to exactly
                        # TICK_PHASES, failed ticks included
                        phase_s = dict.fromkeys(TICK_PHASES, 0.0)
                        partial = getattr(
                            self.engine, "partial_step_phases", dict)() or {}
                        for key, val in partial.items():
                            if key in phase_s:
                                phase_s[key] = val
                        phase_s["inbox_drain"] = t_drain - t_iter
                        pump_s = t_fail - t_iter
                        phase_s["other"] = phase_s.get("other", 0.0) + max(
                            pump_s - sum(phase_s.values()), 0.0
                        )
                        row_steps = self._row_steps()
                        recorder.record_tick(
                            event="tick_failure", replica=self.replica_id,
                            step=step_num,
                            dur_ms=round((t_fail - t_drain) * 1e3, 3),
                            pump_ms=round(pump_s * 1e3, 3),
                            phase_ms=phases_to_ms(phase_s),
                            **row_steps,
                        )
                        metrics.record_tick_phases(phase_s)
                        metrics.record_row_steps(row_steps["row_steps"], row_steps["kv_pages"],
                                                 row_steps["moe_pairs"], row_steps["prefill_latent"],
                                                 row_steps["prefill_turns"], row_steps["conv_state"],
                                                 row_steps["ssm_state"], row_steps["lane_admissions"])
                        for key, val in phase_s.items():
                            self._phase_totals[key] = (
                                self._phase_totals.get(key, 0.0) + val
                            )
                    except Exception:  # noqa: BLE001 — telemetry best-effort
                        logger.debug("failed-tick phase telemetry failed",
                                     exc_info=True)
                    # the failed dispatch may have consumed the donated pool
                    # buffers and left slots half-admitted — rebuild the decode
                    # state so the NEXT request gets a working engine instead of
                    # a permanently poisoned one. Reset runs BEFORE waiters are
                    # touched and before _pump_running flips: this pump still
                    # exclusively owns the engine, so a retrying caller cannot
                    # start a new pump that races the reset.
                    reset_ok = True
                    try:
                        self.engine.reset()
                    except Exception:
                        logger.exception("paged engine reset failed; paged path disabled")
                        reset_ok = False
                    casualties: list[_Ticket] = []
                    with self._mutex:
                        self._tick_failures += 1
                        if not reset_ok:
                            self._pump_running = False
                            self._broken = True
                            self._fail_all_locked(
                                "decode tick failed; engine reset failed")
                            return
                        # crash containment: the reset brought the engine back —
                        # requeue innocent waiters instead of failing every one
                        # of them. ADMITTED tickets were part of the failed tick
                        # and burn one retry; inbox tickets never dispatched, so
                        # they requeue for free (charging them would let a
                        # request exhaust its budget with zero execution
                        # attempts). Only exhausted-budget tickets — or streams
                        # that already delivered tokens, which cannot restart
                        # without duplicating output — get the error result.
                        survivors: list[_Ticket] = []
                        requeued = 0
                        for ticket in self._tickets.values():
                            if ticket.event.is_set():
                                continue
                            if ticket.cancelled:
                                # abandoned caller swept up in the crash
                                self._close_cancelled_locked(ticket)
                                continue
                            resumable = (
                                ticket.stream_q is None or ticket.sent_tokens == 0
                            )
                            if resumable and ticket.retries_left > 0:
                                ticket.retries_left -= 1
                                requeued += 1
                                survivors.append(ticket)
                            else:
                                casualties.append(ticket)
                        for ticket in self._inbox:
                            if ticket.event.is_set():
                                continue
                            if ticket.cancelled:
                                self._close_cancelled_locked(ticket)
                                continue
                            survivors.append(ticket)  # free: never dispatched
                        self._tickets.clear()
                        self._inbox.clear()
                        self._inbox.extend(survivors)
                        self._requeued += requeued
                        for ticket in casualties:
                            self._fail_ticket_locked(ticket, "decode tick failed")
                        if casualties:
                            # counted BEFORE the early returns below, or pump
                            # exits (no survivors / closed) would drop exactly
                            # the sheds where waiters actually failed
                            metrics.record_shed("crash", len(casualties))
                        if self._closed:
                            self._pump_running = False
                            self._fail_all_locked("service closed")
                            return
                        if not self._inbox:
                            self._pump_running = False
                            return
                    # requeued tickets resubmit at the top of the loop; THIS
                    # pump keeps engine ownership across the reset (no handoff)
                    continue
                # in-tick occupancy from the engine: rows that shared the fused
                # decode dispatch (post-tick slot counts would miss requests that
                # retired inside the tick)
                active = getattr(self.engine, "last_tick_active", None)
                if active is None:
                    active = sum(s.active for s in self.engine.slots)
                t_step_end = time.perf_counter()
                # flight-recorder tick event BEFORE delivery: finish_engine in
                # the deliver section stamps tick_last from the recorder's
                # sequence, and the request-window filter (first < tick <=
                # last) must include the tick a request FINISHED in — recording
                # after delivery would silently drop every request's final tick
                # from /debug/flight. The completed phase decomposition cannot
                # exist yet (delivery hasn't happened); it is AMENDED onto this
                # event below. Telemetry is strictly best-effort — an exception
                # here must never kill the pump (waiters would hang).
                tick_seq = None
                try:
                    engine = self.engine
                    queued = len(engine._queue)
                    inbox = len(self._inbox)  # lint: allow(lock-discipline) — GIL-atomic depth hint
                    free = engine.allocator.free_pages
                    radix = getattr(engine, "_radix", None)
                    # XLA compiles this tick triggered (jit-family cache growth,
                    # analysis/audit/fence.py) — steady-state serving should
                    # record 0 here; the event list names the offending family
                    # and abstract signature when it does not
                    compiles_now = paged_compiles()
                    compile_fields: dict = {
                        "xla_compiles": compiles_now - last_compiles,
                    }
                    if compiles_now != last_compiles:
                        # the event ring is process-global and drained
                        # destructively — with several engines alive the
                        # family filter keeps foreign events off this tick,
                        # but a second paged pump may consume events first
                        # (counts above stay exact either way)
                        compile_fields["compile_events"] = [
                            e for e in fence.drain_events()
                            if e["family"].startswith(("paged.", "paged_spec."))
                        ]
                    last_compiles = compiles_now
                    row_steps = self._row_steps()
                    tick_seq = recorder.record_tick(
                        **compile_fields,
                        **row_steps,
                        # what the DEVICE ran, by program, stamped since the
                        # previous record (beside phase_ms, the host's side),
                        # and the stamps that record lost or took out of order
                        **tracing.get_stamper().take_tick_fields(),
                        replica=self.replica_id,
                        # the number this iteration's decode_tick annotation
                        # carries in a profiler window (== tick unless another
                        # pump shares the recorder)
                        step=step_num,
                        dur_ms=round(tick_dur_s * 1e3, 3),
                        active_slots=int(active),
                        queue_depth=queued,
                        inbox_depth=inbox,
                        prefill_tokens=engine.prefill_tokens_total - last_prefill,
                        decode_tokens=engine.decode_tokens_total - last_decode,
                        spec_accepted=engine.spec_emitted_total - last_spec,
                        # prompt tokens this tick served read-only from the radix
                        # prefix cache vs actually forwarded, plus the cache's
                        # page occupancy — the per-tick evidence of prefill
                        # skipped (replaces the old boolean hit/miss counts)
                        prefix_hit_tokens=(
                            engine.prefix_hit_tokens_total - last_hit_toks),
                        prefix_miss_tokens=(
                            engine.prefix_miss_tokens_total - last_miss_toks),
                        prefix_cache_pages=(radix.pages_held if radix else 0),
                        free_pages=free,
                        used_pages=engine.allocator.num_pages - 1 - free,
                        # overload counters (lifetime totals — diffs between
                        # consecutive ticks attribute sheds to a tick window)
                        shed_total=self._shed,  # lint: allow(lock-discipline) — GIL-atomic total
                        expired_total=self._expired,  # lint: allow(lock-discipline) — GIL-atomic total
                        cancelled_total=self._cancelled,  # lint: allow(lock-discipline) — GIL-atomic total
                    )
                    last_prefill = engine.prefill_tokens_total
                    last_decode = engine.decode_tokens_total
                    last_spec = engine.spec_emitted_total
                    last_hit_toks = engine.prefix_hit_tokens_total
                    last_miss_toks = engine.prefix_miss_tokens_total
                    metrics.record_tick(tick_dur_s, int(active), queued + inbox)
                    metrics.record_row_steps(row_steps["row_steps"], row_steps["kv_pages"],
                                             row_steps["moe_pairs"], row_steps["prefill_latent"],
                                             row_steps["prefill_turns"], row_steps["conv_state"],
                                                 row_steps["ssm_state"], row_steps["lane_admissions"])
                except Exception:  # noqa: BLE001
                    logger.debug("tick telemetry failed", exc_info=True)
                t_deliver_start = time.perf_counter()
                now = t_deliver_start
                with tracing.annotation("tick.deliver"), self._mutex:
                    self._heartbeat_ts = now  # tick survived: fresh liveness
                    self._ticks += 1
                    self._active_sum += active
                    self._max_active = max(self._max_active, active)
                    # push newly emitted tokens to streaming tickets still in
                    # flight (the engine's slot.emitted grows by up to
                    # steps_per_tick per tick)
                    for slot in self.engine.slots:
                        if not slot.active:
                            continue
                        ticket = self._tickets.get(slot.request_id)
                        if ticket is None:
                            continue
                        if ticket.tick_admit == 0:
                            ticket.tick_admit = tick_seq or 0
                        # TTFT: first tick where this sequence's sampled tokens
                        # became host-visible (finish-inside-first-tick requests
                        # are stamped at completion below instead)
                        if slot.emitted and ticket.t_first == 0.0:
                            ticket.t_first = now
                            ticket.tokens_first = len(slot.emitted)
                            ticket.tick_first = tick_seq or 0
                            metrics.record_ttft(now - ticket.t_submit,
                                                path=ticket.path)
                            self._note_ttft_locked(now - ticket.t_submit)
                            self._note_first_token(
                                ticket, slot.admit_t, slot.prefill_segments,
                                slot.prompt_tokens, slot.shared_tokens)
                        if ticket.stream_q is None:
                            continue
                        if len(slot.emitted) > ticket.sent_tokens:
                            if ticket.request_id:
                                recorder.note_stream_put(ticket.request_id, now)
                            ticket.stream_q.put(
                                ("toks", list(slot.emitted[ticket.sent_tokens:]))
                            )
                            ticket.sent_tokens = len(slot.emitted)
                    for result in finished:
                        # which replica produced this result, for stats sinks
                        # and tracing spans downstream (PagedResult defaults -1)
                        result.replica_id = self.replica_id
                        ticket = self._tickets.pop(result.request_id, None)
                        if ticket is None:
                            continue
                        if result.finish_reason == "expired":
                            # the ENGINE dropped it (deadline passed while in
                            # its queue) — same typed error as a pump-side drop
                            self._expired += 1
                            metrics.record_shed("expired")
                            self._finish_error_locked(
                                ticket,
                                DeadlineExceededError(
                                    "deadline expired while queued for a slot"),
                                "expired",
                            )
                            continue
                        self._completed += 1
                        if ticket.t_first == 0.0:
                            # finished inside its first tick: _note_finished will
                            # stamp TTFT=now − submit; fold the same sample into
                            # the admission-control EMA here (mutex held)
                            self._note_ttft_locked(now - ticket.t_submit)
                        if ticket.tick_admit == 0:
                            ticket.tick_admit = tick_seq or 0
                        self._note_finished(ticket, result, now, metrics, recorder,
                                            tick_seq or 0)
                        ticket.result = result
                        if ticket.stream_q is not None:
                            if ticket.request_id:
                                recorder.note_stream_put(ticket.request_id, now)
                            ticket.stream_q.put(("done", result))
                        ticket.event.set()
                t_deliver_end = time.perf_counter()
                # tick-phase decomposition (infra/phases.py): the engine's own
                # section timings plus this pump's inbox_drain/deliver spans.
                # Residual (the telemetry block above, mutex waits, call
                # overhead) folds into "other", so sum(phase_ms) == pump_ms
                # holds by CONSTRUCTION — the tier-1 conservation test pins it,
                # and Perfetto slices built from phase_ms nest exactly inside
                # their tick. The dict is AMENDED onto the already-recorded
                # tick event (amend_tick restamps t_s to this span's end, the
                # convention the Chrome exporter subtracts pump_ms from).
                phase_s = dict(self.engine.last_step_phases)
                phase_s["inbox_drain"] = t_drain - t_iter
                phase_s["deliver"] = t_deliver_end - t_deliver_start
                pump_s = t_deliver_end - t_iter
                phase_s["other"] = phase_s.get("other", 0.0) + max(
                    pump_s - sum(phase_s.values()), 0.0
                )
                try:
                    if tick_seq is not None:
                        recorder.amend_tick(
                            tick_seq,
                            pump_ms=round(pump_s * 1e3, 3),
                            phase_ms=phases_to_ms(phase_s),
                        )
                    metrics.record_tick_phases(phase_s)
                except Exception:  # noqa: BLE001
                    logger.debug("phase telemetry failed", exc_info=True)
                # the amend/metrics cost itself rides the duty-cycle totals as
                # "other" (it cannot ride the record it just amended). Totals
                # are pump-thread-owned floats; readers snapshot them
                # GIL-atomically (see duty_cycle()).
                phase_s["other"] += time.perf_counter() - t_deliver_end
                for key, val in phase_s.items():
                    self._phase_totals[key] = self._phase_totals.get(key, 0.0) + val

    def _row_steps(self) -> dict:
        """The tick ring's row-step and K/V-page fields for the tick(s) the
        latest ``engine.step()`` harvested (runtime/paged.py counts them)."""
        return {"sub_steps": self.engine.last_tick_sub_steps,
                "row_steps": dict(self.engine.last_tick_row_steps),
                "kv_pages": dict(self.engine.last_tick_kv_pages),
                # a routed family's expert layers (zeros for any other)
                "moe_pairs": dict(getattr(self.engine, "last_tick_moe", None) or {}),
                # a latent family's prefill tokens, new and expanded (zeros for any other)
                "prefill_latent": dict(getattr(self.engine, "last_tick_prefill_latent", None) or {}),
                # chunked prefill's turns, taken and waited (zeros without PREFILL_CHUNK)
                "prefill_turns": dict(getattr(self.engine, "last_tick_prefill_turns", None) or {}),
                # a family with convolution state: what its prefill rows started
                # from, and the page tails written (zeros for any other)
                "conv_state": dict(getattr(self.engine, "last_tick_conv_state", None) or {}),
                # a family with Mamba layers: what its prefill rows started from,
                # snapshots written and evicted, tokens computed again for want
                # of a snapshot (zeros for any other)
                "ssm_state": dict(getattr(self.engine, "last_tick_ssm_state", None) or {}),
                # the lanes this step's admissions took: free, or spent (handed
                # on while the old row's last tick was in flight)
                "lane_admissions": dict(getattr(self.engine, "last_tick_lane_admissions", None) or {})}

    def _note_ttft_locked(self, ttft_s: float) -> None:  # lock-held: _mutex
        """Fold one observed TTFT into the EMA admission control projects
        queue wait from (alpha 0.2: smooth, still tracks load shifts)."""
        assert_held(self._mutex)
        if self._ttft_ema <= 0.0:
            self._ttft_ema = ttft_s
        else:
            self._ttft_ema = 0.8 * self._ttft_ema + 0.2 * ttft_s

    @staticmethod
    def _note_first_token(ticket: _Ticket, admit_t: float, segments: int,
                          prompt_tokens: int, prefix_hit_tokens: int) -> None:
        """The admission's first token is host-visible (``ticket.t_first``
        is stamped): close its inbox_wait, slot_wait and prefill stages and,
        for the user-facing admission, the request's receipt → first token
        tile (infra/tracing.close_ttft). The engine's stamps come from the
        slot, or from the result of a request that finished inside its
        first tick. Best-effort — never raises."""
        try:
            t_engine = ticket.t_engine or ticket.t_submit
            t_admit = admit_t or t_engine
            tracing.close_ttft(ticket.request_id, ticket.t_first, [
                ("inbox_wait", ticket.t_submit, t_engine, {}),
                ("slot_wait", t_engine, t_admit, {}),
                ("prefill", t_admit, ticket.t_first, {
                    "segments": segments,
                    "ticks": [ticket.tick_admit, ticket.tick_first],
                    "prompt_tokens": prompt_tokens,
                    "prefix_hit_tokens": prefix_hit_tokens,
                }),
            ], parent=ticket.parent)
        except Exception:  # noqa: BLE001
            logger.debug("first-token stage telemetry failed", exc_info=True)

    @staticmethod
    def _note_finished(ticket: _Ticket, result: PagedResult, now: float,
                       metrics, recorder, tick: int = 0) -> None:
        """Per-sequence completion telemetry: TTFT (if the whole generation
        fit inside one tick), TPOT over the post-first-tick tokens, the
        decode stage, and the flight record's engine section. Best-effort —
        never raises."""
        try:
            n = len(result.tokens)
            if ticket.t_first == 0.0:
                # whole generation finished inside its first tick: TTFT is
                # real, but there is no post-first-token interval to divide
                # — recording tpot=0.0 here would drag the histogram's p50
                # toward zero and fake a throughput the engine doesn't have
                ticket.t_first = now
                ticket.tokens_first = n
                ticket.tick_first = tick
                metrics.record_ttft(now - ticket.t_submit, path=ticket.path)
                PagedGenerationService._note_first_token(
                    ticket, result.admit_t, result.prefill_segments,
                    result.prompt_tokens, result.prefix_hit_tokens)
            tracing.stamp("decode", ticket.t_first, now, ticket.request_id,
                          ticket.parent, tokens=n,
                          ticks=[ticket.tick_first, tick])
            tail = n - ticket.tokens_first
            tpot_s = (now - ticket.t_first) / tail if tail > 0 else None
            if tpot_s is not None:
                metrics.record_tpot(tpot_s, path=ticket.path)
            if ticket.request_id:
                recorder.finish_engine(
                    ticket.request_id,
                    ttft_ms=round((ticket.t_first - ticket.t_submit) * 1e3, 2),
                    tpot_ms=(round(tpot_s * 1e3, 3)
                             if tpot_s is not None else None),
                    tokens=n,
                    prompt_tokens=result.prompt_tokens,
                    prefill_tokens=result.prefill_tokens,
                    prefix_hit_tokens=result.prefix_hit_tokens,
                    finish_reason=result.finish_reason,
                )
        except Exception:  # noqa: BLE001
            logger.debug("completion telemetry failed", exc_info=True)

    def _close_cancelled_locked(self, ticket: _Ticket) -> None:  # lock-held: _mutex
        """Account one abandoned (caller-cancelled) ticket and pin the end
        of its flight-record tick window — an open engine section would keep
        absorbing unrelated future ticks into the request's /debug/flight
        view. ONE implementation for the inbox sweep, the admitted sweep,
        and both crash-containment paths."""
        assert_held(self._mutex)
        self._cancelled += 1
        if ticket.request_id:
            get_flight_recorder().finish_engine(
                ticket.request_id, finish_reason="cancelled"
            )

    def _finish_error_locked(
        self, ticket: _Ticket, exc: Exception, finish_reason: str
    ) -> None:  # lock-held: _mutex
        """Terminate a ticket with a TYPED error the caller re-raises
        (deadline expiry, shed-at-requeue) instead of a result."""
        assert_held(self._mutex)
        finish_ticket_error(ticket, exc, finish_reason)

    def _fail_ticket_locked(self, ticket: _Ticket, reason: str) -> None:  # lock-held: _mutex
        """Terminate a ticket with the finish_reason='error' result (the
        legacy decode-failure surface callers already handle)."""
        assert_held(self._mutex)
        if ticket.event.is_set():
            return
        ticket.result = PagedResult(
            request_id=-1, text="", tokens=[],
            prompt_tokens=0, finish_reason="error",
        )
        if ticket.request_id:
            get_flight_recorder().finish_engine(
                ticket.request_id, finish_reason="error", error=reason
            )
        if ticket.stream_q is not None:
            ticket.stream_q.put(("done", ticket.result))
        ticket.event.set()

    def _fail_all_locked(self, reason: str) -> None:  # lock-held: _mutex
        """A dying pump must not leave callers hanging forever."""
        assert_held(self._mutex)
        for ticket in list(self._tickets.values()) + self._inbox:
            self._fail_ticket_locked(ticket, reason)
        self._tickets.clear()
        self._inbox.clear()
