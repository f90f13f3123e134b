"""Multi-replica serving tier: radix-affinity routing + weighted fair queueing.

One ``PagedGenerationService`` is the hard throughput ceiling no matter how
fast a tick is — one pump thread, one engine, one page pool. This module
scales the serving path out data-parallel, following the continuous-batching
replica model of Orca (Yu et al., OSDI '22) and the prefix-affinity
scheduling idea of SGLang's RadixAttention (Zheng et al., 2024):

* a :class:`ReplicaSet` owns N fully independent engine+service replicas
  (private page pool, radix tree, and pump thread each — replicas share
  only the immutable weights and tokenizer). On real hardware each replica
  maps onto a slice of the mesh's ``dp`` axis
  (:func:`sentio_tpu.parallel.mesh.split_mesh_dp`); in-process CPU replicas
  are the N=1-compatible first rung.
* **two-stage routing** — (1) *radix-prefix affinity*: the router tokenizes
  the prompt head and asks every replica's radix cache, via the read-only
  ``peek_prefix`` probe, for its longest cached prefix; the best hit wins
  unless that replica's backlog exceeds a stickiness bound, because a
  session's follow-up landing on the replica that already holds its KV
  turns a cross-replica cache miss into a suffix-only prefill. (2)
  *least-loaded* by projected wait (each replica's TTFT-EMA scaled by its
  backlog — the same estimate admission control uses against deadlines).
* **weighted fair queueing** — in front of the replicas, the single global
  FIFO admission bound generalizes to per-tenant fairness
  (:class:`TenantFairQueue`): requests carry a tenant key (auth principal
  or ``X-Tenant`` header; default one shared tenant), each tenant gets a
  weight-proportional quota of the set's total queue capacity (with a
  reserved headroom so a flooding tenant can never consume the last slots
  a new tenant's first request needs), optional token-weighted deficit
  counters rate-limit contended tenants DRR-style, and a ``batch``
  priority tier sheds earlier than ``interactive`` under load. Overload
  answers stay typed ``ServiceOverloaded`` → 429/503 + Retry-After, now
  per tenant.

The set exposes the same ``generate / generate_stream / check_admission /
warmup / drain / stats / close`` surface as one service, so the serving
container, graph nodes, and eval swap only the constructor. N=1 with the
default single tenant degenerates to (almost) today's behavior — the one
deliberate difference is the WFQ headroom, which sheds a lone flooding
tenant slightly before the absolute queue bound so fairness is available
the instant a second tenant shows up.

**Replica failure domains** — each replica is an independent failure
domain with a supervised health state machine::

    HEALTHY → DEGRADED → QUARANTINED → REBUILDING → HEALTHY

* the router never selects a QUARANTINED/REBUILDING replica, and DEGRADED
  replicas take traffic only when no HEALTHY replica has queue headroom;
* a per-replica breaker trips to QUARANTINED on the service's latched
  ``broken`` flag (failed tick whose ``engine.reset()`` also failed), on a
  burst of tick failures inside a sliding window, or on a caller-observed
  error rate over the same window;
* a supervisor thread rebuilds quarantined replicas **in place**: fresh
  engine + pool + radix + pump from the shared weights
  (``engine.spawn_fresh()``, the same constructor path the serving
  container uses), re-warmed — under an armed compile fence the NEW
  engine's cold compiles are instance-scoped exempt while steady-state
  recompiles elsewhere still trip — and only then swapped back into
  rotation;
* callers **fail over**: a generate (or a stream that has not yet
  delivered tokens) that dies with a replica-infrastructure failure is
  re-admitted (WFQ released, then re-charged — failover never
  double-counts quota) and re-routed to a surviving replica, bounded by a
  per-request failover budget.

**Resumable streams** — a stream that dies WITH delivered tokens cannot
restart (replay would duplicate output), so it is **resumed by
replay-prefill**: the router tracks the exact delivered token ids per
piece (:class:`~sentio_tpu.runtime.service.StreamProgress`) plus the
call-time sampling knobs, and on a mid-stream replica failure re-admits
on a survivor with ``prior_tokens`` = the delivered prefix — the radix
cache turns the replay into a prefix hit when the prompt pages survive
there, and a bounded replay prefill otherwise. Decode continues from the
splice point and the router yields only post-splice text (re-decoded
over the full token sequence, so UTF-8 withholding at the splice cannot
duplicate or drop characters). Greedy resumes are token-exact vs a
no-fault run; sampled resumes carry the seed and knobs so the
continuation is distribution-correct. ``stream_resume_budget``
(default = failover budget; 0 disables) caps attempts per stream;
opted-out or budget-exhausted streams keep the typed mid-stream error.
Each resume emits a ``stream_resumed`` flight event and counts into
``sentio_tpu_stream_resumes_total{outcome}`` and ``stats()``.

**Stall tolerance** — the breaker only sees faults that *raise*; a tick
that hangs inside a wedged device dispatch raises nothing. The supervisor
pass doubles as a **watchdog**: each service stamps a pump heartbeat per
loop iteration, and a heartbeat stale past the service's
``tick_stall_budget_s`` *with pending work* quarantines the replica with
no exception observed. Since a thread blocked in XLA cannot be killed,
recovery **abandons** the wedged engine+service (admitted tickets fail
typed and fail over; the leaked pump is accounted and the count carried
across the incarnation swap) and rebuilds the slot via the normal
``spawn_fresh`` path. At *any* quarantine — stall or breaker — the dead
replica's queued-but-never-dispatched **inbox tickets are handed off**
directly to survivors (WFQ release/re-charge via
:meth:`TenantFairQueue.recharge`); the blocked caller wakes with the
survivor's result without spending failover budget. Rebuilds run on a
bounded **worker pool** so detection cadence never waits behind a long
(or wedged) rebuild.

Health transitions emit flight-recorder events and the
``sentio_tpu_replica_health{replica,state}`` gauge (plus
``sentio_tpu_pump_heartbeat_age_seconds`` per watchdog pass);
``health_summary()`` feeds ``/health`` so an N-replica pod reports
``degraded`` (keep routing) rather than ``unhealthy`` (restart me) while
at least one replica serves.

Threading: routing probes (``peek_prefix``, ``backlog``, ``projected_wait``)
are advisory reads against live replicas; all ReplicaSet/TenantFairQueue
mutable state sits behind one mutex held only for quick bookkeeping — never
across a generate call, a device tick, or a rebuild.

**Process-mode replicas** — everything above is duck-typed against the
service surface, so a :class:`~sentio_tpu.runtime.worker.ProcessReplica`
(one spawned worker process per replica, ``REPLICA_MODE=process``) slots
into the set unchanged: load/liveness probes (``backlog``,
``projected_wait``, ``broken``) read its pushed status frames, the
prefix-affinity probe is a short-timeout RPC that skips wedged workers
(a stale status frame reads as a cold cache), the watchdog reads the
worker's own pump heartbeat, quarantine abandons
via RPC, and the rebuild path respawns the process (``respawn()``)
instead of swapping an in-process service. Under a supervising set the
process replicas arm **router-side ticket shadowing**
(``enable_shadow_handoff``): a dead worker's never-answered tickets are
extracted from the router-side shadow queue and re-admitted on survivors
through the same ``_handoff_inbox`` path as thread mode — handoff parity.
See runtime/worker.py for the remaining deliberate semantic deltas
(mid-decode generates may re-execute on handoff; worker compiles outside
the router's fence).
"""

from __future__ import annotations

import logging
import queue as _queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Iterator, Optional, Sequence

from sentio_tpu.analysis.sanitizer import assert_held, guard_locksets, make_lock
from sentio_tpu.infra import faults
from sentio_tpu.infra.exceptions import (
    ReplicaUnavailable,
    SentioError,
    ServiceOverloaded,
)
from sentio_tpu.infra.metrics import get_metrics
from sentio_tpu.infra.phases import duty_fractions, sum_phase_totals
from sentio_tpu.runtime.service import (
    PagedGenerationService,
    StreamProgress,
    finish_ticket_error,
)

logger = logging.getLogger(__name__)

__all__ = [
    "ReplicaSet",
    "TenantFairQueue",
    "WorkerRegistry",
    "DEFAULT_TENANT",
    "PRIORITY_INTERACTIVE",
    "PRIORITY_BATCH",
    "HEALTH_HEALTHY",
    "HEALTH_DEGRADED",
    "HEALTH_QUARANTINED",
    "HEALTH_REBUILDING",
    "HEALTH_RETIRING",
    "HEALTH_RETIRED",
    "HEALTH_STATES",
]

DEFAULT_TENANT = "shared"
PRIORITY_INTERACTIVE = "interactive"
PRIORITY_BATCH = "batch"

# replica health state machine (see module docstring); values are the
# /metrics label and the flight-recorder event vocabulary
HEALTH_HEALTHY = "HEALTHY"
HEALTH_DEGRADED = "DEGRADED"
HEALTH_QUARANTINED = "QUARANTINED"
HEALTH_REBUILDING = "REBUILDING"
# elastic-fleet states: RETIRING drains a replica that is leaving the set
# voluntarily (scale-in / deregister) — the router never selects it but its
# in-flight work finishes or resumes on survivors; RETIRED is the terminal
# parked state of a slot whose worker is gone (the slot id stays stable so
# gauges, tried-sets, and sanitizer guard names never alias across a reuse)
HEALTH_RETIRING = "RETIRING"
HEALTH_RETIRED = "RETIRED"
HEALTH_STATES = (HEALTH_HEALTHY, HEALTH_DEGRADED, HEALTH_QUARANTINED,
                 HEALTH_REBUILDING, HEALTH_RETIRING, HEALTH_RETIRED)


@dataclass
class _ReplicaHealth:
    """Supervision book-keeping for one replica. All fields guarded by the
    owning ReplicaSet's ``_mutex`` (the dataclass never escapes the lock;
    the supervisor and caller paths both mutate it under that mutex)."""

    state: str = HEALTH_HEALTHY
    since: float = 0.0            # perf_counter of the last transition
    last_reason: str = ""
    # caller-observed outcomes: (perf_counter ts, ok) within the breaker
    # window — replica-infrastructure failures only, never policy sheds
    outcomes: deque = field(default_factory=lambda: deque(maxlen=512))
    # perf_counter stamps of observed tick-failure increments
    tick_fails: deque = field(default_factory=lambda: deque(maxlen=64))
    ticks_seen: int = 0           # service tick_failure counter baseline
    quarantined_at: float = 0.0
    next_rebuild_at: float = 0.0  # earliest perf_counter for a rebuild try
    rebuild_attempts: int = 0     # failed attempts THIS quarantine episode
    rebuilds: int = 0             # lifetime successful in-place rebuilds
    # a rebuild for this replica is queued on (or running on) the worker
    # pool: the next supervisor pass must not enqueue it again
    rebuild_inflight: bool = False


@dataclass
class _TenantState:
    """Book-keeping for one tenant. All fields guarded by the queue's
    mutex (the dataclass itself never escapes the lock)."""

    weight: float = 1.0
    pending: int = 0          # requests admitted and not yet released
    deficit: float = 0.0      # DRR token credit (refill-rate mode only)
    last_refill: float = 0.0  # perf_counter of the last deficit refill
    admitted: int = 0
    shed: int = 0
    tokens: int = 0           # actual tokens consumed (prompt + generated)


@guard_locksets
class TenantFairQueue:
    """Weighted fair admission across tenants over a shared queue capacity.

    Three independent rules, every rejection typed and counted per tenant:

    * **quota** — tenant ``t`` may hold at most
      ``max(min_quota, (capacity - headroom) * w_t / Σ w_active)`` pending
      requests, where the active set is every tenant with pending work plus
      the requester. With one active tenant the quota is the whole capacity
      minus the reserved headroom — the slack that guarantees a second
      tenant's FIRST request always finds room (without it, a flood fills
      every replica inbox and fairness can never begin).
    * **deficit** (off by default, ``refill_tokens_per_s > 0`` arms it) —
      token-weighted deficit-round-robin: each tenant's credit refills at
      ``rate x weight`` tokens/s (capped at ``burst x weight``), admission
      under contention (other tenants have pending work) requires a
      non-negative credit, and each admission debits its token cost
      (corrected to actual consumption at release). A lone tenant is never
      deficit-limited — idle capacity is not rationed.
    * **priority tiers** — ``batch`` requests shed once total pending
      crosses ``batch_shed_fraction x capacity``; ``interactive`` requests
      may use the full capacity. Two tiers, shed-earlier semantics: batch
      traffic yields headroom to interactive traffic under load.
    """

    # label-cardinality bound for /metrics: beyond this many distinct
    # tenant keys, new ones share one overflow bucket (a client minting
    # random tenant headers must not grow the metric space unboundedly)
    MAX_TRACKED = 256
    OVERFLOW_TENANT = "overflow"

    def __init__(
        self,
        capacity: int,
        weights: Optional[dict[str, float]] = None,
        default_weight: float = 1.0,
        refill_tokens_per_s: float = 0.0,
        burst_tokens: int = 8192,
        batch_shed_fraction: float = 0.8,
        headroom: Optional[int] = None,
        min_quota: int = 1,
    ) -> None:
        self.capacity = max(int(capacity), 1)
        self.default_weight = max(float(default_weight), 1e-3)
        self.refill_tokens_per_s = max(float(refill_tokens_per_s), 0.0)
        self.burst_tokens = max(int(burst_tokens), 1)
        self.batch_shed_fraction = min(max(float(batch_shed_fraction), 0.0), 1.0)
        self.min_quota = max(int(min_quota), 1)
        # reserved slack no single tenant's quota may consume: the landing
        # room for a tenant the system has not seen yet. An explicit
        # headroom survives capacity re-derivation (set_capacity); the
        # default formula re-derives with the fleet.
        self._explicit_headroom = headroom is not None
        self.headroom = (
            int(headroom) if headroom is not None
            else max(1, self.capacity // 8)
        )
        self.headroom = min(self.headroom, self.capacity - 1)
        self._weights = dict(weights or {})
        self._mutex = make_lock("TenantFairQueue._mutex")
        self._tenants: dict[str, _TenantState] = {}  # guarded-by: _mutex

    # ------------------------------------------------------------- internal

    def _state_locked(self, tenant: str) -> tuple[str, _TenantState]:  # lock-held: _mutex
        assert_held(self._mutex)
        if tenant not in self._tenants and len(self._tenants) >= self.MAX_TRACKED:
            tenant = self.OVERFLOW_TENANT
        state = self._tenants.get(tenant)
        if state is None:
            state = _TenantState(
                weight=max(self._weights.get(tenant, self.default_weight), 1e-3),
            )
            if self.refill_tokens_per_s > 0:
                state.deficit = self.burst_tokens * state.weight
                state.last_refill = time.perf_counter()
            self._tenants[tenant] = state
        return tenant, state

    def _refill_locked(self, state: _TenantState, now: float) -> None:  # lock-held: _mutex
        assert_held(self._mutex)
        if self.refill_tokens_per_s <= 0:
            return
        dt = max(now - state.last_refill, 0.0)
        state.last_refill = now
        state.deficit = min(
            state.deficit + self.refill_tokens_per_s * state.weight * dt,
            self.burst_tokens * state.weight,
        )

    def _quota_locked(self, tenant: str, state: _TenantState) -> int:  # lock-held: _mutex
        assert_held(self._mutex)
        active_weight = state.weight if state.pending == 0 else 0.0
        for other in self._tenants.values():
            if other.pending > 0:
                active_weight += other.weight
        share = (self.capacity - self.headroom) * state.weight \
            / max(active_weight, state.weight)
        return max(self.min_quota, int(share))

    def _shed_locked(self, tenant: str, state: _TenantState, reason: str,
                     message: str, status: int,
                     retry_after_s: float) -> None:  # lock-held: _mutex
        assert_held(self._mutex)
        state.shed += 1
        metrics = get_metrics()
        metrics.record_shed(reason)
        metrics.record_tenant_shed(tenant, reason)
        raise ServiceOverloaded(
            message, status=status, retry_after_s=retry_after_s,
            details={"tenant": tenant, "shed_reason": reason},
        )

    # --------------------------------------------------------------- public

    def set_capacity(self, capacity: int) -> None:
        """Re-derive the shared queue capacity from live fleet membership
        (elastic join / graceful retire). Quotas are computed per-admit from
        ``capacity``/``headroom``, so held reservations need no migration: a
        shrink only tightens FUTURE admissions, it never revokes a pending
        one. An explicitly configured headroom is kept (re-clamped); the
        default formula re-derives with the new capacity."""
        with self._mutex:
            self.capacity = max(int(capacity), 1)  # guarded-by: _mutex
            if not self._explicit_headroom:
                self.headroom = max(1, self.capacity // 8)  # guarded-by: _mutex
            self.headroom = min(self.headroom, self.capacity - 1)  # guarded-by: _mutex

    def admit(self, tenant: str, cost_tokens: int,
              priority: str = PRIORITY_INTERACTIVE,
              reserve: bool = True) -> str:
        """Admit (or, with ``reserve=False``, merely test) one request for
        ``tenant`` with an estimated token cost. Raises a typed
        :class:`ServiceOverloaded` carrying the tenant and shed reason;
        returns the (possibly overflow-bucketed) tenant key actually
        charged, which MUST be passed back to :meth:`release`."""
        now = time.perf_counter()
        with self._mutex:
            tenant, state = self._state_locked(tenant)
            self._refill_locked(state, now)
            total_pending = sum(s.pending for s in self._tenants.values())
            quota = self._quota_locked(tenant, state)
            if state.pending >= quota:
                self._shed_locked(
                    tenant, state, "tenant_quota",
                    f"tenant {tenant!r} is at its fair-share quota "
                    f"({state.pending}/{quota} of {self.capacity} total)",
                    status=429, retry_after_s=1.0,
                )
            if priority == PRIORITY_BATCH and total_pending + 1 > \
                    self.batch_shed_fraction * self.capacity:
                self._shed_locked(
                    tenant, state, "priority_batch",
                    f"batch-tier request shed at {total_pending}/"
                    f"{self.capacity} pending (batch yields to interactive)",
                    status=503, retry_after_s=2.0,
                )
            contended = total_pending - state.pending > 0
            if self.refill_tokens_per_s > 0 and contended and state.deficit < 0:
                wait = -state.deficit / (
                    self.refill_tokens_per_s * state.weight
                )
                self._shed_locked(
                    tenant, state, "tenant_deficit",
                    f"tenant {tenant!r} exhausted its token deficit "
                    f"({state.deficit:.0f}); refilling at "
                    f"{self.refill_tokens_per_s * state.weight:.0f} tok/s",
                    status=429, retry_after_s=max(wait, 0.5),
                )
            if reserve:
                state.pending += 1
                state.admitted += 1
                if self.refill_tokens_per_s > 0:
                    state.deficit -= max(int(cost_tokens), 0)
                get_metrics().record_tenant_admitted(tenant)
            return tenant

    def recharge(self, tenant: str, cost_tokens: int,
                 priority: str = PRIORITY_INTERACTIVE) -> None:
        """Atomically release + re-admit one HELD reservation — the
        quarantine inbox handoff's WFQ move. The ticket is already pending
        (its caller still blocks on it), so this re-evaluates the quota and
        priority rules as if the reservation were being granted now: on
        success the pending count is unchanged and one admission is
        recorded (a handoff is an attempt, like a failover retry); on shed
        the original reservation is RESTORED before the typed error raises,
        so the caller's eventual ``release`` still balances. The deficit is
        untouched — the tokens were debited at original admission and the
        handoff does not re-spend them."""
        now = time.perf_counter()
        with self._mutex:
            state = self._tenants.get(tenant)
            if state is None or state.pending == 0:
                return  # already released (racing completion): nothing held
            self._refill_locked(state, now)
            state.pending -= 1
            try:
                total_pending = sum(s.pending for s in self._tenants.values())
                quota = self._quota_locked(tenant, state)
                if state.pending >= quota:
                    self._shed_locked(
                        tenant, state, "tenant_quota",
                        f"tenant {tenant!r} is over its fair-share quota at "
                        f"handoff ({state.pending + 1}/{quota} of "
                        f"{self.capacity} total)",
                        status=429, retry_after_s=1.0,
                    )
                if priority == PRIORITY_BATCH and total_pending + 1 > \
                        self.batch_shed_fraction * self.capacity:
                    self._shed_locked(
                        tenant, state, "priority_batch",
                        f"batch-tier handoff shed at {total_pending + 1}/"
                        f"{self.capacity} pending (batch yields to "
                        "interactive)",
                        status=503, retry_after_s=2.0,
                    )
            finally:
                state.pending += 1
            state.admitted += 1
            get_metrics().record_tenant_admitted(tenant)

    def release(self, tenant: str, cost_tokens: int,
                actual_tokens: Optional[int] = None) -> None:
        """Return one admission. ``actual_tokens`` (when known) corrects the
        estimated debit, so deficits track real consumption — a request that
        stopped early gets its unspent credit back."""
        with self._mutex:
            state = self._tenants.get(tenant)
            if state is None:
                return
            state.pending = max(state.pending - 1, 0)
            if actual_tokens is not None:
                state.tokens += int(actual_tokens)
                if self.refill_tokens_per_s > 0:
                    state.deficit += max(int(cost_tokens), 0) - max(
                        int(actual_tokens), 0
                    )

    def stats(self) -> dict:
        with self._mutex:
            return {
                "capacity": self.capacity,
                "headroom": self.headroom,
                "refill_tokens_per_s": self.refill_tokens_per_s,
                "per_tenant": {
                    name: {
                        "weight": state.weight,
                        "pending": state.pending,
                        "admitted": state.admitted,
                        "shed": state.shed,
                        "tokens": state.tokens,
                        **({"deficit": round(state.deficit, 1)}
                           if self.refill_tokens_per_s > 0 else {}),
                    }
                    for name, state in self._tenants.items()
                },
            }


@guard_locksets
class WorkerRegistry:
    """Router-side registry of SOCKET replica workers: who is connected,
    at which **incarnation epoch**, and which frames are too old to trust.

    The multi-host worker tier (``REPLICA_MODE=socket``,
    runtime/worker.py + runtime/transport.py) replaces the spawn pipe's
    built-in identity — one pipe, one process, one lifetime — with TCP
    connections that can outlive, predate, or overlap a worker's useful
    life. The registry restores identity with one monotonic counter per
    replica slot:

    * every (re)registration — a spawned worker's first connect, a
      partitioned worker's reconnect, a router dial to an advertised
      remote worker — bumps the slot's epoch and stamps it into the
      connection's frame headers (``SocketTransport.epoch``);
    * the router-side dispatcher drops any frame whose epoch is older
      than the slot's CURRENT epoch (:meth:`note_stale_frame`): a worker
      that vanished behind a partition and later heals can never
      resurrect dead tickets or double-deliver stream chunks, because its
      pre-partition frames are fenced the instant the new incarnation
      registers;
    * the supervisor's respawn path *awaits re-registration* here
      (:meth:`await_registration`) before deciding between **heal** (a
      live worker reconnected — adopt the new connection, keep the
      process) and **respawn** (no re-registration in time — reap and
      spawn fresh).

    One listener serves every slot; worker hellos are authenticated with
    the shared token (constant-time compare) and version-checked before
    any epoch is granted. Rejections are counted into
    ``sentio_tpu_worker_reconnects_total{outcome=rejected_*}``.

    **Elastic membership** — the startup slot count is a floor, not a
    ceiling. A hello carrying ``slot == -1`` is an ELASTIC JOIN: the
    registry allocates a slot (reusing a released one when available, else
    growing the set), acks the assigned slot back (``hello_ack`` carries
    ``"slot"`` — the worker adopts it for reconnects), and publishes a
    join event (:meth:`drain_joins`) the ReplicaSet's supervisor consumes
    to wire a new :class:`~sentio_tpu.runtime.worker.ProcessReplica` into
    rotation. :meth:`release_slot` returns a slot after graceful retire;
    the slot's epoch entry SURVIVES release, so a reused slot's first
    epoch continues the monotonic fence and pre-retire frames can never
    read as fresh. Explicit out-of-range slots stay rejected — elastic
    join is opt-in via the sentinel, not a blanket trust of any slot id."""

    def __init__(
        self,
        auth_token: str,
        slots: int,
        bind_host: str = "127.0.0.1",
        bind_port: int = 0,
        max_frame_bytes: int = 32 * 1024 * 1024,
        frame_timeout_s: float = 30.0,
        hello_timeout_s: float = 10.0,
    ) -> None:
        import socket as _socket

        if not auth_token:
            raise ValueError("WorkerRegistry needs a non-empty auth token")
        self.auth_token = auth_token
        self.slots = max(int(slots), 1)
        self.max_frame_bytes = int(max_frame_bytes)
        self.frame_timeout_s = float(frame_timeout_s)
        self.hello_timeout_s = float(hello_timeout_s)
        self._mutex = make_lock("WorkerRegistry._mutex")
        self._epochs = [0] * self.slots  # guarded-by: _mutex
        self._stale = [0] * self.slots  # guarded-by: _mutex
        self._registrations = 0  # guarded-by: _mutex
        self._rejections = 0  # guarded-by: _mutex
        # elastic membership book-keeping: released slot ids available for
        # reuse, elastic-join counters, and the join-event queue the
        # ReplicaSet supervisor drains to attach new workers. _pending only
        # GROWS (never shrinks) so lock-free indexed reads stay valid; the
        # per-slot queues are themselves thread-safe.
        self._free: list[int] = []  # guarded-by: _mutex
        self._elastic_joins = 0  # guarded-by: _mutex
        self._released = 0  # guarded-by: _mutex
        self._joins: _queue.Queue = _queue.Queue()
        # deliberately NOT lock-guarded: the list only grows (appends
        # happen under _mutex in _alloc_slot, indices never shift), so a
        # lock-free indexed read always lands on a valid thread-safe Queue
        self._pending: list[_queue.Queue] = [
            _queue.Queue() for _ in range(self.slots)
        ]
        self._stop = threading.Event()
        listener = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
        listener.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
        # bounded accept wait: close() must be able to stop the loop
        listener.settimeout(0.2)
        listener.bind((bind_host, int(bind_port)))
        listener.listen(max(2 * self.slots, 8))
        self._listener = listener
        self._addr = listener.getsockname()
        self._accepter = threading.Thread(
            target=self._accept_loop, name="worker-registry-accept",
            daemon=True,
        )
        self._accepter.start()

    @property
    def address(self) -> tuple:
        """(host, port) workers dial to (self-)register."""
        return self._addr

    # ------------------------------------------------------------ epoch book

    def current_epoch(self, slot: int) -> int:
        with self._mutex:
            return self._epochs[slot]

    def assign_epoch(self, slot: int) -> int:
        """Bump + return the slot's incarnation epoch. The bump is the
        fence: from this instant every frame of the PREVIOUS incarnation
        is stale. Also used directly by the dial-out path
        (``REPLICA_WORKERS``), where the router initiates the connection
        and no listener registration happens."""
        with self._mutex:
            self._epochs[slot] += 1
            epoch = self._epochs[slot]
        try:
            get_metrics().record_worker_incarnation(slot, epoch)
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            pass
        return epoch

    def note_stale_frame(self, slot: int) -> None:
        with self._mutex:
            self._stale[slot] += 1
        try:
            get_metrics().record_stale_frames(slot)
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            pass

    def stale_frames(self, slot: int) -> int:
        with self._mutex:
            return self._stale[slot]

    # ------------------------------------------------------------ elasticity

    def _alloc_slot(self) -> int:
        """Allocate a slot for an elastic join: reuse the lowest released
        slot when one exists (its epoch entry was kept, so the fence
        continues), else grow the slot set by one."""
        with self._mutex:
            if self._free:
                self._free.sort()
                slot = self._free.pop(0)
            else:
                slot = self.slots
                self.slots += 1  # guarded-by: _mutex
                self._epochs.append(0)
                self._stale.append(0)
                self._pending.append(_queue.Queue())
            self._elastic_joins += 1
        return slot

    def release_slot(self, slot: int) -> None:
        """Return a slot after a graceful retire. The epoch entry is KEPT
        (not reset): the next worker on this slot registers at a HIGHER
        epoch than every frame the retired incarnation ever sent, so slot
        reuse can never un-fence stale frames. Double-release is a no-op."""
        with self._mutex:
            if not (0 <= slot < self.slots) or slot in self._free:
                return
            self._free.append(slot)
            self._released += 1
        # drop any registration that raced the release onto the queue: a
        # redial of the retired incarnation must not be adopted later
        q = self._pending[slot]
        while True:
            try:
                transport, _h, _e = q.get_nowait()
            except _queue.Empty:
                break
            transport.close()

    def drain_joins(self) -> list[int]:
        """Slots elastically joined since the last call (non-blocking).
        The ReplicaSet supervisor polls this to wire new workers into
        rotation; each slot appears once per registration event."""
        slots: list[int] = []
        while True:
            try:
                slots.append(self._joins.get_nowait())
            except _queue.Empty:
                break
        return slots

    # ---------------------------------------------------------- registration

    def _accept_loop(self) -> None:
        import socket as _socket

        while not self._stop.is_set():
            try:
                conn, _peer = self._listener.accept()
            except _socket.timeout:
                continue
            except OSError:
                return  # listener closed under us: shutting down
            # handshake on its own short-lived thread: a connector that
            # never sends its hello must not stall the accept loop (the
            # hello read is bounded by hello_timeout_s)
            threading.Thread(
                target=self._handshake, args=(conn,),
                name="worker-registry-handshake", daemon=True,
            ).start()

    # frame-emit: handshake-to-dialer via=socket
    def _handshake(self, conn) -> None:
        from sentio_tpu.runtime.transport import (
            SocketTransport,
            TransportClosed,
            TransportError,
            expect_hello,
        )

        transport = SocketTransport(
            conn, max_frame_bytes=self.max_frame_bytes,
            frame_timeout_s=self.frame_timeout_s,
        )
        try:
            hello = expect_hello(transport, self.auth_token,
                                 timeout_s=self.hello_timeout_s)
        except TransportClosed as exc:
            # a connection that never spoke (port scan, TCP liveness
            # probe, flaky dialer): not a protocol rejection — booking it
            # as rejected_* would pollute the series operators are told
            # should be zero in steady state
            logger.debug("silent connection to the worker registry "
                         "dropped: %s", exc)
            with self._mutex:
                self._rejections += 1
            transport.close()
            return
        except TransportError as exc:
            self._reject(transport, None, str(exc))
            return
        except Exception:  # noqa: BLE001 — a hostile hello must not kill the thread
            logger.exception("worker registration handshake crashed")
            transport.close()
            return
        slot = hello.get("slot", -1)
        elastic = isinstance(slot, int) and slot == -1
        if elastic:
            # elastic join: the worker asks for a slot instead of claiming
            # one — allocate (reuse-or-grow) and tell it the answer in the
            # ack so its reconnect loop redials the SAME identity
            try:
                faults.hit("registry.elastic_join")
            except Exception as exc:  # noqa: BLE001 — chaos: an injected join failure must reject typed, not kill the handshake thread
                self._reject(transport, transport,
                             f"elastic join failed: {exc}")
                return
            slot = self._alloc_slot()
        elif not isinstance(slot, int) or not (0 <= slot < self.slots):
            self._reject(transport, transport, f"unknown slot {slot!r}")
            return
        else:
            with self._mutex:
                retired = slot in self._free
            if retired:
                # a retired incarnation redialing its released slot: a
                # typed rejection stops its reconnect loop — adopting it
                # would resurrect a worker the fleet already drained out
                self._reject(transport, transport,
                             f"slot {slot} was retired")
                return
        epoch = self.assign_epoch(slot)
        transport.fault_scope = f"r{slot}"
        transport.epoch = epoch
        try:
            transport.send((0, "hello_ack", {"epoch": epoch, "slot": slot}))
        except TransportError:
            if elastic:
                self.release_slot(slot)
            transport.close()
            return
        with self._mutex:
            self._registrations += 1
        logger.info("worker registered for slot %d at epoch %d (pid %s%s)",
                    slot, epoch, hello.get("pid"),
                    ", elastic join" if elastic else "")
        q = self._pending[slot]
        # supersede by EPOCH, not by arrival order: two racing
        # registrations for a slot (a partitioned worker's redial vs the
        # supervisor's fresh respawn) may drain each other concurrently,
        # and keeping whichever thread ran last would let the STALE
        # connection bury the live one. Collect everything queued plus
        # this one, keep the highest epoch, close the rest.
        entries = [(transport, hello, epoch)]
        while True:
            try:
                entries.append(q.get_nowait())
            except _queue.Empty:
                break
        entries.sort(key=lambda e: e[2])
        for old_transport, _h, _e in entries[:-1]:
            old_transport.close()
        q.put(entries[-1])
        if elastic:
            # publish the join AFTER the registration is queued: the
            # consumer's await_registration must find the transport
            self._joins.put(slot)

    # frame-emit: handshake-to-dialer via=socket
    def _reject(self, transport, ackable, reason: str) -> None:
        with self._mutex:
            self._rejections += 1
        outcome = ("rejected_auth" if "token" in reason
                   else "rejected_proto")
        logger.warning("worker registration rejected: %s", reason)
        try:
            get_metrics().record_worker_reconnect(outcome)
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            pass
        if ackable is not None:
            from sentio_tpu.runtime.transport import TransportError

            try:
                ackable.send((0, "hello_reject", {"reason": reason}))
            except TransportError:
                pass
        transport.close()

    def await_registration(self, slot: int, timeout_s: float):
        """Block until a worker registers for ``slot`` (or raise a typed
        :class:`ReplicaUnavailable` after ``timeout_s``). Returns
        ``(transport, hello, epoch)`` for the NEWEST registration —
        superseded ones were already fenced and closed."""
        deadline = time.perf_counter() + max(timeout_s, 0.0)
        q = self._pending[slot]
        while True:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                raise ReplicaUnavailable(
                    f"no worker registered for slot {slot} within "
                    f"{timeout_s:.0f}s",
                    retry_after_s=2.0,
                    details={"replica": slot, "reason": "no_registration"},
                )
            try:
                transport, hello, epoch = q.get(timeout=min(remaining, 0.5))
            except _queue.Empty:
                continue
            if epoch < self.current_epoch(slot):
                transport.close()  # superseded while queued
                continue
            return transport, hello, epoch

    # ------------------------------------------------------------- lifecycle

    def stats(self) -> dict:
        with self._mutex:
            return {
                "epochs": list(self._epochs),
                "stale_frames": list(self._stale),
                "registrations": self._registrations,
                "rejections": self._rejections,
                "slots": self.slots,
                "free_slots": sorted(self._free),
                "elastic_joins": self._elastic_joins,
                "released_slots": self._released,
            }

    def close(self) -> None:
        self._stop.set()
        try:
            self._listener.close()
        except OSError:
            pass
        if self._accepter.is_alive():
            self._accepter.join(timeout=5.0)
        for q in self._pending:
            while True:
                try:
                    transport, _h, _e = q.get_nowait()
                except _queue.Empty:
                    break
                transport.close()


@guard_locksets
class ReplicaSet:
    """Front-end over N independent paged-decode replicas: WFQ admission →
    radix-affinity / least-loaded routing → delegate to the chosen
    replica's :class:`PagedGenerationService`. Same call surface as one
    service; N=1 degenerates to a thin pass-through."""

    # duck-typing flag callers use to decide whether tenant/priority kwargs
    # are understood (a bare PagedGenerationService or a test fake is not)
    supports_tenants = True

    def __init__(
        self,
        services: Sequence[PagedGenerationService],
        tenant_weights: Optional[dict[str, float]] = None,
        tenant_default_weight: float = 1.0,
        tenant_refill_tokens_per_s: float = 0.0,
        tenant_burst_tokens: int = 8192,
        tenant_headroom: Optional[int] = None,
        batch_shed_fraction: float = 0.8,
        affinity_stickiness: float = 4.0,
        route_prefix_tokens: int = 512,
        supervise: bool = True,
        probe_interval_s: float = 0.25,
        breaker_window_s: float = 30.0,
        breaker_error_rate: float = 0.5,
        breaker_min_samples: int = 4,
        breaker_tick_failures: int = 3,
        quarantine_backoff_s: float = 0.5,
        rebuild_budget: int = 3,
        rebuild_drain_s: float = 5.0,
        failover_budget: int = 1,
        stream_resume_budget: Optional[int] = None,
        rebuild_workers: int = 1,
    ) -> None:
        services = list(services)
        if not services:
            raise ValueError("ReplicaSet needs at least one replica")
        self._check_isolation(services)
        # element SWAPS (supervised rebuild) happen under _mutex; reads are
        # deliberately lock-free GIL-atomic list indexing — a caller that
        # grabbed the old replica mid-swap gets a typed failure and fails
        # over, which is cheaper than locking every routing probe
        self._services = services
        for i, svc in enumerate(services):
            svc.replica_id = i
            guard = getattr(svc.engine, "_san", None)
            if guard is not None:
                # per-replica pump ownership: sanitizer errors must name
                # WHICH replica's engine a stray thread touched
                guard.name = f"ContinuousBatchingEngine[r{i}]"
        self.tokenizer = services[0].engine.tokenizer
        # route on at most this many prompt-head tokens: prefixes longer
        # than this are indistinguishable to the router but not to the
        # replica's radix cache, which still reuses the full match
        self.route_prefix_tokens = max(int(route_prefix_tokens),
                                       services[0].engine.page_size)
        # a prefix-hit replica keeps the request only while its backlog is
        # within stickiness x its slot count; past that, cache reuse costs
        # more queueing delay than the suffix prefill it saves
        self.affinity_stickiness = max(float(affinity_stickiness), 0.0)
        self.tenants = TenantFairQueue(
            capacity=sum(svc.max_queue for svc in services),
            weights=tenant_weights,
            default_weight=tenant_default_weight,
            refill_tokens_per_s=tenant_refill_tokens_per_s,
            burst_tokens=tenant_burst_tokens,
            batch_shed_fraction=batch_shed_fraction,
            headroom=tenant_headroom,
        )
        self._mutex = make_lock("ReplicaSet._mutex")
        # routing outcome counters (telemetry only)
        self._routed_affinity = 0  # guarded-by: _mutex
        self._routed_load = 0  # guarded-by: _mutex
        self._affinity_overflow = 0  # guarded-by: _mutex
        # ---- replica supervision (failure domains) ----
        self.probe_interval_s = max(float(probe_interval_s), 0.01)
        self.breaker_window_s = max(float(breaker_window_s), 0.1)
        self.breaker_error_rate = min(max(float(breaker_error_rate), 0.0), 1.0)
        self.breaker_min_samples = max(int(breaker_min_samples), 1)
        self.breaker_tick_failures = max(int(breaker_tick_failures), 1)
        self.quarantine_backoff_s = max(float(quarantine_backoff_s), 0.0)
        # failed rebuild attempts beyond this budget fall back to the max
        # backoff (the supervisor never gives up — a replica stuck broken
        # just retries slowly instead of hot-looping expensive rebuilds)
        self.rebuild_budget = max(int(rebuild_budget), 0)
        self.rebuild_drain_s = max(float(rebuild_drain_s), 0.0)
        # ReplicaSet-layer retry budget for failed-over requests (PR 5's
        # per-ticket crash retry budget, lifted across replicas)
        self.failover_budget = max(int(failover_budget), 0)
        # resume-by-replay budget for DELIVERED-token streams (the case
        # plain failover cannot restart without duplicating output): None
        # follows the failover budget; 0 disables resumption and keeps the
        # pre-resume typed mid-stream error (STREAM_RESUME_BUDGET env via
        # serve/dependencies.py)
        self.stream_resume_budget = (
            max(int(stream_resume_budget), 0)
            if stream_resume_budget is not None else self.failover_budget
        )
        self._health = [
            _ReplicaHealth(since=time.perf_counter(),
                           # baseline, not zero: pre-existing tick failures
                           # on a reused engine must not instantly trip the
                           # burst breaker
                           ticks_seen=svc.tick_failure_count)
            for svc in services
        ]  # guarded-by: _mutex
        self._failovers = 0  # guarded-by: _mutex
        self._closed = False  # guarded-by: _mutex
        # elastic-fleet counters: runtime joins, graceful retires, and the
        # ONLY trace a retired replica leaves behind besides its slot id
        self._joined = 0  # guarded-by: _mutex
        self._retired = 0  # guarded-by: _mutex
        self._retire_drain_s: deque = deque(maxlen=256)  # guarded-by: _mutex
        # membership source: a callable returning freshly registered
        # services to wire into rotation (socket mode wires the registry's
        # drain_joins here). Single-writer (set once at startup before the
        # supervisor observes it), read by the supervisor pass.
        self._membership_source = None
        self._release_slot = None
        # stall-tolerance telemetry: inbox tickets moved to survivors at
        # quarantine, stall-triggered quarantines, and pump_leaked counts
        # carried over from service incarnations a rebuild replaced (the
        # per-replica sum only sees CURRENT incarnations — without the
        # carryover an abandoned wedged pump would vanish from stats)
        self._handed_off = 0  # guarded-by: _mutex
        self._stall_quarantines = 0  # guarded-by: _mutex
        self._pump_leaked_carryover = 0  # guarded-by: _mutex
        # resumable-stream telemetry: successful mid-flight splices, the
        # delivered tokens replayed for them, and streams whose resume
        # budget (or opt-out) still surfaced the typed mid-stream error
        self._stream_resumes = 0  # guarded-by: _mutex
        self._resume_replayed_tokens = 0  # guarded-by: _mutex
        self._resume_exhausted = 0  # guarded-by: _mutex
        metrics = get_metrics()
        for i in range(len(services)):
            metrics.record_replica_health(i, HEALTH_HEALTHY)
        self._stop = threading.Event()
        self._supervisor: Optional[threading.Thread] = None
        # rebuild worker pool: rebuilds are seconds-to-minutes of drain +
        # compile — running them on the supervisor thread would delay the
        # NEXT breaker/watchdog pass behind them. With the pool, the
        # supervisor only detects and enqueues; workers rebuild. Without a
        # supervisor (test mode) _supervise_once rebuilds inline so
        # deterministic stepping keeps working.
        self.rebuild_workers = max(int(rebuild_workers), 0)
        self._rebuild_q: Optional[_queue.Queue] = None
        self._rebuild_pool: list[threading.Thread] = []
        if supervise:
            # process-mode replicas (runtime/worker.py) mirror their
            # never-dispatched tickets router-side; with a supervisor
            # running, a dead worker's shadowed tickets are handed off to
            # survivors instead of failing typed — parity with thread
            # mode's quarantine inbox handoff. Without a supervisor nobody
            # would ever extract the shadow queue, so the flag stays off
            # and death keeps its fail-fast typed surface.
            for svc in services:
                enable = getattr(svc, "enable_shadow_handoff", None)
                if enable is not None:
                    enable()
            if self.rebuild_workers > 0:
                self._rebuild_q = _queue.Queue()
                self._rebuild_pool = [
                    threading.Thread(
                        target=self._rebuild_worker,
                        name=f"replica-rebuild-{k}", daemon=True,
                    )
                    for k in range(self.rebuild_workers)
                ]
                for t in self._rebuild_pool:
                    t.start()
            self._supervisor = threading.Thread(
                target=self._supervise_loop, name="replica-supervisor",
                daemon=True,
            )
            self._supervisor.start()

    @staticmethod
    def _check_isolation(services: Sequence[PagedGenerationService]) -> None:
        """Replicas must not share mutable decode state: a shared engine,
        allocator, pool, or radix tree would be mutated by two pump threads
        at once (immutable weights/tokenizer sharing is the point)."""
        seen: dict[int, tuple[int, str]] = {}
        for i, svc in enumerate(services):
            eng = svc.engine
            parts = {
                "service": svc,
                "engine": eng,
                "allocator": getattr(eng, "allocator", None),
                "pool": getattr(eng, "pool", None),
                "radix": getattr(eng, "_radix", None),
            }
            for what, obj in parts.items():
                if obj is None:
                    continue
                prior = seen.get(id(obj))
                if prior is not None:
                    raise ValueError(
                        f"replica {i} shares its {what} with replica "
                        f"{prior[0]}'s {prior[1]} — replicas must own "
                        f"private decode state"
                    )
                seen[id(obj)] = (i, what)

    # -------------------------------------------------------------- routing

    @property
    def replicas(self) -> int:
        return len(self._services)

    def _route_tokens(self, prompt: str) -> list[int]:
        # chars bound the token count for every tokenizer in the tree (byte
        # tokenizer is 1:1; BPE merges only shrink), so slicing chars first
        # keeps the encode cost flat for very long prompts
        head = prompt[: self.route_prefix_tokens * 4]
        try:
            toks = self.tokenizer.encode(head, add_bos=True)
        except Exception:  # noqa: BLE001 — routing must never fail a request
            return []
        return list(toks[: self.route_prefix_tokens])

    def _eligible(self, exclude: frozenset = frozenset()) -> list[int]:
        """Replica indices the router may pick, by health: HEALTHY first;
        DEGRADED replicas join only when every healthy replica's backlog is
        at its admission bound (no headroom) — and carry the set alone when
        no replica is HEALTHY. QUARANTINED/REBUILDING replicas are NEVER
        eligible. Raises a typed :class:`ReplicaUnavailable` (503 +
        Retry-After) when nothing can serve — the supervisor is rebuilding,
        so retrying IS the right caller move."""
        with self._mutex:
            if self._closed:
                # a closed set never heals: retryable=False so callers (and
                # the wire layer) do not wait on a rebuild nobody will run
                raise ReplicaUnavailable(
                    "replica set is closed", retry_after_s=1.0,
                    retryable=False,
                )
            states = [h.state for h in self._health]
            retry_in = self._rebuild_eta_locked()
        healthy = [i for i, s in enumerate(states)
                   if s == HEALTH_HEALTHY and i not in exclude]
        degraded = [i for i, s in enumerate(states)
                    if s == HEALTH_DEGRADED and i not in exclude]
        if healthy:
            if degraded and all(
                self._services[i].backlog() >= self._services[i].max_queue
                for i in healthy
            ):
                return healthy + degraded
            return healthy
        if degraded:
            return degraded
        raise ReplicaUnavailable(
            "no serving replica available (every replica is quarantined, "
            "rebuilding, or already failed this request over)",
            retry_after_s=max(retry_in, 1.0),
            details={"replica_states": states},
        )

    def _least_loaded(self, eligible: Sequence[int]) -> int:
        """The least-loaded replica among ``eligible`` (projected wait,
        then backlog, then index) — the routing stage-2 key, shared with
        the quarantine inbox handoff's survivor choice."""
        def load_key(i: int):
            svc = self._services[i]
            return (svc.projected_wait() or 0.0, svc.backlog(), i)

        return min(eligible, key=load_key)

    def _rebuild_eta_locked(self) -> float:  # lock-held: _mutex
        """Seconds until the next quarantined replica is due a rebuild try
        — the honest Retry-After for an all-replicas-down shed."""
        assert_held(self._mutex)
        now = time.perf_counter()
        etas = [h.next_rebuild_at - now for h in self._health
                if h.state in (HEALTH_QUARANTINED, HEALTH_REBUILDING)]
        return max(min(etas), 0.0) if etas else 1.0

    def _route(self, toks: Sequence[int], count: bool = True,
               exclude: frozenset = frozenset()) -> tuple[int, int]:
        """→ (replica index, predicted prefix-hit tokens). Stage 0: filter
        to health-eligible replicas (minus ``exclude``, the replicas a
        failing-over request already tried). Stage 1: best ``peek_prefix``
        hit, sticky while that replica's backlog stays under ``stickiness x
        max_slots``. Stage 2: least projected wait. ``count=False`` for
        probes (check_admission): the SSE pre-check routes the same request
        a second time and must not double-count the routing-outcome
        telemetry."""
        eligible = self._eligible(exclude)
        best_i, best_hit = -1, 0
        if len(eligible) > 1 and toks:
            for i in eligible:
                hit = self._services[i].engine.peek_prefix(toks)
                if hit > best_hit:
                    best_i, best_hit = i, hit
        if best_hit > 0:
            svc = self._services[best_i]
            bound = self.affinity_stickiness * max(svc.engine.max_slots, 1)
            if svc.backlog() <= bound:
                if count:
                    with self._mutex:
                        self._routed_affinity += 1
                return best_i, best_hit
            if count:
                with self._mutex:
                    self._affinity_overflow += 1

        idx = self._least_loaded(eligible)
        if count:
            with self._mutex:
                self._routed_load += 1
        return idx, 0

    # ------------------------------------------------------------------ api

    @staticmethod
    def _is_replica_failure(exc: BaseException) -> bool:
        """Failures that indict the REPLICA (its engine broke, its service
        closed under it) rather than the request (sheds, deadlines,
        validation) — only these are worth failing over."""
        return isinstance(exc, ReplicaUnavailable)

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
        priority: str = PRIORITY_INTERACTIVE,
    ):
        """Route + delegate, with cross-replica failover: a replica that
        dies under this request (typed ReplicaUnavailable, or the
        finish_reason='error' result a crashed pump hands its waiters) is
        reported to the breaker and — within ``failover_budget`` — the
        request is re-admitted and re-routed to a surviving replica. The
        WFQ reservation is fully released before each retry re-charges, so
        failover can never double-count a tenant's quota."""
        toks = self._route_tokens(prompt)
        cost = len(toks) + max_new_tokens
        tenant_key = tenant or DEFAULT_TENANT
        attempts = 0
        tried: set[int] = set()
        while True:
            charged = self.tenants.admit(tenant_key, cost, priority=priority)
            idx = svc = None
            try:
                idx, _hit = self._route(toks, exclude=frozenset(tried))
                svc = self._services[idx]
                result = svc.generate(
                    prompt, max_new_tokens=max_new_tokens,
                    temperature=temperature, timeout_s=timeout_s,
                    request_id=request_id, deadline_s=deadline_s,
                    deadline_ts=deadline_ts, top_k=top_k,
                    # opaque WFQ metadata riding the ticket: the quarantine
                    # inbox handoff uses it to release/re-charge this
                    # reservation when the ticket moves to a survivor
                    tenant=charged, priority=priority, cost_tokens=cost,
                )
            except BaseException as exc:
                # failed before (shed) or during decode: refund the
                # estimated debit — charging full cost for work that never
                # ran would let replica-level sheds drain an innocent
                # tenant's deficit
                self.tenants.release(charged, cost, actual_tokens=0)
                if idx is not None and self._is_replica_failure(exc):
                    self._note_failure(idx, exc, svc)
                    tried.add(idx)
                    if attempts < self.failover_budget:
                        attempts += 1
                        with self._mutex:
                            self._failovers += 1
                        continue  # re-admits (re-charges) at the loop top
                raise
            if result.finish_reason == "error":
                # the crashed pump's budget-exhausted waiter surface: the
                # request itself never misbehaved, so it is resumable here
                self._note_failure(
                    idx, ReplicaUnavailable("error result from replica"),
                    svc)
                tried.add(idx)
                if attempts < self.failover_budget:
                    self.tenants.release(charged, cost, actual_tokens=0)
                    attempts += 1
                    with self._mutex:
                        self._failovers += 1
                    continue
            else:
                self._note_success(idx, svc)
            self.tenants.release(
                charged, cost,
                actual_tokens=result.prompt_tokens + len(result.tokens),
            )
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
        priority: str = PRIORITY_INTERACTIVE,
        stats_out: Optional[dict] = None,
        seed: Optional[int] = None,
        resumable: bool = True,
    ) -> Iterator[str]:
        """Streaming with MID-FLIGHT failover. A stream that dies before
        delivering anything fails over like a generate (fresh restart on a
        survivor, within ``failover_budget``). A stream that dies WITH
        delivered tokens — today's only non-resumable case before this —
        is RESUMED by replay-prefill: the router re-admits on a survivor
        with the exact delivered token prefix as a prior context suffix
        (``prior_tokens``), decode continues from the splice point, and
        only post-splice text is yielded — the client sees one
        uninterrupted stream with zero duplicated and zero missing tokens.
        Greedy resumes are token-exact vs a no-fault run; sampled resumes
        carry the call-time knobs (temperature/top_k/``seed``) so the
        continuation is distribution-correct. ``resumable=False`` (or
        ``stream_resume_budget=0``) opts out and keeps the typed
        mid-stream error."""
        toks = self._route_tokens(prompt)
        idx, _hit = self._route(toks)
        progress = StreamProgress()
        kwargs = dict(
            max_new_tokens=max_new_tokens, temperature=temperature,
            timeout_s=timeout_s, request_id=request_id,
            deadline_s=deadline_s, deadline_ts=deadline_ts, top_k=top_k,
            # WFQ handoff metadata (see generate): streams charge at first
            # next(), so the ticket is stamped provisionally with the raw
            # key here and RE-STAMPED with the charged (possibly overflow-
            # bucketed) key inside _stream_impl once admit() resolves it —
            # a quarantine-handoff recharge looks the ticket's key up in
            # the fair queue, and the raw key of a bucketed tenant is
            # unknown there (the PR 10 recharge gap)
            tenant=tenant or DEFAULT_TENANT, priority=priority,
            cost_tokens=len(toks) + max_new_tokens,
            stats_out=stats_out,
            # delivered-state tracking: per-piece token ids mirrored by the
            # replica's stream impl — the splice a resume re-admits; the
            # sampling knobs above (temperature/top_k) plus this seed are
            # stamped at CALL time and ride kwargs into every attempt
            seed=seed, progress=progress,
        )
        # the replica's own generate_stream runs its CALL-time validation
        # (top_k vs paged speculation) here, before any SSE 200 commits;
        # its admission — and our tenant reservation — stay deferred to the
        # first next(), the long-standing stream contract
        svc = self._services[idx]
        inner = svc.generate_stream(prompt, **kwargs)
        return self._stream_impl(inner, idx, svc, toks, prompt, kwargs,
                                 tenant or DEFAULT_TENANT,
                                 len(toks) + max_new_tokens, priority,
                                 progress, max_new_tokens, resumable)

    def _stream_impl(self, inner: Iterator[str], idx: int, svc,
                     toks: Sequence[int], prompt: str, kwargs: dict,
                     tenant: str, cost: int, priority: str,
                     progress: StreamProgress, max_new_tokens: int,
                     resumable: bool) -> Iterator[str]:
        attempts = 0   # fresh-restart failovers (nothing delivered yet)
        resumes = 0    # replay-prefill resumes (delivered tokens spliced)
        tried = {idx}
        base: list[int] = []  # token ids delivered by PRIOR attempts
        flushed = ""          # text already yielded to the caller
        # a resume is BOOKED (counters, flight event, metric) only after
        # its attempt clears the loop-top WFQ admission below — booking in
        # the except branch would count a resume the quota then shed
        pending_resume_note: Optional[tuple] = None
        while True:
            try:
                charged = self.tenants.admit(tenant, cost, priority=priority)
            except BaseException:
                if pending_resume_note is not None:
                    self._record_resume_outcome("failed")
                raise
            if pending_resume_note is not None:
                self._note_resume(*pending_resume_note)
                pending_resume_note = None
            if kwargs.get("tenant") != charged:
                # the reservation landed under a DIFFERENT key than the one
                # stamped at call time (overflow bucketing): re-create the
                # not-yet-started inner iterator with the charged key, so a
                # quarantine inbox handoff can recharge the reservation it
                # actually holds instead of silently skipping it. The
                # discarded iterator never ran (generator bodies defer to
                # first next()), so no ticket or admission leaks.
                kwargs["tenant"] = charged
                inner = svc.generate_stream(prompt, **kwargs)
            try:
                if not base:
                    # first attempt (or fresh restart): forward verbatim —
                    # the zero-overhead happy path; the service's own UTF-8
                    # withholding already shaped the pieces
                    for piece in inner:
                        flushed += piece
                        yield piece
                else:
                    # resumed attempt: the inner stream's pieces decode the
                    # CONTINUATION tokens in isolation, which may not
                    # splice cleanly onto text the dead attempt already
                    # flushed (withheld trailing chars, multi-token UTF-8).
                    # Re-decode the FULL delivered sequence at each piece
                    # and yield only what extends the flushed prefix: zero
                    # duplicated, zero missing tokens by construction.
                    for _piece in inner:
                        text = self.tokenizer.decode(
                            base + list(progress.tokens))
                        safe = text[:-1] if text.endswith("�") else text
                        if len(safe) > len(flushed):
                            delta = safe[len(flushed):]
                            flushed = safe
                            yield delta
                    # final flush is unconditional, like the service's own
                    # done-path: a finished answer may end in a replacement
                    # char for real
                    text = self.tokenizer.decode(base + list(progress.tokens))
                    if len(text) > len(flushed):
                        delta = text[len(flushed):]
                        flushed = text
                        yield delta
                stats_out = kwargs.get("stats_out")
                if stats_out is not None and resumes:
                    # the service's done-path stats cover the CONTINUATION
                    # request only; restore the whole-stream token count and
                    # stamp the resume provenance for bench/confidence sinks
                    stats_out["tokens"] = len(base) + len(progress.tokens)
                    stats_out["resumed"] = resumes
                    stats_out["replayed_tokens"] = len(base)
                self.tenants.release(charged, cost)
                self._note_success(idx, svc)
                return
            except BaseException as exc:
                # streams release at close/exhaust/error with the estimate —
                # the exact split is not worth holding the reservation open
                self.tenants.release(charged, cost)
                if not self._is_replica_failure(exc):
                    raise
                self._note_failure(idx, exc, svc)
                delivered = bool(flushed) or bool(base)
                if not delivered and attempts < self.failover_budget:
                    tried.add(idx)
                    attempts += 1
                    with self._mutex:
                        self._failovers += 1
                    progress.reset()
                    # may itself raise typed ReplicaUnavailable when no
                    # survivor exists — still a typed terminal outcome
                    idx, _hit = self._route(toks, exclude=frozenset(tried))
                    svc = self._services[idx]
                    inner = svc.generate_stream(prompt, **kwargs)
                    continue
                if delivered and resumable \
                        and resumes < self.stream_resume_budget:
                    from_idx = idx
                    tried.add(idx)
                    resumes += 1
                    base = base + list(progress.tokens)
                    progress.reset()
                    remaining = max_new_tokens - len(base)
                    if remaining <= 0:
                        # every requested token was already delivered; only
                        # a final flush can be owed — emit it and finish
                        # without re-admitting anything. replica_to=-1:
                        # the death was absorbed with NO survivor
                        # re-admission, so the event must not claim a
                        # splice landed on some replica
                        text = self.tokenizer.decode(base)
                        self._note_resume(from_idx, -1, 0, len(base))
                        stats_out = kwargs.get("stats_out")
                        if stats_out is not None:
                            # the dead attempt never reached its done-path
                            # stats fill; stamp what the router knows so
                            # bench/confidence sinks see a completed,
                            # resumed stream instead of an empty dict
                            stats_out["tokens"] = len(base)
                            stats_out["resumed"] = resumes
                            stats_out["replayed_tokens"] = 0
                        if len(text) > len(flushed):
                            yield text[len(flushed):]
                        return
                    try:
                        # survivor choice favors the deepest cached prefix
                        # of prompt+delivered (peek_prefix walks the full
                        # resume context head): surviving pages turn the
                        # replay into a prefix hit. Valid only while the
                        # routing head covers the WHOLE prompt — toks is
                        # clamped to route_prefix_tokens, and appending
                        # base after a truncated head would probe a token
                        # sequence no radix holds
                        resume_toks = (
                            list(toks) + base
                            if len(toks) < self.route_prefix_tokens
                            else list(toks)
                        )
                        # exclude only the replica that just died — not the
                        # whole `tried` history: a replica a FRESH failover
                        # left behind may have been rebuilt and healthy by
                        # now, and `_route` already skips quarantined/
                        # rebuilding replicas on its own
                        idx, _hit = self._route(
                            resume_toks, exclude=frozenset({from_idx}))
                    except BaseException:
                        self._record_resume_outcome("failed")
                        raise
                    svc = self._services[idx]
                    kwargs["prior_tokens"] = list(base)
                    kwargs["max_new_tokens"] = remaining
                    inner = svc.generate_stream(prompt, **kwargs)
                    # booked at the top of the loop AFTER the WFQ admission
                    # for this attempt clears
                    pending_resume_note = (from_idx, idx, len(base),
                                           len(base))
                    continue
                if delivered:
                    self._record_resume_outcome(
                        "exhausted" if resumable
                        and self.stream_resume_budget > 0 else "opt_out")
                raise

    def _note_resume(self, replica_from: int, replica_to: int,
                     replayed: int, splice_index: int) -> None:
        """Book one successful mid-flight resume: counters, the
        ``stream_resumed`` flight event, and the outcome metric.
        ``replica_to=-1`` marks a death absorbed with NO survivor
        re-admission (every requested token was already delivered)."""
        with self._mutex:
            self._stream_resumes += 1
            self._resume_replayed_tokens += replayed
        self._record_resume_outcome("resumed")
        try:
            from sentio_tpu.infra.flight import get_flight_recorder

            get_flight_recorder().record_tick(
                event="stream_resumed", replica_from=replica_from,
                replica_to=replica_to, replayed_tokens=replayed,
                splice_index=splice_index,
            )
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            logger.debug("stream resume telemetry failed", exc_info=True)

    def _record_resume_outcome(self, outcome: str) -> None:
        if outcome == "exhausted":
            with self._mutex:
                self._resume_exhausted += 1
        try:
            get_metrics().record_stream_resume(outcome)
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            logger.debug("stream resume metric failed", exc_info=True)

    def check_admission(
        self,
        deadline_ts: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: str = PRIORITY_INTERACTIVE,
        prompt: Optional[str] = None,
    ) -> None:
        """Raise what a submit right now would raise, WITHOUT reserving:
        WFQ tenant check first (peek mode), then the target replica's own
        admission check. With a ``prompt`` the probe routes exactly as the
        submit will; without one it checks the least-loaded replica (if
        that one sheds, every routing choice would). With every replica
        quarantined the routing stage itself raises the typed 503."""
        self.tenants.admit(tenant or DEFAULT_TENANT, 0, priority=priority,
                           reserve=False)
        toks = self._route_tokens(prompt) if prompt else []
        idx, _hit = self._route(toks, count=False)
        self._services[idx].check_admission(deadline_ts)

    # ------------------------------------------------------- elastic fleet

    def set_membership_source(self, source, release_slot=None) -> None:
        """Install the callable the supervisor polls each pass for freshly
        joined replicas (socket mode wires a closure that drains the
        WorkerRegistry's join events and builds one ``ProcessReplica`` per
        new slot). The source returns ``[(slot, service), ...]`` —
        ``slot=None`` lets the set pick its own index (thread mode).
        ``release_slot`` (optional) is called with the slot index after a
        graceful retire closes the worker, returning the registry slot to
        the elastic free list. Install at startup, before traffic — both
        attributes are single-writer and read only by supervisor-side
        passes."""
        self._membership_source = source
        self._release_slot = release_slot

    def _rederive_capacity(self) -> None:
        """Re-derive the WFQ summed capacity (and default headroom) from
        live membership after a join or retire. The snapshot is taken under
        ``_mutex``; the fair queue is updated OUTSIDE it so no ReplicaSet →
        TenantFairQueue lock-order edge is ever created."""
        with self._mutex:
            caps = [
                getattr(self._services[i], "max_queue", 0)
                for i, h in enumerate(self._health)
                if h.state != HEALTH_RETIRED
            ]
        self.tenants.set_capacity(sum(caps))
        try:
            live = len(caps)
            get_metrics().record_fleet_size(live)
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            pass

    def fleet_load(self) -> dict:
        """Lightweight saturation sample for the autoscaler: serving
        replica count, mean busy fraction (``1 - idle`` duty), and summed
        backlog as a fraction of summed queue capacity — all from cached
        probes (process/socket replicas answer from their pushed status
        frames, so sampling at supervisor cadence costs zero RPCs)."""
        with self._mutex:
            serving = [
                (i, self._services[i])
                for i, h in enumerate(self._health)
                if h.state in (HEALTH_HEALTHY, HEALTH_DEGRADED)
            ]
        per: list[dict] = []
        backlog_total = 0
        capacity_total = 0
        for i, svc in serving:
            try:
                duty = svc.duty_cycle() or {}
                idle = float(duty.get("idle", 1.0))
                backlog = int(svc.backlog())
            except Exception:  # noqa: BLE001 — replica mid-swap: skip one sample
                continue
            busy = max(0.0, min(1.0, 1.0 - idle))
            backlog_total += backlog
            capacity_total += int(getattr(svc, "max_queue", 0) or 0)
            per.append({"replica": i, "busy": busy, "backlog": backlog})
        busy_mean = (sum(p["busy"] for p in per) / len(per)) if per else 0.0
        return {
            "serving": len(serving),
            "busy": busy_mean,
            "backlog_fraction": (backlog_total / capacity_total
                                 if capacity_total else 0.0),
            "replicas": per,
        }

    def add_replica(self, svc, idx: Optional[int] = None) -> int:
        """Wire a NEW replica into rotation at runtime (elastic join).
        ``idx=None`` reuses the lowest RETIRED slot, else appends; socket
        mode passes the registry slot so router index and wire identity
        stay aligned. The new replica enters HEALTHY, the WFQ capacity and
        headroom re-derive from live membership, and — under a supervising
        set — shadow handoff arms exactly like a startup replica. Returns
        the slot index the replica serves under."""
        faults.hit("replica.join")
        supervised = self._supervisor is not None
        with self._mutex:
            if self._closed:
                raise ReplicaUnavailable(
                    "replica set is closed", retry_after_s=1.0,
                    retryable=False,
                )
            if idx is None:
                idx = next((i for i, h in enumerate(self._health)
                            if h.state == HEALTH_RETIRED), None)
            elif idx < len(self._health) \
                    and self._health[idx].state != HEALTH_RETIRED:
                raise ValueError(
                    f"slot {idx} is occupied by a "
                    f"{self._health[idx].state} replica")
            elif idx > len(self._health):
                raise ValueError(
                    f"slot {idx} would leave a gap (set holds "
                    f"{len(self._health)} slots)")
            elif idx == len(self._health):
                idx = None  # plain append
            live = [self._services[i] for i, h in enumerate(self._health)
                    if h.state != HEALTH_RETIRED]
            self._check_isolation(live + [svc])
            fresh_health = _ReplicaHealth(
                since=time.perf_counter(),
                ticks_seen=getattr(svc, "tick_failure_count", 0) or 0,
            )
            if idx is None:
                idx = len(self._services)
                svc.replica_id = idx
                self._services.append(svc)
                self._health.append(fresh_health)
            else:
                # RETIRED slot reuse: stable index, fresh incarnation — the
                # retired service already folded its leaked pumps into the
                # carryover at retire time
                svc.replica_id = idx
                self._services[idx] = svc
                self._health[idx] = fresh_health
            guard = getattr(getattr(svc, "engine", None), "_san", None)
            if guard is not None:
                guard.name = f"ContinuousBatchingEngine[r{idx}]"
            self._joined += 1
        if supervised:
            enable = getattr(svc, "enable_shadow_handoff", None)
            if enable is not None:
                enable()
        self._rederive_capacity()
        logger.info("replica %d joined the set at runtime", idx)
        try:
            get_metrics().record_replica_health(idx, HEALTH_HEALTHY)
            from sentio_tpu.infra.flight import get_flight_recorder

            get_flight_recorder().record_tick(
                event="replica_joined", replica=idx,
            )
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            logger.debug("replica join telemetry failed", exc_info=True)
        return idx

    def retire(self, idx: int, deadline_s: Optional[float] = None) -> dict:
        """Gracefully remove replica ``idx`` (scale-in / voluntary
        deregister): mark RETIRING (the router never selects it again),
        hand its never-dispatched inbox tickets to survivors through the
        quarantine handoff path (WFQ recharge — callers just wake with a
        survivor's result), drain in-flight work within ``deadline_s``
        (default ``rebuild_drain_s``; a delivered-token stream that the
        deadline cuts off resumes token-exact on a survivor via the normal
        resume path, costing the caller nothing), then close the service,
        park the slot RETIRED, release the registry slot, and re-derive
        WFQ capacity. Refuses to retire the last serving replica. Blocking
        (up to the drain deadline) — callers that must not stall ride the
        rebuild worker pool via the supervisor's deregister path."""
        deadline = (float(deadline_s) if deadline_s is not None
                    else self.rebuild_drain_s)
        with self._mutex:
            if self._closed:
                raise ReplicaUnavailable(
                    "replica set is closed", retry_after_s=1.0,
                    retryable=False,
                )
            if not (0 <= idx < len(self._health)):
                raise ValueError(f"no replica {idx} to retire")
            state = self._health[idx].state
            if state in (HEALTH_RETIRING, HEALTH_RETIRED):
                return {"replica": idx, "state": state, "retired": False}
            serving_others = sum(
                1 for i, h in enumerate(self._health)
                if i != idx and h.state in (HEALTH_HEALTHY, HEALTH_DEGRADED)
            )
            if serving_others == 0:
                raise ReplicaUnavailable(
                    f"cannot retire replica {idx}: no other serving "
                    "replica would remain", retry_after_s=5.0,
                    retryable=False,
                    details={"replica": idx, "reason": "last_serving"},
                )
        faults.hit("replica.retire")
        t0 = time.perf_counter()
        self._transition(idx, HEALTH_RETIRING, "scale-in")
        svc = self._services[idx]
        # queued-never-dispatched tickets move to survivors NOW — waiting
        # out the drain would add the whole deadline to their latency
        inbox: list = []
        try:
            inbox = svc.extract_inbox()
        except Exception:  # noqa: BLE001 — retire must complete regardless
            logger.exception("replica %d retire inbox extraction failed",
                             idx)
        self._handoff_inbox(idx, inbox)
        drained: dict = {}
        try:
            drained = svc.drain(deadline) or {}
        except Exception:  # noqa: BLE001 — drain is best-effort on retire
            logger.warning("replica %d retire drain failed", idx,
                           exc_info=True)
        if not getattr(svc, "closed", False):
            try:
                svc.close()
            except Exception:  # noqa: BLE001 — close every retiree regardless
                logger.warning("replica %d retire close failed", idx,
                               exc_info=True)
        leaked = getattr(svc, "pump_leaked_count", 0) or 0
        drain_s = time.perf_counter() - t0
        with self._mutex:
            self._retired += 1
            self._pump_leaked_carryover += leaked
            self._retire_drain_s.append(drain_s)
        self._transition(idx, HEALTH_RETIRED,
                         f"retired after {drain_s:.2f}s drain")
        release = self._release_slot
        if release is not None:
            try:
                release(idx)
            except Exception:  # noqa: BLE001 — slot release is best-effort
                logger.warning("registry slot %d release failed", idx,
                               exc_info=True)
        self._rederive_capacity()
        return {
            "replica": idx,
            "retired": True,
            "drain_s": round(drain_s, 3),
            "handed_off": len(inbox),
            "drained": drained.get("drained", True),
        }

    def _attach_new_members(self) -> None:
        """One supervisor-cadence poll of the membership source: wire every
        freshly registered worker into rotation. A single bad joiner must
        not block the pass (or its sibling joiners)."""
        source = self._membership_source
        if source is None:
            return
        try:
            fresh = source() or []
        except Exception:  # noqa: BLE001 — the supervisor must survive
            logger.exception("membership source poll failed")
            return
        for slot, svc in fresh:
            try:
                self.add_replica(svc, idx=slot)
            except Exception:  # noqa: BLE001 — one bad joiner, not the pass
                logger.exception("elastic join of slot %s failed", slot)
                try:
                    svc.close()
                except Exception:  # noqa: BLE001 — already on the error path
                    logger.debug("failed joiner cleanup failed",
                                 exc_info=True)

    def _enqueue_retire(self, idx: int) -> bool:
        """Hand one voluntary-deregister retire to the rebuild worker pool
        (False = no pool, caller retires inline). Reuses the rebuild
        in-flight latch so one worker slot is never queued twice."""
        if self._rebuild_q is None:
            return False
        with self._mutex:
            health = self._health[idx]
            if health.rebuild_inflight:
                return True  # already queued or running
            health.rebuild_inflight = True
        self._rebuild_q.put(("retire", idx))
        return True

    # ---------------------------------------------------------- supervision

    def _transition(self, idx: int, state: str, reason: str = "") -> bool:
        """Move replica ``idx`` to ``state`` (no-op if already there),
        emitting the flight-recorder event + health gauge + log line every
        operator surface shares. Returns whether a transition happened."""
        with self._mutex:
            health = self._health[idx]
            prev = health.state
            if prev == state:
                return False
            health.state = state
            health.since = time.perf_counter()
            health.last_reason = reason
        logger.warning("replica %d health %s -> %s (%s)",
                       idx, prev, state, reason or "n/a")
        try:  # telemetry is best-effort; supervision must not die on it
            get_metrics().record_replica_health(idx, state)
            from sentio_tpu.infra.flight import get_flight_recorder

            get_flight_recorder().record_tick(
                event="replica_health", replica=idx,
                state_from=prev, state_to=state, reason=reason[:200],
            )
        except Exception:  # noqa: BLE001
            logger.debug("health transition telemetry failed", exc_info=True)
        return True

    def _note_success(self, idx: int, svc=None) -> None:
        with self._mutex:
            if idx >= len(self._health):
                return
            if svc is not None and self._services[idx] is not svc:
                return  # slot was rebuilt under this request; stale sample
            self._health[idx].outcomes.append((time.perf_counter(), True))

    def _note_failure(self, idx: int, exc: BaseException, svc=None) -> None:
        """Caller-observed replica-infrastructure failure: feed the breaker
        window and, when the service has LATCHED broken (reset failed — it
        can never recover by itself), quarantine immediately instead of
        waiting for the next supervisor pass; by backlog a corpse looks
        least-loaded, so every poll-interval of delay re-routes live
        traffic into it. ``svc`` is the service object the caller actually
        talked to: if the slot has since been rebuilt (swap under _mutex),
        the outcome belongs to the DEAD incarnation and is dropped — a
        straggler's failure must not demote the fresh replica."""
        now = time.perf_counter()
        with self._mutex:
            if self._closed or idx >= len(self._health):
                return  # shutdown churn is not a health signal
            current = self._services[idx]
            if svc is not None and current is not svc:
                return  # failure observed on a replaced incarnation
            health = self._health[idx]
            health.outcomes.append((now, False))
            state = health.state
        if state in (HEALTH_QUARANTINED, HEALTH_REBUILDING,
                     HEALTH_RETIRING, HEALTH_RETIRED):
            return
        if getattr(current, "broken", False) or getattr(current, "closed",
                                                        False):
            self._quarantine(idx, f"replica latched unavailable: {exc}")

    def _quarantine(self, idx: int, reason: str, stalled: bool = False) -> None:
        now = time.perf_counter()
        with self._mutex:
            health = self._health[idx]
            if health.state in (HEALTH_QUARANTINED, HEALTH_REBUILDING,
                                HEALTH_RETIRING, HEALTH_RETIRED):
                # a retiring replica is already leaving gracefully — its
                # drain/close supersedes any quarantine the breaker or a
                # caller might race in
                return
            health.quarantined_at = now
            health.rebuild_attempts = 0
            # first rebuild try is immediate (next supervisor pass); the
            # exponential backoff applies to FAILED rebuild attempts
            health.next_rebuild_at = now
            if stalled:
                self._stall_quarantines += 1
        self._transition(idx, HEALTH_QUARANTINED, reason)
        svc = self._services[idx]
        inbox: list = []
        if stalled:
            # a wedged pump cannot be killed: abandon the engine+service
            # outright — admitted tickets fail typed (their KV dies with
            # the wedged engine; callers fail over), inbox tickets hand off
            try:
                inbox = svc.abandon(reason)
            except Exception:  # noqa: BLE001 — quarantine must complete
                logger.exception("replica %d abandon failed", idx)
        else:
            # breaker quarantine of a WORKING replica: in-flight work gets
            # the rebuild's drain grace, but queued-never-dispatched
            # tickets would otherwise sit out the whole rebuild — move them
            try:
                inbox = svc.extract_inbox()
            except Exception:  # noqa: BLE001
                logger.exception("replica %d inbox extraction failed", idx)
        self._handoff_inbox(idx, inbox)

    def _handoff_inbox(self, idx: int, tickets: list) -> None:
        """Quarantine inbox handoff: re-admit the dead replica's
        never-dispatched tickets directly to surviving replicas instead of
        leaving them to ride each caller's failover loop (which only fires
        after the caller OBSERVES a failure — for a queued ticket that
        means waiting out its full deadline). Each ticket's WFQ reservation
        is released and re-charged (``TenantFairQueue.recharge``); a ticket
        no survivor can take fails with the typed error the caller's
        failover budget is NOT billed for — the ticket object itself moves,
        so the blocked caller just wakes with a result (or a typed
        error)."""
        if not tickets:
            return
        moved = 0
        for ticket in tickets:
            exc: Optional[Exception] = None
            if ticket.tenant is not None:
                try:
                    self.tenants.recharge(
                        ticket.tenant, ticket.cost_tokens,
                        priority=ticket.priority or PRIORITY_INTERACTIVE,
                    )
                except ServiceOverloaded as shed:
                    exc = shed
            if exc is None:
                try:
                    eligible = self._eligible(exclude=frozenset({idx}))
                    target = self._least_loaded(eligible)
                    self._services[target].adopt(ticket)
                    moved += 1
                    continue
                except Exception as adopt_exc:  # noqa: BLE001 — typed below
                    exc = adopt_exc
            if not isinstance(exc, SentioError):
                # the caller blocked on this ticket must never see an
                # untyped infrastructure error
                exc = ReplicaUnavailable(
                    f"inbox handoff failed: {exc}", retry_after_s=2.0,
                    details={"replica": idx},
                )
            self._finish_handoff_ticket(ticket, exc)
        with self._mutex:
            self._handed_off += moved
        logger.warning("replica %d quarantine: %d/%d inbox tickets handed "
                       "off to survivors", idx, moved, len(tickets))
        try:
            from sentio_tpu.infra.flight import get_flight_recorder

            get_flight_recorder().record_tick(
                event="inbox_handoff", replica=idx,
                handed_off=moved, failed=len(tickets) - moved,
            )
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            logger.debug("handoff telemetry failed", exc_info=True)

    @staticmethod
    def _finish_handoff_ticket(ticket, exc: Exception) -> None:
        """Terminal typed outcome for a ticket no survivor could adopt.
        The ticket was extracted from its dead service's inbox, so this
        thread owns it exclusively — no service lock applies; the shared
        sequence in runtime/service.py keeps this path byte-identical to
        the normal in-service error path."""
        finish_ticket_error(ticket, exc, "failed_over")

    def _prune_locked(self, series: deque, now: float) -> None:  # lock-held: _mutex
        assert_held(self._mutex)
        horizon = now - self.breaker_window_s
        while series and series[0][0] < horizon:
            series.popleft()

    def _supervise_loop(self) -> None:
        while not self._stop.wait(self.probe_interval_s):
            try:
                self._supervise_once()
            except Exception:  # noqa: BLE001 — the supervisor must survive
                logger.exception("replica supervision pass failed")

    def _supervise_once(self) -> None:
        """One breaker + rebuild pass over every replica (also directly
        callable by tests for deterministic stepping). Breakers for ALL
        replicas are evaluated BEFORE any rebuild runs: a rebuild is
        seconds-to-minutes of drain + compile, and a sibling replica's trip
        must not wait behind it within the pass (it still waits between
        passes — the supervisor is one thread; see ROADMAP)."""
        now = time.perf_counter()
        # elastic joins first: a freshly registered worker should be in
        # rotation before this pass evaluates breakers (it may be the
        # survivor a handoff needs)
        self._attach_new_members()
        rebuild_ready: list[int] = []
        retire_ready: list[int] = []
        for idx in range(len(self._services)):
            svc = self._services[idx]
            with self._mutex:
                health = self._health[idx]
                state = health.state
                if state in (HEALTH_RETIRING, HEALTH_RETIRED):
                    continue
                if state in (HEALTH_HEALTHY, HEALTH_DEGRADED):
                    # tick-failure burst: fold counter growth into the
                    # window (each increment is one failed decode tick)
                    count = None
                    try:
                        count = svc.tick_failure_count
                    except Exception:  # noqa: BLE001 — service mid-swap
                        pass
                    if count is not None:
                        for _ in range(max(count - health.ticks_seen, 0)):
                            health.tick_fails.append((now, False))
                        health.ticks_seen = max(count, health.ticks_seen)
                    self._prune_locked(health.tick_fails, now)
                    self._prune_locked(health.outcomes, now)
                    burst = len(health.tick_fails)
                    fails = sum(1 for _, ok in health.outcomes if not ok)
                    samples = len(health.outcomes)
                rebuild_due = (state == HEALTH_QUARANTINED
                               and now >= health.next_rebuild_at
                               and not health.rebuild_inflight)
            if state in (HEALTH_HEALTHY, HEALTH_DEGRADED) and \
                    getattr(svc, "deregister_requested", None):
                # voluntary deregister frame observed: queue a graceful
                # retire (pool-side — the drain deadline must never stall
                # this detection pass)
                retire_ready.append(idx)
            if state in (HEALTH_QUARANTINED, HEALTH_REBUILDING):
                # zero the heartbeat gauge for out-of-rotation replicas:
                # left at its last (over-budget) value it would keep the
                # stall alert firing for the whole rebuild, making
                # "watchdog acted" indistinguishable from "watchdog dead"
                try:
                    get_metrics().record_heartbeat_age(idx, 0.0)
                except Exception:  # noqa: BLE001 — telemetry best-effort
                    pass
                if rebuild_due:
                    rebuild_ready.append(idx)
                continue
            # ---- stall watchdog (detection only — recovery is the normal
            # quarantine → abandon → rebuild path). A pump wedged inside a
            # device dispatch raises nothing and latches nothing; the only
            # observable is a stale heartbeat WITH pending work, so this
            # check needs no exception to fire.
            budget = getattr(svc, "tick_stall_budget_s", 0.0) or 0.0
            age = None
            if budget > 0:
                try:
                    age = svc.heartbeat_age()
                except Exception:  # noqa: BLE001 — service mid-swap
                    pass
            try:
                get_metrics().record_heartbeat_age(
                    idx, age if age is not None else 0.0)
                # duty cycle rides the same supervisor cadence, so the
                # host/device/idle gauge stays fresh between scrapes
                get_metrics().record_duty_cycle(idx, svc.duty_cycle())
                # telemetry freshness gauge (process/socket replicas only —
                # duck-typed so thread services stay untouched): seconds
                # since the last ACCEPTED worker telemetry frame. The alert
                # joins this against replica health: stale telemetry on a
                # HEALTHY worker means the observability plane itself broke
                tel_age = getattr(svc, "telemetry_age", None)
                if callable(tel_age):
                    age_t = tel_age()
                    if age_t is not None:
                        get_metrics().record_telemetry_age(idx, age_t)
            except Exception:  # noqa: BLE001 — telemetry best-effort
                pass
            if age is not None and age > budget:
                try:
                    from sentio_tpu.infra.flight import get_flight_recorder

                    get_flight_recorder().record_tick(
                        event="pump_stall", replica=idx,
                        heartbeat_age_s=round(age, 3), budget_s=budget,
                    )
                except Exception:  # noqa: BLE001
                    logger.debug("stall telemetry failed", exc_info=True)
                self._quarantine(
                    idx,
                    f"pump stalled: heartbeat {age:.1f}s old with pending "
                    f"work (budget {budget:.0f}s)",
                    stalled=True,
                )
                continue
            if getattr(svc, "broken", False):
                self._quarantine(idx, "engine latched broken (reset failed)")
            elif burst >= self.breaker_tick_failures:
                self._quarantine(
                    idx, f"{burst} tick failures inside "
                         f"{self.breaker_window_s:.0f}s window")
            elif (samples >= self.breaker_min_samples
                  and fails / samples >= self.breaker_error_rate):
                self._quarantine(
                    idx, f"error rate {fails}/{samples} over "
                         f"{self.breaker_window_s:.0f}s window")
            elif fails > 0 or burst > 0:
                self._transition(
                    idx, HEALTH_DEGRADED,
                    f"{fails} caller failures / {burst} tick failures "
                    "in window")
            elif state == HEALTH_DEGRADED:
                self._transition(idx, HEALTH_HEALTHY, "window clean")
        for idx in rebuild_ready:
            if self._stop.is_set():
                break
            if not self._enqueue_rebuild(idx):
                # no worker pool (supervise=False test mode): rebuild
                # inline so deterministic _supervise_once stepping keeps
                # its synchronous contract
                self._rebuild(idx)
        for idx in retire_ready:
            if self._stop.is_set():
                break
            with self._mutex:
                serving_others = sum(
                    1 for i, h in enumerate(self._health)
                    if i != idx
                    and h.state in (HEALTH_HEALTHY, HEALTH_DEGRADED))
            if serving_others == 0:
                # the last serving replica asked to leave: hold the retire
                # until a sibling joins (debug — this re-evaluates every
                # pass and must not spam operator logs)
                logger.debug("replica %d deregister held: last serving "
                             "replica", idx)
                continue
            if not self._enqueue_retire(idx):
                try:
                    self.retire(idx)
                except Exception:  # noqa: BLE001 — the pass must survive
                    logger.exception("replica %d deregister retire failed",
                                     idx)

    def _enqueue_rebuild(self, idx: int) -> bool:
        """Hand one due rebuild to the worker pool (False = no pool, run
        inline). Marks the replica's rebuild in-flight so the next
        detection pass — which keeps running at the probe cadence while
        workers rebuild — does not enqueue it twice."""
        if self._rebuild_q is None:
            return False
        with self._mutex:
            health = self._health[idx]
            if health.rebuild_inflight:
                return True  # already queued or running
            health.rebuild_inflight = True
        self._rebuild_q.put(idx)
        return True

    def _rebuild_worker(self) -> None:
        """One bounded-pool rebuild worker: detection (supervisor) cadence
        is decoupled from rebuild duration — a minutes-long (or wedged)
        rebuild occupies a worker, not the supervisor's breaker pass."""
        while not self._stop.is_set():
            try:
                item = self._rebuild_q.get(timeout=0.25)
            except _queue.Empty:
                continue
            if item is None:
                return  # shutdown sentinel
            if isinstance(item, tuple) and item[0] == "retire":
                # voluntary-deregister retire rides the same bounded pool:
                # the drain deadline occupies a worker, not the supervisor
                idx = item[1]
                try:
                    self.retire(idx)
                except Exception:  # noqa: BLE001 — the pool must survive
                    logger.exception("replica %d retire crashed on worker",
                                     idx)
                finally:
                    with self._mutex:
                        if idx < len(self._health):
                            self._health[idx].rebuild_inflight = False
                continue
            idx = item
            try:
                self._rebuild(idx)
            except Exception:  # noqa: BLE001 — the pool must survive
                logger.exception("replica %d rebuild crashed on worker", idx)

    def _rebuild(self, idx: int) -> bool:
        """In-place rebuild of a quarantined replica: fresh engine + pool +
        radix + pump from the shared weights, re-warmed, then swapped back
        into rotation. Runs on the supervisor thread (or a test driver) —
        never under ``_mutex``, since it compiles and decodes.

        Process-mode replicas (runtime/worker.py) duck-type the rebuild: a
        replica exposing ``respawn()`` is rebuilt by SPAWNING A FRESH WORKER
        PROCESS from the same spec instead of constructing an in-process
        engine+service — the backoff, warm-before-swap, and health
        bookkeeping are identical either way."""
        with self._mutex:
            attempt = self._health[idx].rebuild_attempts + 1
            self._health[idx].rebuild_inflight = True
        self._transition(idx, HEALTH_REBUILDING, f"rebuild attempt {attempt}")
        fresh = None
        try:
            faults.hit("replica.rebuild")
            old = self._services[idx]
            if not getattr(old, "closed", False):
                try:
                    # error-rate quarantines leave a WORKING service: give
                    # its in-flight callers a bounded window to finish
                    # before the swap orphans them. An ABANDONED (stalled)
                    # service has no pending tickets left, so this returns
                    # immediately and close()'s join — bounded by the drain
                    # deadline's remainder — counts the wedged pump leaked
                    old.drain(self.rebuild_drain_s)
                except Exception:  # noqa: BLE001 — drain is best-effort
                    logger.warning("replica %d pre-rebuild drain failed",
                                   idx, exc_info=True)
            respawn = getattr(old, "respawn", None)
            if respawn is not None:
                # process mode: the dead worker is reaped (drain → close
                # above SIGKILLs stragglers) and a fresh process takes the
                # slot; its cold compiles happen in the WORKER, outside the
                # router's compile fence
                fresh = respawn()
            else:
                engine = old.engine.spawn_fresh()
                guard = getattr(engine, "_san", None)
                if guard is not None:
                    guard.name = f"ContinuousBatchingEngine[r{idx}]"
                fresh = PagedGenerationService(
                    engine,
                    default_timeout_s=old.default_timeout_s,
                    max_queue=old.max_queue,
                    default_deadline_s=old.default_deadline_s,
                    retry_budget=old.retry_budget,
                    replica_id=idx,
                    tick_stall_budget_s=old.tick_stall_budget_s,
                    warmup_budget_s=getattr(old, "warmup_budget_s", 600.0),
                )
            self._warm_rebuilt(fresh)
            if self._stop.is_set():
                # the set is shutting down: never swap a live pump into a
                # closing rotation
                fresh.close()
                return False
            # the old incarnation leaves rotation: carry its leaked-pump
            # count (the wedged pump a stall abandonment left behind) so
            # the set's summed pump_leaked never silently shrinks
            leaked = old.pump_leaked_count
            with self._mutex:
                # baselined cross-thread-race: the ONLY _services mutation,
                # and it holds _mutex; the list is deliberately un-annotated
                # because readers take lock-free GIL-atomic snapshots
                # (router hot path — see the header comment on _route)
                self._services[idx] = fresh
                self._pump_leaked_carryover += leaked
                health = self._health[idx]
                health.outcomes.clear()
                health.tick_fails.clear()
                health.ticks_seen = 0
                health.rebuild_attempts = 0
                health.rebuilds += 1
            self._transition(idx, HEALTH_HEALTHY, "rebuilt in place")
            return True
        except Exception as exc:  # noqa: BLE001 — rebuild retries on backoff
            logger.exception("replica %d rebuild failed", idx)
            if fresh is not None:
                # the half-built service never entered rotation: close it
                # (pump + engine pool), or every failed attempt would stack
                # another live KV pool until the device OOMs
                try:
                    fresh.close()
                except Exception:  # noqa: BLE001 — already on the error path
                    logger.warning("replica %d failed-rebuild cleanup "
                                   "failed", idx, exc_info=True)
            now = time.perf_counter()
            with self._mutex:
                health = self._health[idx]
                health.rebuild_attempts += 1
                # exponential backoff per failed attempt; attempts past the
                # rebuild budget idle at the max backoff (keep trying, slowly)
                if health.rebuild_attempts > self.rebuild_budget:
                    backoff = 60.0
                else:
                    backoff = min(
                        self.quarantine_backoff_s
                        * (2.0 ** (health.rebuild_attempts - 1)),
                        60.0,
                    )
                health.next_rebuild_at = now + backoff
            self._transition(idx, HEALTH_QUARANTINED,
                             f"rebuild failed: {exc}")
            return False
        finally:
            with self._mutex:
                if idx < len(self._health):
                    self._health[idx].rebuild_inflight = False

    def _warm_rebuilt(self, fresh: PagedGenerationService) -> None:
        """Warm a rebuilt replica before it re-enters rotation. Under an
        ARMED compile fence the full warmup sweep runs with the NEW
        engine's FamilyFn instances marked fence-exempt — its cold compiles
        are expected and scoped to this rebuild, while a steady-state
        recompile on any sibling replica still trips the fence throughout.
        Without an armed fence a smoke generation suffices (later compiles
        are legal, just slow)."""
        from sentio_tpu.analysis.audit import fence

        if fence.enabled() and fence.is_armed():
            fresh.engine.set_fence_exempt(True)
            try:
                fresh.warmup()
            finally:
                fresh.engine.set_fence_exempt(False)
        else:
            result = fresh.generate("replica rebuild smoke probe",
                                    max_new_tokens=2, temperature=0.0,
                                    deadline_s=0, timeout_s=120.0)
            if result.finish_reason == "error":
                raise RuntimeError("rebuilt replica failed its smoke probe")

    def health_summary(self) -> dict:
        """Set-level health for ``/health``: ``healthy`` while every replica
        is HEALTHY, ``degraded`` while at least one replica can serve
        (HEALTHY or DEGRADED — k8s must keep routing to a half-alive pod,
        not restart it), ``unhealthy`` only at zero serving replicas."""
        with self._mutex:
            # RETIRED slots left the fleet on purpose: they are invisible
            # here (a retired worker must read as "never existed") except
            # through the retired counter; RETIRING replicas stay visible
            # — they are draining, which an operator should see
            replicas = [
                {
                    "replica": i,
                    "state": h.state,
                    "since_s": round(time.perf_counter() - h.since, 1),
                    "rebuilds": h.rebuilds,
                    **({"reason": h.last_reason} if h.last_reason else {}),
                }
                for i, h in enumerate(self._health)
                if h.state != HEALTH_RETIRED
            ]
            joined, retired = self._joined, self._retired
        serving = sum(1 for r in replicas
                      if r["state"] in (HEALTH_HEALTHY, HEALTH_DEGRADED))
        healthy = sum(1 for r in replicas if r["state"] == HEALTH_HEALTHY)
        if healthy == len(replicas):
            status = "healthy"
        elif serving >= 1:
            status = "degraded"
        else:
            status = "unhealthy"
        return {
            "status": status,
            "healthy_replicas": healthy,
            "serving_replicas": serving,
            "total_replicas": len(replicas),
            "joined_replicas": joined,
            "retired_replicas": retired,
            "replicas": replicas,
        }

    # ------------------------------------------------------------ lifecycle

    def _stop_supervisor(self, timeout_s: float = 10.0) -> None:
        self._stop.set()
        supervisor = self._supervisor
        if supervisor is not None and supervisor.is_alive():
            supervisor.join(timeout=timeout_s)
            if supervisor.is_alive():
                # a rebuild mid-flight can outlive the join window; it
                # checks _stop before swapping and closes its fresh
                # service, so the straggler is bounded — surface it
                logger.warning(
                    "replica supervisor did not exit within %.0fs "
                    "(rebuild in flight?)", timeout_s,
                )
        if self._rebuild_q is not None:
            for _ in self._rebuild_pool:
                self._rebuild_q.put(None)  # wake idle workers immediately
            for t in self._rebuild_pool:
                if t.is_alive():
                    t.join(timeout=timeout_s)
                    if t.is_alive():
                        # a worker wedged inside a stalled rebuild cannot
                        # be killed — it checks _stop before swapping, so
                        # abandoning it is bounded; surface the leak
                        logger.warning(
                            "rebuild worker %s did not exit within %.0fs "
                            "(stalled rebuild?)", t.name, timeout_s,
                        )

    def warmup(self, max_new_tokens: int = 4) -> dict:
        """Warm EVERY replica CONCURRENTLY (each compiles its own jit
        variants over its own pool/mesh slice, so serial warmup would
        multiply startup by N) before the compile fence arms — serve
        startup arms the fence only after this returns, i.e. after all
        replicas report. A failed replica warmup re-raises: arming the
        fence over an unwarmed replica would fail its first real request."""
        results: list = [None] * len(self._services)
        errors: list = []

        def _warm(i: int, svc: PagedGenerationService) -> None:
            try:
                results[i] = svc.warmup(max_new_tokens=max_new_tokens)
            except Exception as exc:  # noqa: BLE001 — re-raised below
                errors.append(exc)

        threads = [
            threading.Thread(target=_warm, args=(i, svc),
                             name=f"replica-warmup-{i}", daemon=True)
            for i, svc in enumerate(self._services)
        ]
        for t in threads:
            t.start()
        for t in threads:
            # each replica warmup bounds its own generations; the join only
            # outwaits that, never blocks startup forever on a wedged pump
            t.join(timeout=max(svc.default_timeout_s
                               for svc in self._services) + 120.0)
        if errors:
            raise errors[0]
        return {
            "prompts": sum(r.get("prompts", 0) for r in results),
            "xla_compiles": sum(r.get("xla_compiles", 0) for r in results),
            "replicas": len(self._services),
        }

    def drain(self, deadline_s: float = 30.0) -> dict:
        """Drain all replicas CONCURRENTLY: each gets the same wall-clock
        window (draining serially would give replica k only the deadline
        minus its predecessors' spend). Aggregates drained/abandoned. The
        supervisor stops FIRST so a mid-drain rebuild cannot swap a fresh
        pump into a rotation that is shutting down."""
        self._stop_supervisor()
        with self._mutex:
            # RETIRED replicas already drained + closed at retire time:
            # draining them again would only log spurious failures
            live = [(i, self._services[i])
                    for i, h in enumerate(self._health)
                    if h.state != HEALTH_RETIRED]
        results: dict[int, Optional[dict]] = {i: None for i, _svc in live}

        def _drain(i: int, svc: PagedGenerationService) -> None:
            try:
                results[i] = svc.drain(deadline_s)
            except Exception:  # noqa: BLE001 — drain is best-effort
                logger.warning("replica %d drain failed", i, exc_info=True)

        threads = [
            threading.Thread(target=_drain, args=(i, svc),
                             name=f"replica-drain-{i}", daemon=True)
            for i, svc in live
        ]
        for t in threads:
            t.start()
        for t in threads:
            # each replica's drain bounds itself by deadline_s; the grace
            # covers close()'s pump join, not extra drain time
            t.join(timeout=deadline_s + 15.0)
        per = []
        for i, svc in live:
            res = results[i]
            if res is None:
                try:
                    backlog = svc.backlog()
                except Exception:  # noqa: BLE001 — replica mid-close
                    backlog = 0
                res = {"drained": False, "abandoned": backlog}
            per.append({"replica": i, **res})
        with self._mutex:
            # every replica's drain ends in close(): the set is done — later
            # submits get the non-retryable closed-set error instead of
            # failover churn against corpses
            self._closed = True
        return {
            "drained": all(r["drained"] for r in per),
            "abandoned": sum(r.get("abandoned", 0) for r in per),
            "replicas": per,
        }

    def close(self) -> None:
        self._stop_supervisor()
        with self._mutex:
            self._closed = True
        for svc in self._services:
            if getattr(svc, "closed", False):
                continue  # retired replicas closed at retire time
            try:
                svc.close()
            except Exception:  # noqa: BLE001 — close every replica regardless
                logger.warning("replica %d close failed", svc.replica_id,
                               exc_info=True)

    # ---------------------------------------------------------------- stats

    _SUM_KEYS = (
        "active_slots", "max_slots", "queued", "free_pages", "total_pages",
        "pool_hbm_bytes", "conv_state_bytes", "ssm_state_bytes", "ssm_snapshot_bytes", "ssm_snapshots",
        "ssm_snapshots_held", "head_skips", "ttft_count", "prefill_tokens",
        "decode_tokens", "lane_admissions_free", "lane_admissions_spent",
        "prefix_hits", "prefix_misses", "prefix_hit_tokens",
        "prefix_miss_tokens", "prefix_cache_pages", "prefix_cache_nodes",
        "queued_inbox", "ticks", "completed", "max_queue", "shed", "expired",
        "cancelled", "requeued", "tick_failures", "pump_leaked",
        "spec_verifies", "spec_emitted", "stale_frames",
        "worker_reconnects",
    )
    _MAX_KEYS = ("max_active_slots", "draining")

    def stats(self) -> dict:
        """Aggregate + per-replica stats. Counters SUM over replicas exactly
        once each (every per-replica total appears in exactly one replica's
        stats, so the sum cannot double-count — the leaked-pump audit relies
        on this); high-water marks take the max; percentile-ish telemetry
        (ttft_p50/p95, avg occupancy) is weighted by each replica's sample
        count and labeled by construction as an approximation."""
        with self._mutex:
            # RETIRED slots are closed (a stats RPC against a reaped worker
            # would fail anyway) and must read as "never existed": only
            # live membership aggregates
            live = [self._services[i] for i, h in enumerate(self._health)
                    if h.state != HEALTH_RETIRED]
        per = []
        agg: dict = {}
        for svc in live:
            try:
                s = svc.stats()
            except Exception:  # noqa: BLE001 — a replica mid-retire/rebuild
                logger.debug("replica %d stats unavailable",
                             getattr(svc, "replica_id", -1), exc_info=True)
                continue
            per.append(s)
            for key in self._SUM_KEYS:
                if key in s:
                    agg[key] = agg.get(key, 0) + s[key]
            for key in self._MAX_KEYS:
                if key in s:
                    agg[key] = max(agg.get(key, 0), s[key])
        if not per:
            per = [{}]
        ticks = agg.get("ticks", 0)
        if ticks:
            agg["avg_active_slots"] = round(
                sum(s.get("avg_active_slots", 0.0) * s.get("ticks", 0)
                    for s in per) / ticks, 3,
            )
        else:
            agg["avg_active_slots"] = 0.0
        hit = agg.get("prefix_hit_tokens", 0)
        miss = agg.get("prefix_miss_tokens", 0)
        if hit + miss:
            agg["prefix_hit_token_ratio"] = round(hit / (hit + miss), 4)
        ttft_n = sum(s.get("ttft_count", 0) for s in per
                     if "ttft_p50_ms" in s)
        if ttft_n:
            for key in ("ttft_p50_ms", "ttft_p95_ms"):
                agg[key] = round(
                    sum(s[key] * s.get("ttft_count", 0) for s in per
                        if key in s) / ttft_n, 2,
                )
        spec_v = agg.get("spec_verifies", 0)
        if spec_v:
            agg["spec_tokens_per_verify"] = round(
                agg.get("spec_emitted", 0) / spec_v, 2)
        # tick-phase attribution (infra/phases.py): phase seconds sum
        # across replicas; the set-level duty cycle is summed busy time
        # over summed wall time — i.e. the per-replica AVERAGE split (the
        # per-replica rows below keep the individual gauges honest)
        phase_totals, duty_elapsed = sum_phase_totals(per)
        if duty_elapsed > 0:
            agg["phase_seconds"] = {k: round(v, 6)
                                    for k, v in phase_totals.items()}
            agg["duty_elapsed_s"] = round(duty_elapsed, 6)
            agg["duty_cycle"] = duty_fractions(phase_totals, duty_elapsed)
        first = per[0]
        agg["page_size"] = first.get("page_size")
        agg["kv_quant"] = first.get("kv_quant")
        agg["kv_bytes_per_token"] = first.get("kv_bytes_per_token")
        agg["paged_attention"] = first.get("paged_attention")
        agg["prefill_attention"] = first.get("prefill_attention")
        agg["page_write"] = first.get("page_write")
        agg["ssm_update"] = first.get("ssm_update")
        agg["expert_tiles"] = first.get("expert_tiles")
        agg["n_replicas"] = len(per)
        agg["replicas"] = per
        with self._mutex:
            agg["routing"] = {
                "affinity": self._routed_affinity,
                "least_loaded": self._routed_load,
                "affinity_overflow": self._affinity_overflow,
            }
            agg["failovers"] = self._failovers
            # stall tolerance: tickets moved at quarantine, stall-triggered
            # quarantines, and leaked pumps whose service incarnation a
            # rebuild already replaced (summed pump_leaked above only sees
            # the CURRENT incarnations)
            agg["handed_off"] = self._handed_off
            agg["stall_quarantines"] = self._stall_quarantines
            agg["pump_leaked"] = (
                agg.get("pump_leaked", 0) + self._pump_leaked_carryover
            )
            # resumable streams: successful mid-flight splices, delivered
            # tokens replayed for them, and resumes that ran out of budget
            agg["stream_resumes"] = self._stream_resumes
            agg["resume_replayed_tokens"] = self._resume_replayed_tokens
            agg["resume_exhausted"] = self._resume_exhausted
            # elastic fleet: runtime joins/retires and the graceful-drain
            # latency distribution scale-in decisions pay
            drains = sorted(self._retire_drain_s)
            agg["fleet"] = {
                "live_replicas": len(live),
                "joined": self._joined,
                "retired": self._retired,
                **({
                    "retire_drain_p95_s": round(
                        drains[min(int(len(drains) * 0.95),
                                   len(drains) - 1)], 3),
                } if drains else {}),
            }
        agg["tenants"] = self.tenants.stats()
        agg["health"] = self.health_summary()
        return agg
