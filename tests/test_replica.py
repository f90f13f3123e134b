"""Multi-replica serving tier (runtime/replica.py) — tier 1.

The contract under test, end to end on tiny engines (conftest arms
SENTIO_SANITIZE=1 for this module, so every tick self-checks):

* **radix-prefix affinity** — a session's follow-up routes to the replica
  whose radix cache holds its prefix, and that request's
  ``prefix_hit_tokens`` proves the KV was actually reused (not just that
  routing picked a replica); stickiness yields to least-loaded when the
  hit replica is backlogged;
* **weighted fair queueing** — a flooding tenant is capped at its
  fair-share quota below total capacity, so a second tenant's FIRST
  request is admitted (the acceptance criterion, asserted both on the
  queue in isolation and through real engines under load);
* **N=1 equivalence** — a single-replica set is a pass-through: same
  greedy tokens, same stats keys the serving gauges read;
* **chaos** — a faulted tick on one replica is contained by that replica's
  crash-containment (PR 5 fault points); every caller terminates and the
  set keeps serving;
* **fan-out lifecycle** — warmup warms every replica before returning,
  drain drains concurrently, leaked pumps sum without double-count.
"""

import threading
import time

import pytest

from sentio_tpu.infra import faults
from sentio_tpu.infra.exceptions import ReplicaUnavailable, ServiceOverloaded
from sentio_tpu.runtime.paged import ContinuousBatchingEngine, PagedResult
from sentio_tpu.runtime.replica import (
    DEFAULT_TENANT,
    HEALTH_DEGRADED,
    HEALTH_HEALTHY,
    HEALTH_QUARANTINED,
    HEALTH_REBUILDING,
    PRIORITY_BATCH,
    ReplicaSet,
    TenantFairQueue,
)
from sentio_tpu.runtime.service import PagedGenerationService


def _engine(base=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages_per_seq", 4)
    kw.setdefault("steps_per_tick", 2)
    if base is not None:
        kw.setdefault("params", base.params)
        kw.setdefault("tokenizer", base.tokenizer)
    return ContinuousBatchingEngine(**kw)


@pytest.fixture(scope="module")
def replica_set():
    """One 2-replica set for the module: each new engine recompiles its jit
    variants, so tests share the set (the chaos drill resets, not poisons)."""
    e0 = _engine()
    e1 = _engine(base=e0)
    rs = ReplicaSet(
        [PagedGenerationService(e0, max_queue=8),
         PagedGenerationService(e1, max_queue=8)],
        # no supervisor thread: routing/health tests flip states by hand
        # and must not race an async rebuild (the supervised path is
        # drilled end to end in test_chaos + TestSupervisor below)
        supervise=False,
    )
    yield rs
    rs.close()


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.reset()


def _assert_pages_conserved(rs):
    for s in rs.stats()["replicas"]:
        assert s["free_pages"] + s.get("prefix_cache_pages", 0) \
            == s["total_pages"] - 1, s


class TestTenantFairQueue:
    def test_flood_capped_and_second_tenant_admitted(self):
        """THE fairness criterion: a saturating single-tenant flood is
        quota-capped below capacity, and a second tenant's first request is
        admitted within its quota."""
        q = TenantFairQueue(capacity=16)
        shed = None
        for _ in range(20):
            try:
                q.admit("hot", 10)
            except ServiceOverloaded as exc:
                shed = exc
                break
        assert shed is not None and shed.status == 429
        assert shed.details["shed_reason"] == "tenant_quota"
        hot = q.stats()["per_tenant"]["hot"]
        assert hot["pending"] < q.capacity, "flood consumed the whole capacity"
        # the idle tenant's FIRST request lands inside the reserved headroom
        assert q.admit("idle", 10) == "idle"
        assert q.stats()["per_tenant"]["idle"]["admitted"] == 1
        # the hot tenant stays capped (its quota HALVED once idle is active)
        with pytest.raises(ServiceOverloaded):
            q.admit("hot", 10)
        # releases restore admission
        for _ in range(hot["pending"]):
            q.release("hot", 10)
        assert q.admit("hot", 10) == "hot"

    def test_weights_scale_quotas(self):
        q = TenantFairQueue(capacity=30, weights={"big": 2.0, "small": 1.0},
                            headroom=0)
        # both active: big's quota should be ~2x small's
        q.admit("big", 1)
        q.admit("small", 1)
        big_quota = small_quota = 0
        with q._mutex:
            big_quota = q._quota_locked("big", q._tenants["big"])
            small_quota = q._quota_locked("small", q._tenants["small"])
        assert big_quota == 2 * small_quota

    def test_batch_tier_sheds_before_interactive(self):
        q = TenantFairQueue(capacity=10, batch_shed_fraction=0.5, headroom=1)
        for _ in range(5):
            q.admit("a", 1)
        with pytest.raises(ServiceOverloaded) as exc_info:
            q.admit("b", 1, priority=PRIORITY_BATCH)
        assert exc_info.value.status == 503
        assert exc_info.value.details["shed_reason"] == "priority_batch"
        q.admit("b", 1)  # interactive still admits at the same load

    def test_deficit_rate_limits_contended_tenant_only(self):
        q = TenantFairQueue(capacity=100, refill_tokens_per_s=1.0,
                            burst_tokens=10)
        # burn the burst while ALONE: never deficit-shed (idle capacity is
        # not rationed), even far past the credit
        for _ in range(30):
            q.admit("solo", 5)
        with q._mutex:
            assert q._tenants["solo"].deficit < 0
        # a second tenant appears → solo is now contended and broke
        q.admit("other", 1)
        with pytest.raises(ServiceOverloaded) as exc_info:
            q.admit("solo", 5)
        assert exc_info.value.details["shed_reason"] == "tenant_deficit"
        assert exc_info.value.details["retry_after_s"] >= 0.5
        # the fresh tenant has full burst credit
        q.admit("other", 5)

    def test_release_corrects_estimate_to_actual(self):
        q = TenantFairQueue(capacity=10, refill_tokens_per_s=1.0,
                            burst_tokens=100)
        q.admit("t", 60)
        with q._mutex:
            assert q._tenants["t"].deficit == pytest.approx(40, abs=1)
        q.release("t", 60, actual_tokens=10)  # stopped early: credit back
        with q._mutex:
            assert q._tenants["t"].deficit == pytest.approx(90, abs=1)
        assert q.stats()["per_tenant"]["t"]["tokens"] == 10

    def test_tenant_cardinality_bounded(self):
        q = TenantFairQueue(capacity=10_000)
        # 20 over the cap: few enough that the shared overflow bucket stays
        # inside its own fair-share quota (overflow tenants still queue)
        for i in range(TenantFairQueue.MAX_TRACKED + 20):
            charged = q.admit(f"t{i}", 1)
        assert charged == TenantFairQueue.OVERFLOW_TENANT
        assert len(q.stats()["per_tenant"]) <= TenantFairQueue.MAX_TRACKED + 1

    def test_tenant_metrics_recorded(self):
        from sentio_tpu.infra.metrics import MetricsCollector, set_metrics

        collector = MetricsCollector()
        set_metrics(collector)
        try:
            q = TenantFairQueue(capacity=4, headroom=1)
            for _ in range(4):
                try:
                    q.admit("m", 1)
                except ServiceOverloaded:
                    pass
            counters = collector.memory.snapshot()["counters"]
            assert counters.get("tenant_admitted('m',)", 0) >= 1
            assert counters.get("tenant_shed('m', 'tenant_quota')", 0) >= 1
        finally:
            set_metrics(None)


class TestIsolation:
    def test_shared_engine_rejected(self, replica_set):
        svc = replica_set._services[0]
        with pytest.raises(ValueError, match="share"):
            ReplicaSet([svc, PagedGenerationService(svc.engine)])

    def test_sanitizer_guard_named_per_replica(self, replica_set):
        guard = replica_set._services[1].engine._san
        assert guard is not None and "[r1]" in guard.name


class TestRouting:
    SESSION = ("session head for affinity routing spanning several pages "
               "of cached prefix easily")

    def test_two_turn_session_lands_on_prefix_holder(self, replica_set):
        rs = replica_set
        first = rs.generate(self.SESSION + " turn one", max_new_tokens=3,
                            temperature=0.0, timeout_s=120)
        assert first.finish_reason in ("stop", "length")
        toks = rs._route_tokens(self.SESSION + " turn two")
        peeks = [svc.engine.peek_prefix(toks) for svc in rs._services]
        holder = max(range(len(peeks)), key=lambda i: peeks[i])
        assert peeks[holder] > 0, "first turn left no cached prefix"
        routed, hit = rs._route(toks)
        assert routed == holder and hit == peeks[holder]
        # end to end: the second turn's result PROVES the KV reuse
        hits_before = rs.stats()["replicas"][holder]["prefix_hit_tokens"]
        second = rs.generate(self.SESSION + " turn two", max_new_tokens=3,
                             temperature=0.0, timeout_s=120)
        assert second.prefix_hit_tokens > 0
        hits_after = rs.stats()["replicas"][holder]["prefix_hit_tokens"]
        assert hits_after - hits_before >= second.prefix_hit_tokens

    def test_stickiness_yields_under_backlog(self, replica_set, monkeypatch):
        rs = replica_set
        toks = rs._route_tokens(self.SESSION + " turn three")
        holder, hit = rs._route(toks)
        assert hit > 0
        # the prefix holder reports a backlog past the stickiness bound:
        # routing must fall through to least-loaded (the OTHER replica)
        monkeypatch.setattr(rs._services[holder], "backlog", lambda: 10_000)
        monkeypatch.setattr(rs._services[holder], "projected_wait",
                            lambda: 100.0)
        routed, hit2 = rs._route(toks)
        assert routed != holder and hit2 == 0
        stats = rs.stats()["routing"]
        assert stats["affinity_overflow"] >= 1

    def test_cold_prompt_routes_least_loaded(self, replica_set, monkeypatch):
        rs = replica_set
        toks = rs._route_tokens("entirely novel prompt with no cached head")
        assert all(svc.engine.peek_prefix(toks) == 0 for svc in rs._services)
        monkeypatch.setattr(rs._services[0], "projected_wait", lambda: 9.0)
        monkeypatch.setattr(rs._services[1], "projected_wait", lambda: 0.1)
        assert rs._route(toks)[0] == 1

    def test_peek_prefix_takes_no_refcounts_and_no_lru_touch(self):
        from sentio_tpu.runtime.radix import RadixPrefixCache

        class _Alloc:
            def free(self, ids):
                pass

        cache = RadixPrefixCache(page_size=4, allocator=_Alloc())
        toks = list(range(8))
        node, _donated = cache.insert(toks, 0, [1, 2])
        before = (node.refcount, node.last_used)
        assert cache.peek_prefix(toks + [99]) == 8
        assert cache.peek_prefix(toks[:5]) == 4  # page-aligned partial
        assert cache.peek_prefix([7, 7, 7, 7]) == 0
        assert (node.refcount, node.last_used) == before, (
            "peek_prefix must not pin or LRU-touch nodes"
        )
        # match() by contrast DOES touch LRU — the probe is the exception
        cache.match(toks)
        assert node.last_used != before[1]


class TestEquivalence:
    def test_n1_set_is_a_pass_through(self):
        engine = _engine()
        svc = PagedGenerationService(engine)
        rs = ReplicaSet([svc])
        try:
            prompt = "single replica equivalence check prompt"
            direct = svc.generate(prompt, max_new_tokens=6, temperature=0.0,
                                  timeout_s=120)
            routed = rs.generate(prompt, max_new_tokens=6, temperature=0.0,
                                 timeout_s=120)
            assert routed.tokens == direct.tokens
            stats = rs.stats()
            # every key the serving gauges read must survive aggregation
            for key in ("active_slots", "queued", "queued_inbox",
                        "free_pages", "total_pages", "completed", "ticks",
                        "max_queue", "shed", "expired", "pump_leaked",
                        "avg_active_slots", "max_active_slots",
                        "pool_hbm_bytes", "draining"):
                assert key in stats, key
            assert stats["n_replicas"] == 1
            assert stats["completed"] == svc.stats()["completed"]
        finally:
            rs.close()


class TestWfqThroughEngines:
    def test_flooding_tenant_cannot_starve_second_tenant(self):
        """End to end through real engines: tenant A floods past its quota
        (typed 429s observed, reason ``tenant_quota``), and tenant B's
        request — arriving mid-flood — is admitted and completes. A
        dedicated set with a large headroom pins A's quota at 4 of the 16
        queue slots, so the quota layer (not the per-replica queue bound)
        is provably what capped the flood."""
        e0 = _engine()
        e1 = _engine(base=e0)
        rs = ReplicaSet(
            [PagedGenerationService(e0, max_queue=8),
             PagedGenerationService(e1, max_queue=8)],
            tenant_headroom=12,  # capacity 16 → lone-tenant quota 4
        )
        outcomes: list = []

        def flood(i):
            try:
                outcomes.append(rs.generate(
                    f"tenant a flood request number {i}", max_new_tokens=12,
                    temperature=0.0, timeout_s=120, tenant="team-a",
                ))
            except ServiceOverloaded as exc:
                outcomes.append(exc)

        try:
            threads = [threading.Thread(target=flood, args=(i,))
                       for i in range(10)]
            for t in threads:
                t.start()
            # the first admissions pay the fresh engines' compile (seconds),
            # so the flood saturates its 4-slot quota long before anything
            # completes; wait until that is observable
            deadline = time.monotonic() + 60
            saturated = False
            while time.monotonic() < deadline and not saturated:
                a = rs.tenants.stats()["per_tenant"].get("team-a")
                saturated = bool(a and a["shed"] >= 1)
                time.sleep(0.002)
            assert saturated, "flood never hit tenant A's quota"
            # mid-flood, tenant B's FIRST request is admitted within its
            # quota and completes — A cannot starve it
            result_b = rs.generate("tenant b first request", max_new_tokens=3,
                                   temperature=0.0, timeout_s=120,
                                   tenant="team-b")
            assert result_b.finish_reason in ("stop", "length")
            for t in threads:
                t.join(timeout=180)
            sheds = [o for o in outcomes if isinstance(o, ServiceOverloaded)]
            dones = [o for o in outcomes if isinstance(o, PagedResult)]
            assert sheds, "the flood was never shed"
            assert all(e.details.get("shed_reason") == "tenant_quota"
                       and e.details.get("tenant") == "team-a"
                       for e in sheds), sheds
            assert dones, "the flood tenant must still be served within quota"
            tenants = rs.tenants.stats()["per_tenant"]
            assert tenants["team-a"]["shed"] >= 1
            assert tenants["team-b"]["shed"] == 0
            assert tenants["team-b"]["admitted"] == 1
            _assert_pages_conserved(rs)
        finally:
            rs.close()


class TestChaos:
    def test_one_replica_faults_others_keep_serving(self, replica_set):
        """PR 5 fault points through the set: a one-shot tick fault hits
        whichever replica ticks next; its crash containment requeues, the
        other replica never notices, every caller terminates."""
        rs = replica_set
        outcomes: dict = {}

        def call(i):
            try:
                outcomes[i] = rs.generate(
                    f"chaos replica load {i}", max_new_tokens=4,
                    temperature=0.0, timeout_s=120,
                )
            except Exception as exc:  # noqa: BLE001 — typed errors terminal
                outcomes[i] = exc

        with faults.inject("paged.step", error=RuntimeError("replica chaos"),
                           times=2) as rule:
            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert not any(t.is_alive() for t in threads)
        assert rule.fired >= 1
        assert len(outcomes) == 6
        # the set survived: a post-chaos request works end to end
        ok = rs.generate("post replica chaos sanity", max_new_tokens=3,
                         timeout_s=120)
        assert ok.finish_reason in ("stop", "length")
        agg = rs.stats()
        assert agg["tick_failures"] >= 1
        _assert_pages_conserved(rs)


class TestHealthRouting:
    """Acceptance: the router NEVER selects a QUARANTINED/REBUILDING
    replica; DEGRADED replicas take traffic only when no healthy replica
    has queue headroom; zero serving replicas is a typed 503."""

    @pytest.fixture(autouse=True)
    def _restore_states(self, replica_set):
        yield
        with replica_set._mutex:
            for h in replica_set._health:
                h.state = HEALTH_HEALTHY

    def _set_state(self, rs, idx, state):
        with rs._mutex:
            rs._health[idx].state = state

    def test_router_never_selects_quarantined_or_rebuilding(self, replica_set):
        rs = replica_set
        toks = rs._route_tokens("health exclusion probe prompt")
        for state in (HEALTH_QUARANTINED, HEALTH_REBUILDING):
            self._set_state(rs, 0, state)
            for _ in range(8):
                assert rs._route(toks, count=False)[0] == 1, state
            self._set_state(rs, 0, HEALTH_HEALTHY)
            self._set_state(rs, 1, state)
            for _ in range(8):
                assert rs._route(toks, count=False)[0] == 0, state
            self._set_state(rs, 1, HEALTH_HEALTHY)

    def test_affinity_never_overrides_quarantine(self, replica_set):
        """Even the replica holding a session's cached prefix is skipped
        once quarantined — cache reuse never beats exclusion."""
        rs = replica_set
        toks = rs._route_tokens(TestRouting.SESSION + " turn four")
        holder, hit = rs._route(toks, count=False)
        if hit == 0:  # session prefix evicted: seed it again
            rs.generate(TestRouting.SESSION + " turn four",
                        max_new_tokens=2, temperature=0.0, timeout_s=120)
            holder, hit = rs._route(toks, count=False)
        assert hit > 0
        self._set_state(rs, holder, HEALTH_QUARANTINED)
        routed, _routed_hit = rs._route(toks, count=False)
        assert routed != holder

    def test_degraded_taken_only_without_healthy_headroom(self, replica_set,
                                                          monkeypatch):
        rs = replica_set
        toks = rs._route_tokens("entirely cold degraded routing probe")
        self._set_state(rs, 0, HEALTH_DEGRADED)
        # healthy replica 1 has headroom: degraded 0 is not even eligible
        assert rs._route(toks, count=False)[0] == 1
        # healthy replica saturated at its admission bound: degraded joins
        monkeypatch.setattr(rs._services[1], "backlog",
                            lambda: rs._services[1].max_queue)
        monkeypatch.setattr(rs._services[1], "projected_wait", lambda: 99.0)
        assert rs._route(toks, count=False)[0] == 0

    def test_all_down_is_typed_503_with_retry_hint(self, replica_set):
        rs = replica_set
        self._set_state(rs, 0, HEALTH_QUARANTINED)
        self._set_state(rs, 1, HEALTH_REBUILDING)
        with pytest.raises(ReplicaUnavailable) as exc_info:
            rs.generate("nowhere to go", max_new_tokens=2)
        assert exc_info.value.status == 503
        assert exc_info.value.details["retry_after_s"] >= 1.0
        # the SSE pre-check sheds the same way, BEFORE a 200 commits
        with pytest.raises(ReplicaUnavailable):
            rs.check_admission(prompt="nowhere to go")

    def test_health_summary_degraded_vs_unhealthy(self, replica_set):
        rs = replica_set
        assert rs.health_summary()["status"] == "healthy"
        self._set_state(rs, 0, HEALTH_QUARANTINED)
        summary = rs.health_summary()
        assert summary["status"] == "degraded"
        assert summary["healthy_replicas"] == 1
        assert summary["serving_replicas"] == 1
        # DEGRADED still serves: not unhealthy
        self._set_state(rs, 1, HEALTH_DEGRADED)
        assert rs.health_summary()["status"] == "degraded"
        self._set_state(rs, 1, HEALTH_REBUILDING)
        summary = rs.health_summary()
        assert summary["status"] == "unhealthy"
        assert summary["serving_replicas"] == 0


class TestSupervisor:
    """N=1 supervisor equivalence (no router involved): a single replica
    that latches broken quarantines immediately, answers typed 503s while
    down, is rebuilt in place by the supervisor pass, and serves again.
    Driven via _supervise_once for determinism (the async supervisor
    thread is exercised by the replica-kill drill in test_chaos)."""

    def test_n1_quarantine_rebuild_recover(self):
        engine = _engine()
        svc = PagedGenerationService(engine, retry_budget=0)
        svc.generate("n1 supervisor warm", max_new_tokens=2, timeout_s=180)
        rs = ReplicaSet([svc], supervise=False, quarantine_backoff_s=0.0,
                        failover_budget=1)
        try:
            with faults.inject("paged.step",
                               error=RuntimeError("n1 kill"), times=1), \
                 faults.inject("engine.reset",
                               error=RuntimeError("n1 reset denied"),
                               times=1):
                with pytest.raises(ReplicaUnavailable):
                    rs.generate("doomed", max_new_tokens=4, timeout_s=120)
            assert svc.broken
            # the caller-path breaker quarantined it without any supervisor
            assert rs.health_summary()["replicas"][0]["state"] \
                == HEALTH_QUARANTINED
            # while down: typed 503 + Retry-After, from generate AND from
            # the stream pre-check — never an untyped 500
            with pytest.raises(ReplicaUnavailable) as exc_info:
                rs.generate("while down", max_new_tokens=2)
            assert exc_info.value.status == 503
            with pytest.raises(ReplicaUnavailable):
                rs.check_admission()
            # one supervisor pass rebuilds in place (backoff 0 → due now)
            rs._supervise_once()
            summary = rs.health_summary()
            assert summary["status"] == "healthy", summary
            assert summary["replicas"][0]["rebuilds"] == 1
            ok = rs.generate("recovered", max_new_tokens=3, timeout_s=180)
            assert ok.finish_reason in ("stop", "length")
            # the rebuilt engine is a fresh instance on the same weights
            assert rs._services[0] is not svc
            assert rs._services[0].engine is not engine
            assert rs._services[0].engine.params is engine.params
        finally:
            faults.reset()
            rs.close()

    def test_breaker_trips_on_tick_failure_burst(self):
        """Tick failures (with SUCCESSFUL resets — callers keep succeeding
        via requeue) still quarantine once the burst threshold is crossed:
        a replica that crashes every few ticks is a liability even though
        crash containment hides it from callers."""
        engine = _engine()
        svc = PagedGenerationService(engine, retry_budget=3)
        svc.generate("burst warm", max_new_tokens=2, timeout_s=180)
        rs = ReplicaSet([svc], supervise=False, breaker_tick_failures=2,
                        quarantine_backoff_s=60.0)
        try:
            with faults.inject("paged.step",
                               error=RuntimeError("flaky tick"), times=2):
                ok = rs.generate("survives the flaky ticks",
                                 max_new_tokens=4, timeout_s=120)
            assert ok.finish_reason in ("stop", "length")
            assert svc.tick_failure_count >= 2
            rs._supervise_once()
            state = rs.health_summary()["replicas"][0]["state"]
            assert state == HEALTH_QUARANTINED
            assert "tick failures" in \
                rs.health_summary()["replicas"][0]["reason"]
        finally:
            faults.reset()
            rs.close()

    def test_degraded_on_failure_then_clean_window_heals(self):
        engine = _engine()
        svc = PagedGenerationService(engine)
        rs = ReplicaSet([svc], supervise=False, breaker_window_s=0.3,
                        breaker_min_samples=50)
        try:
            rs._note_failure(0, ReplicaUnavailable("transient"))
            rs._supervise_once()
            assert rs.health_summary()["replicas"][0]["state"] \
                == HEALTH_DEGRADED
            time.sleep(0.4)  # window expires
            rs._supervise_once()
            assert rs.health_summary()["replicas"][0]["state"] \
                == HEALTH_HEALTHY
        finally:
            rs.close()

    def test_failover_releases_and_recharges_wfq(self):
        """Failover must not double-count tenant quota: after a failed-over
        generate completes, the tenant's pending count is zero and exactly
        one admission per attempt was recorded."""
        e0 = _engine()
        e1 = _engine(base=e0)
        svc0 = PagedGenerationService(e0, retry_budget=0)
        svc1 = PagedGenerationService(e1, retry_budget=0)
        svc0.generate("failover warm zero", max_new_tokens=2, timeout_s=180)
        svc1.generate("failover warm one", max_new_tokens=2, timeout_s=180)
        rs = ReplicaSet([svc0, svc1], supervise=False, failover_budget=1)
        try:
            with faults.inject("paged.step",
                               error=RuntimeError("kill once"), times=1), \
                 faults.inject("engine.reset",
                               error=RuntimeError("reset denied"), times=1):
                result = rs.generate("failover rider", max_new_tokens=4,
                                     temperature=0.0, timeout_s=120,
                                     tenant="team-f")
            assert result.finish_reason in ("stop", "length")
            stats = rs.stats()
            assert stats["failovers"] == 1
            tenant = stats["tenants"]["per_tenant"]["team-f"]
            assert tenant["pending"] == 0, "reservation leaked"
            assert tenant["admitted"] == 2, "one admission per attempt"
            # exactly one replica died and the set degraded, not collapsed
            assert [svc0.broken, svc1.broken].count(True) == 1
            assert rs.health_summary()["status"] == "degraded"
        finally:
            faults.reset()
            rs.close()


class TestStallTolerance:
    """ISSUE 10: the watchdog/handoff/rebuild-pool layer in isolation
    (the supervised end-to-end wedge is drilled in test_chaos)."""

    def test_heartbeat_age_none_when_idle(self):
        svc = PagedGenerationService(_engine(), tick_stall_budget_s=30.0)
        try:
            assert svc.heartbeat_age() is None  # no pump yet
            svc.generate("heartbeat idle probe", max_new_tokens=2,
                         timeout_s=180)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and svc.heartbeat_age() is not None:
                time.sleep(0.01)
            # pump drained and exited (or idles with zero pending): an idle
            # service is never stalled
            assert svc.heartbeat_age() is None
        finally:
            svc.close()

    def test_recharge_keeps_accounting_balanced(self):
        """The handoff's WFQ move: release + re-admit atomically — pending
        unchanged, one admission recorded; an over-quota tenant sheds typed
        with its reservation RESTORED so the caller's release balances."""
        q = TenantFairQueue(capacity=8, headroom=0)  # lone-tenant quota: 8
        q.admit("t", 10)
        q.recharge("t", 10)
        t = q.stats()["per_tenant"]["t"]
        assert t["pending"] == 1 and t["admitted"] == 2
        # t holds 6 while alone (within its lone quota of 8) ...
        for _ in range(5):
            q.admit("t", 10)
        # ... then a second tenant activates, HALVING t's quota to 4: a
        # handoff recharge now finds t over quota -> typed shed, with the
        # original reservation restored (pending untouched)
        q.admit("u", 10)
        with pytest.raises(ServiceOverloaded) as exc_info:
            q.recharge("t", 10)
        assert exc_info.value.details["shed_reason"] == "tenant_quota"
        assert q.stats()["per_tenant"]["t"]["pending"] == 6
        # unknown / already-released tenants are a no-op, never a crash
        q.recharge("ghost", 10)

    def test_stream_ticket_stamps_bucketed_tenant_key(self, monkeypatch):
        """The PR 10 recharge gap: stream tickets used to stamp the RAW
        tenant key, so a quarantine-handoff recharge of an overflow-
        bucketed stream tenant looked up a key the fair queue had never
        registered and silently skipped the re-charge. The ticket must
        carry the CHARGED key admit() actually resolved."""
        monkeypatch.setattr(TenantFairQueue, "MAX_TRACKED", 1)
        e0 = _engine()
        svc = PagedGenerationService(e0)
        rs = ReplicaSet([svc], supervise=False)
        try:
            # fill the (shrunken) tenant table so the next fresh key buckets
            rs.generate("seed tenant table", max_new_tokens=2,
                        tenant="first", timeout_s=180)
            stamped = []
            orig = svc.generate_stream

            def spy(prompt, **kwargs):
                stamped.append(kwargs.get("tenant"))
                return orig(prompt, **kwargs)

            monkeypatch.setattr(svc, "generate_stream", spy)
            out = "".join(rs.generate_stream(
                "bucketed stream tenant probe", max_new_tokens=2,
                tenant="fresh-stream-tenant", timeout_s=180,
            ))
            assert isinstance(out, str)
            # call-time iterator carries the raw key; admission resolves the
            # overflow bucket and the ticket is re-created with THAT key
            assert stamped[0] == "fresh-stream-tenant"
            assert stamped[-1] == TenantFairQueue.OVERFLOW_TENANT
            # the key on the ticket must be rechargeable while HELD — a
            # handoff moves a still-pending ticket, and its recharge must
            # record an admission instead of no-op'ing on an unknown key
            # (the raw "fresh-stream-tenant" key would hit exactly that)
            charged = rs.tenants.admit("second-fresh-tenant", 4)
            assert charged == TenantFairQueue.OVERFLOW_TENANT == stamped[-1]
            per_before = rs.tenants.stats()["per_tenant"][charged]
            rs.tenants.recharge(stamped[-1], 4)
            per_after = rs.tenants.stats()["per_tenant"][charged]
            assert per_after["admitted"] == per_before["admitted"] + 1
            assert per_after["pending"] == per_before["pending"]
            rs.tenants.release(charged, 4)
        finally:
            rs.close()

    def test_breaker_quarantine_hands_off_inbox(self):
        """Quarantine (breaker flavor, not just stall) moves the dead
        replica's queued-never-dispatched tickets to the survivor instead
        of leaving them to ride each caller's failover loop: the blocked
        caller just wakes with the survivor's result."""
        e0 = _engine()
        e1 = _engine(base=e0)
        svc0 = PagedGenerationService(e0)
        svc1 = PagedGenerationService(e1)
        svc0.generate("handoff warm zero", max_new_tokens=2, timeout_s=180)
        svc1.generate("handoff warm one", max_new_tokens=2, timeout_s=180)
        rs = ReplicaSet([svc0, svc1], supervise=False)
        try:
            # plant a ticket straight into replica 0's inbox with WFQ
            # metadata, as the router would on a submit that raced the
            # breaker (the pump is idle-exited, so it stays undispatched
            # until a pump would spawn — generate() in a thread)
            outcome: dict = {}

            def call():
                try:
                    outcome["r"] = svc0.generate(
                        "wedged in flight", max_new_tokens=3,
                        temperature=0.0, timeout_s=60,
                    )
                except Exception as exc:  # noqa: BLE001
                    outcome["r"] = exc

            # hold replica 0's pump wedged so later tickets stay queued
            release = threading.Event()
            with faults.inject("paged.step", stall_event=release,
                               stall_s=30.0, times=1) as rule:
                t = threading.Thread(target=call)
                t.start()
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline and rule.stalled == 0:
                    time.sleep(0.005)
                assert rule.stalled == 1
                # second caller piles into the wedged inbox, carrying the
                # WFQ metadata the router would have stamped (plus the
                # caller-side charge it pairs with)
                rs.tenants.admit(DEFAULT_TENANT, 8)
                outcome2: dict = {}

                def call2():
                    try:
                        outcome2["r"] = svc0.generate(
                            "second queued ticket", max_new_tokens=3,
                            temperature=0.0, timeout_s=60,
                            tenant=DEFAULT_TENANT, cost_tokens=8,
                        )
                    except Exception as exc:  # noqa: BLE001
                        outcome2["r"] = exc

                t2 = threading.Thread(target=call2)
                t2.start()
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline and len(svc0._inbox) < 1:
                    time.sleep(0.005)
                # breaker-flavor quarantine: inbox moves, admitted stays
                rs._quarantine(0, "seeded breaker trip")
                t2.join(timeout=60)
                assert isinstance(outcome2["r"], PagedResult), outcome2["r"]
                assert outcome2["r"].finish_reason in ("stop", "length")
                assert rs.stats()["handed_off"] >= 1
                # the first (admitted, wedged) ticket is NOT handed off —
                # it still sits on the wedged engine
                assert not outcome
                release.set()
                t.join(timeout=60)
            # breaker quarantine leaves a WORKING service: the unwedged
            # pump finishes its admitted ticket normally
            assert isinstance(outcome.get("r"), PagedResult), outcome
            tenants = rs.tenants.stats()["per_tenant"][DEFAULT_TENANT]
            rs.tenants.release(DEFAULT_TENANT, 8)
            # caller-side admit + the handoff's recharge, reservation held
            # throughout (never double-counted, never leaked)
            assert tenants["admitted"] == 2, tenants
            assert tenants["pending"] == 1, tenants
            _assert_pages_conserved(rs)
        finally:
            faults.reset()
            rs.close()

    def test_stalled_rebuild_does_not_delay_second_quarantine(self):
        """Acceptance: a rebuild wedged via the ``replica.rebuild`` stall
        fault occupies a WORKER, not the supervisor — the detection pass
        keeps its cadence and quarantines a second replica promptly, even
        with a single rebuild worker (the second rebuild just queues)."""
        from sentio_tpu.runtime.replica import HEALTH_REBUILDING

        e0 = _engine()
        e1 = _engine(base=e0)
        svc0 = PagedGenerationService(e0, retry_budget=0)
        svc1 = PagedGenerationService(e1, retry_budget=0)
        svc0.generate("pool warm zero", max_new_tokens=2, timeout_s=180)
        svc1.generate("pool warm one", max_new_tokens=2, timeout_s=180)
        rs = ReplicaSet(
            [svc0, svc1],
            probe_interval_s=0.02, quarantine_backoff_s=0.0,
            rebuild_drain_s=0.2, failover_budget=1, rebuild_workers=1,
        )
        release = threading.Event()
        try:
            # wedge replica 0's rebuild on the worker
            rule = faults.FaultRule(stall_event=release, stall_s=60.0,
                                    times=1)
            faults.arm("replica.rebuild", rule)
            rs._quarantine(0, "seeded for wedged rebuild")
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and rule.stalled == 0:
                time.sleep(0.01)
            assert rule.stalled == 1, "rebuild never started on the worker"
            assert rs.health_summary()["replicas"][0]["state"] \
                == HEALTH_REBUILDING
            # with the rebuild wedged, kill replica 1: the supervisor's
            # detection pass must quarantine it promptly
            with faults.inject("paged.step",
                               error=RuntimeError("kill two"), times=1), \
                 faults.inject("engine.reset",
                               error=RuntimeError("reset denied"), times=1):
                with pytest.raises(ReplicaUnavailable):
                    rs.generate("doomed on replica one", max_new_tokens=4,
                                timeout_s=120)
            t_kill = time.monotonic()
            deadline = time.monotonic() + 10
            state = None
            while time.monotonic() < deadline:
                state = rs.health_summary()["replicas"][1]["state"]
                if state == HEALTH_QUARANTINED:
                    break
                time.sleep(0.01)
            assert state == HEALTH_QUARANTINED, (
                f"second quarantine waited on the wedged rebuild: {state}"
            )
            assert time.monotonic() - t_kill < 5.0
            # replica 0 is still wedged mid-rebuild the whole time
            assert rs.health_summary()["replicas"][0]["state"] \
                == HEALTH_REBUILDING
            # release: replica 0's rebuild completes, then the worker picks
            # up replica 1's queued rebuild; the set returns to health
            release.set()
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if rs.health_summary()["status"] == "healthy":
                    break
                time.sleep(0.05)
            summary = rs.health_summary()
            assert summary["status"] == "healthy", summary
            assert summary["replicas"][0]["rebuilds"] == 1
            assert summary["replicas"][1]["rebuilds"] == 1
            ok = rs.generate("post pool recovery", max_new_tokens=3,
                             timeout_s=180)
            assert ok.finish_reason in ("stop", "length")
        finally:
            release.set()
            faults.reset()
            rs.close()


class TestResumableStreamWfq:
    """ISSUE 14: WFQ tenant accounting must stay balanced across every
    resume path. Each attempt — fresh, failed-over, or resumed by
    replay-prefill — releases its reservation before re-charging, so a
    resumed stream records exactly one admission per attempt and leaves
    ``pending`` at zero whether the resume succeeded, exhausted its
    budget, or rode an overflow-bucketed tenant key."""

    PROMPT = "wfq conservation drill stream with a decent prompt body"

    @staticmethod
    def _two_replica_set(**svc_kw):
        # the seeded tiny model greedy-samples EOS two tokens in: only a
        # fixed-length answer spans the ticks (8 tokens, 2 a tick) that
        # "tick 2 dies" needs
        e0 = _engine(ignore_eos=True)
        e1 = _engine(base=e0, ignore_eos=True)
        svc0 = PagedGenerationService(e0, **svc_kw)
        svc1 = PagedGenerationService(e1, **svc_kw)
        # both warmed BEFORE any fault arms: warmup ticks must not eat a
        # skip-counted fault hit, and idle pumps exit after draining so the
        # drill stream's replica is the only one stepping
        svc0.generate("wfq warm zero", max_new_tokens=2, timeout_s=180)
        svc1.generate("wfq warm one", max_new_tokens=2, timeout_s=180)
        return svc0, svc1

    def test_successful_resume_balances_tenant_accounting(self):
        """(a) a mid-stream death resumed onto the survivor: the stream
        completes, one admission per attempt, zero pending after."""
        svc0, svc1 = self._two_replica_set()
        rs = ReplicaSet([svc0, svc1], supervise=False, failover_budget=1)
        try:
            # tick 1 delivers a chunk (skip=1), tick 2 dies: at least one
            # token is always delivered before the death
            faults.arm("paged.step", faults.FaultRule(
                error=RuntimeError("wfq drill: midstream death"),
                times=1, skip=1))
            out = "".join(rs.generate_stream(
                self.PROMPT, max_new_tokens=8, temperature=0.0,
                timeout_s=120, tenant="team-r",
            ))
            faults.reset()
            assert out
            stats = rs.stats()
            assert stats["stream_resumes"] == 1
            assert stats["resume_exhausted"] == 0
            tenant = stats["tenants"]["per_tenant"]["team-r"]
            assert tenant["pending"] == 0, "reservation leaked"
            assert tenant["admitted"] == 2, "one admission per attempt"
        finally:
            faults.reset()
            rs.close()

    def test_exhausted_budget_balances_and_stays_typed(self):
        """(b) the resumed attempt dies too and the budget is spent: the
        caller gets the typed mid-stream error, the exhausted outcome is
        counted, and the tenant's ledger is still balanced."""
        # retry_budget=0: the survivor's failed tick kills the resumed
        # ticket typed instead of requeueing it service-side, so the second
        # death deterministically reaches the router's budget check
        svc0, svc1 = self._two_replica_set(retry_budget=0)
        rs = ReplicaSet([svc0, svc1], supervise=False, failover_budget=1)
        try:
            # hit 1 passes (a chunk delivers), hits 2+3 die: the original
            # replica mid-stream, then the survivor's resumed attempt
            faults.arm("paged.step", faults.FaultRule(
                error=RuntimeError("wfq drill: double death"),
                times=2, skip=1))
            with pytest.raises(ReplicaUnavailable):
                for _ in rs.generate_stream(
                        self.PROMPT, max_new_tokens=8, temperature=0.0,
                        timeout_s=120, tenant="team-x"):
                    pass
            faults.reset()
            stats = rs.stats()
            assert stats["stream_resumes"] == 1, "first resume still books"
            assert stats["resume_exhausted"] == 1
            tenant = stats["tenants"]["per_tenant"]["team-x"]
            assert tenant["pending"] == 0, "reservation leaked"
            assert tenant["admitted"] == 2, "one admission per attempt"
        finally:
            faults.reset()
            rs.close()

    def test_overflow_bucketed_tenant_resumes_balanced(self, monkeypatch):
        """(c) the PR 11(a) regression shape under RESUME: a stream whose
        fresh tenant key overflow-bucketed at admission must release and
        re-charge the CHARGED key on every resume attempt — the raw key
        was never registered and would silently leak the reservation."""
        monkeypatch.setattr(TenantFairQueue, "MAX_TRACKED", 1)
        svc0, svc1 = self._two_replica_set()
        rs = ReplicaSet([svc0, svc1], supervise=False, failover_budget=1)
        try:
            # fill the (shrunken) tenant table so the stream's key buckets
            rs.generate("seed tenant table", max_new_tokens=2,
                        tenant="first", timeout_s=180)
            overflow = TenantFairQueue.OVERFLOW_TENANT
            # the bucket only registers at its first admission
            before = rs.tenants.stats()["per_tenant"].get(
                overflow, {"pending": 0, "admitted": 0})
            assert before["pending"] == 0
            faults.arm("paged.step", faults.FaultRule(
                error=RuntimeError("wfq drill: bucketed death"),
                times=1, skip=1))
            out = "".join(rs.generate_stream(
                self.PROMPT, max_new_tokens=8, temperature=0.0,
                timeout_s=120, tenant="fresh-stream-tenant",
            ))
            faults.reset()
            assert out
            assert rs.stats()["stream_resumes"] == 1
            after = rs.tenants.stats()["per_tenant"][overflow]
            assert after["pending"] == 0, "bucketed reservation leaked"
            assert after["admitted"] == before["admitted"] + 2, (
                "one admission per attempt on the CHARGED key"
            )
        finally:
            faults.reset()
            rs.close()


class TestVerifyTenantCharging:
    """ROADMAP item 1 leftover: verify-node decode admissions must be
    charged to the REQUESTING tenant's WFQ quota, not the shared default —
    otherwise one tenant's verify traffic rides free and can starve every
    other tenant."""

    def _verifier_over(self, service):
        from sentio_tpu.config import GeneratorConfig
        from sentio_tpu.ops.generator import LLMGenerator, TpuProvider
        from sentio_tpu.ops.verifier import AnswerVerifier

        cfg = GeneratorConfig(provider="tpu", verifier_max_tokens=8)
        generator = LLMGenerator(
            provider=TpuProvider(service=service), config=cfg)
        return AnswerVerifier(generator=generator, config=cfg)

    def test_verify_charges_request_tenant_and_cannot_starve(self):
        """A flooding tenant's verify calls saturate ITS quota (typed sheds
        → degraded 'warn' verdicts), while another tenant's verify call
        admits mid-flood and completes — through a real TenantFairQueue."""
        import queue as _q

        release = threading.Event()
        charged: list[str] = []
        queue = TenantFairQueue(capacity=4, headroom=2)  # lone quota: 2

        class GatedSet:
            """Replica-tier-shaped fake: supports_tenants + a real WFQ in
            front of a generate that holds its admission until released
            (standing in for a slow decode)."""

            supports_tenants = True

            def generate(self, prompt, max_new_tokens=64, temperature=0.0,
                         request_id=None, deadline_ts=None, tenant=None,
                         priority=None, **kw):
                key = queue.admit(tenant or DEFAULT_TENANT, 8)
                charged.append(key)
                try:
                    release.wait(30)
                finally:
                    queue.release(key, 8)
                return PagedResult(
                    request_id=0,
                    text='{"verdict": "pass", "citations_ok": true, '
                         '"notes": []}',
                    tokens=[1], prompt_tokens=1, finish_reason="stop",
                )

        verifier = self._verifier_over(GatedSet())
        results: dict[str, object] = {}

        def verify_as(tag, tenant):
            results[tag] = verifier.verify(
                "q?", "answer", [], tenant=tenant)

        hold = [threading.Thread(target=verify_as, args=(f"a{i}", "team-a"))
                for i in range(2)]
        for t in hold:
            t.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and len(charged) < 2:
            time.sleep(0.005)
        assert charged.count("team-a") == 2
        # 3rd team-a verify: over ITS quota → typed shed → warn verdict
        verify_as("a2", "team-a")
        warn = results["a2"]
        assert warn.verdict == "warn"
        assert any("quota" in note for note in warn.notes), warn.notes
        # team-b's verify admits inside the reserved headroom mid-flood
        b = threading.Thread(target=verify_as, args=("b0", "team-b"))
        b.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and "team-b" not in charged:
            time.sleep(0.005)
        assert "team-b" in charged, "tenant B's verify was starved"
        release.set()
        for t in hold:
            t.join(timeout=30)
        b.join(timeout=30)
        assert results["b0"].verdict == "pass"

    def test_verify_node_threads_tenant_from_metadata(self):
        import asyncio

        from sentio_tpu.graph.nodes import create_verifier_node
        from sentio_tpu.ops.verifier import VerifyResult

        captured: dict = {}

        class StubVerifier:
            def verify(self, query, answer, docs, request_id=None,
                       deadline_ts=None, tenant=None, priority=None):
                captured.update(tenant=tenant, priority=priority,
                                request_id=request_id)
                return VerifyResult(verdict="pass")

        from sentio_tpu.config import Settings

        node = create_verifier_node(StubVerifier(), settings=Settings())
        state = {
            "query": "q?",
            "response": "an answer",
            "retrieved_documents": [],
            "metadata": {"query_id": "vt-1", "tenant": "team-z",
                         "priority": "batch"},
        }
        out = asyncio.run(node(state))
        assert out["evaluation"]["verdict"] == "pass"
        assert captured["tenant"] == "team-z"
        assert captured["priority"] == "batch"
        assert captured["request_id"] == "vt-1"


class TestLifecycleFanOut:
    def test_warmup_warms_every_replica(self):
        e0 = _engine()
        e1 = _engine(base=e0)
        rs = ReplicaSet([PagedGenerationService(e0),
                         PagedGenerationService(e1)])
        try:
            out = rs.warmup(max_new_tokens=2)
            assert out["replicas"] == 2
            assert out["prompts"] > 0
            for s in rs.stats()["replicas"]:
                assert s["completed"] > 0, (
                    f"replica {s['replica']} was never warmed: {s}"
                )
        finally:
            rs.close()

    def test_drain_concurrent_and_aggregated(self, replica_set):
        out = replica_set.drain(deadline_s=30.0)
        assert out["drained"] is True
        assert out["abandoned"] == 0
        assert [r["replica"] for r in out["replicas"]] == [0, 1]
        with pytest.raises((ReplicaUnavailable, ServiceOverloaded)):
            replica_set.generate("after drain", max_new_tokens=2)

    def test_leaked_pump_sums_without_double_count(self):
        e0 = _engine()
        e1 = _engine(base=e0)
        svc0 = PagedGenerationService(e0)
        svc1 = PagedGenerationService(e1)
        rs = ReplicaSet([svc0, svc1])
        release = threading.Event()

        class StuckPump:
            name = "paged-decode-pump"
            daemon = True

            def join(self, timeout=None):
                pass

            def is_alive(self):
                return not release.is_set()

        with svc1._mutex:
            svc1._pump = StuckPump()
        rs.close()
        stats = rs.stats()
        assert stats["pump_leaked"] == 1
        assert [s["pump_leaked"] for s in stats["replicas"]] == [0, 1]
        release.set()


class TestMeshSplit:
    def test_split_dp_into_disjoint_submeshes(self):
        from sentio_tpu.config import MeshConfig
        from sentio_tpu.parallel.mesh import AXIS_DP, build_mesh, split_mesh_dp

        mesh = build_mesh(MeshConfig())  # 8 virtual CPU devices, all on dp
        subs = split_mesh_dp(mesh, 2)
        assert len(subs) == 2
        seen = set()
        for sub in subs:
            assert sub.shape[AXIS_DP] == mesh.shape[AXIS_DP] // 2
            ids = {d.id for d in sub.devices.flat}
            assert not (ids & seen), "replicas share devices"
            seen |= ids
        assert len(seen) == len(list(mesh.devices.flat))

    def test_ragged_split_raises(self):
        from sentio_tpu.config import MeshConfig
        from sentio_tpu.parallel.mesh import MeshError, build_mesh, split_mesh_dp

        mesh = build_mesh(MeshConfig())
        with pytest.raises(MeshError, match="not divisible"):
            split_mesh_dp(mesh, 3)
