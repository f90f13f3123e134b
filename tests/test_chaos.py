"""Chaos drill: the paged serving path under faults-plus-load (tier 1).

SURVEY §5 prescribes fault-injection-driven resilience; this is the drill
that exercises it end to end: probabilistic decode-tick faults armed while
concurrent generate/stream callers hammer the service. The contract under
chaos (the contract vLLM-class systems must keep, Kwon et al., SOSP '23):

* every caller reaches a TERMINAL outcome — a result, a typed shed/deadline
  error, or a budgeted error result; nobody hangs;
* a tick failure with a successful ``engine.reset()`` requeues innocent
  waiters (per-ticket retry budget) instead of failing all of them;
* page-pool conservation holds throughout (conftest arms SENTIO_SANITIZE=1
  for this module, so every tick self-checks);
* no pump or waiter threads leak.

Engines here are tiny (default LlamaConfig.tiny) so the drill runs in the
quick tier — the point is scheduler/recovery logic, not model quality.
"""

import threading
import time

import pytest

from sentio_tpu.infra import faults
from sentio_tpu.infra.exceptions import (
    DeadlineExceededError,
    ReplicaUnavailable,
    SentioError,
    ServiceOverloaded,
)
from sentio_tpu.runtime.paged import ContinuousBatchingEngine, PagedResult
from sentio_tpu.runtime.service import PagedGenerationService


@pytest.fixture(scope="module")
def engine():
    # ONE engine for the module: each engine instance owns fresh jit
    # wrappers, so more engines = more XLA compiles in the quick tier
    return ContinuousBatchingEngine(
        max_slots=4, page_size=8, max_pages_per_seq=4, steps_per_tick=2,
    )


# the seeded tiny model greedy-samples EOS two tokens in; the drills that
# kill "tick 2" of a stream need an answer that spans at least three ticks,
# which only a fixed-length one does (2 tokens a tick)
_MULTI_TICK = dict(max_slots=2, page_size=8, max_pages_per_seq=4,
                   steps_per_tick=2, ignore_eos=True)


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.reset()


def _assert_pages_conserved(svc):
    s = svc.stats()
    assert s["free_pages"] + s.get("prefix_cache_pages", 0) \
        == s["total_pages"] - 1, s


def _catch(fn, **kwargs):
    """Run ``fn`` and return its result OR the exception it raised — for
    threads whose outcome (either way) the test asserts on afterwards."""
    try:
        return fn(**kwargs)
    except Exception as exc:  # noqa: BLE001 — the test inspects the type
        return exc


def _build_parallel(build_fn, n=2, timeout_s=360.0):
    """Construct ``n`` replicas CONCURRENTLY. Each ProcessReplica
    constructor blocks through a full spawn + jax init + handshake
    (~10 s on CPU); building the drills' 2-worker sets serially doubles
    that startup wall time for no isolation benefit."""
    out: dict = {}
    errs: dict = {}

    def run(i):
        try:
            out[i] = build_fn(i)
        except BaseException as exc:  # noqa: BLE001 — re-raised below
            errs[i] = exc

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout_s)
    if errs:
        for built in out.values():  # don't leak the siblings that DID spawn
            try:
                built.close()
            except Exception:  # noqa: BLE001 — already failing
                pass
        raise next(iter(errs.values()))
    assert len(out) == n, "replica construction timed out"
    return [out[i] for i in range(n)]


def _assert_no_pump_threads(timeout_s: float = 15.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        pumps = [t for t in threading.enumerate()
                 if t.name.startswith(("paged-decode-pump",
                                       "replica-supervisor",
                                       "replica-rebuild"))
                 and t.is_alive()]
        if not pumps:
            return
        time.sleep(0.05)
    raise AssertionError(f"leaked pump/supervisor threads: {pumps}")


class TestChaosDrill:
    def test_mixed_load_under_probabilistic_tick_faults(self, engine):
        """≥8 concurrent mixed generate/stream callers while every decode
        tick fails with probability 0.25: all callers terminate, the pool
        conserves, the service still works afterwards, nothing leaks."""
        svc = PagedGenerationService(engine, retry_budget=2)
        outcomes: dict[str, object] = {}

        def call_generate(i):
            try:
                outcomes[f"g{i}"] = svc.generate(
                    f"chaos generate load {i}", max_new_tokens=6,
                    temperature=0.0, timeout_s=120,
                )
            except Exception as exc:  # noqa: BLE001 — any typed error is terminal
                outcomes[f"g{i}"] = exc

        def call_stream(i):
            try:
                outcomes[f"s{i}"] = "".join(svc.generate_stream(
                    f"chaos stream load {i}", max_new_tokens=6,
                    temperature=0.0, timeout_s=120,
                ))
            except Exception as exc:  # noqa: BLE001
                outcomes[f"s{i}"] = exc

        with faults.inject("paged.step", error=RuntimeError("chaos tick"),
                           probability=0.25, seed=1234) as rule:
            threads = (
                [threading.Thread(target=call_generate, args=(i,)) for i in range(5)]
                + [threading.Thread(target=call_stream, args=(i,)) for i in range(4)]
            )
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert not any(t.is_alive() for t in threads), (
                "caller thread hung under chaos"
            )
        assert rule.fired >= 1, "drill never actually injected a fault"
        # EVERY caller reached a terminal outcome
        assert len(outcomes) == 9
        for name, out in outcomes.items():
            assert isinstance(out, (PagedResult, str, Exception)), (name, out)
            if isinstance(out, PagedResult):
                assert out.finish_reason in ("stop", "length", "error"), (name, out)
        # the service survived: a post-chaos request works end to end
        ok = svc.generate("post chaos sanity", max_new_tokens=4, timeout_s=120)
        assert ok.finish_reason in ("stop", "length")
        _assert_pages_conserved(svc)
        svc.close()
        _assert_no_pump_threads()

    def test_tick_failure_requeues_innocent_waiters(self, engine):
        """One failed tick + successful reset: BOTH in-flight waiters are
        requeued and complete normally — the pre-fix behavior failed every
        waiter via _fail_all_locked even after a clean reset."""
        svc = PagedGenerationService(engine, retry_budget=1)
        results = {}

        def call(i):
            results[i] = svc.generate(
                f"innocent waiter number {i} with padding", max_new_tokens=6,
                temperature=0.0, timeout_s=120,
            )

        with faults.inject("paged.step", error=RuntimeError("one bad tick"),
                           times=1) as rule:
            threads = [threading.Thread(target=call, args=(i,)) for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        assert rule.fired == 1
        assert len(results) == 2
        for i, res in results.items():
            assert res.finish_reason in ("stop", "length"), (i, res)
        stats = svc.stats()
        assert stats["requeued"] >= 1, stats
        assert stats["tick_failures"] == 1, stats
        _assert_pages_conserved(svc)
        svc.close()

    def test_exhausted_budget_fails_only_that_ticket(self, engine):
        """A stream that already delivered tokens cannot be resubmitted
        (restart would duplicate output) — after a tick failure it gets the
        error, while a queued generate is requeued and succeeds.

        Determinism: phase 1 arms a delay-only rule (every tick sleeps, so
        the short stream cannot outrun the test), phase 2 swaps in the
        one-shot error once BOTH requests are observably in flight."""
        svc = PagedGenerationService(
            ContinuousBatchingEngine(
                params=engine.params, tokenizer=engine.tokenizer,
                **_MULTI_TICK),
            retry_budget=1)
        stream_err: list = []
        stream_text: list[str] = []
        faults.arm("paged.step", faults.FaultRule(delay_s=0.1))

        def consume():
            try:
                for piece in svc.generate_stream(
                    "s",  # short prompt: maximum decode room in the window
                    max_new_tokens=200, temperature=0.0, timeout_s=120,
                ):
                    stream_text.append(piece)
            except Exception as exc:  # noqa: BLE001
                stream_err.append(exc)

        streamer = threading.Thread(target=consume)
        streamer.start()
        # wait until real tokens flowed to the stream consumer
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not stream_text:
            time.sleep(0.005)
        assert stream_text, "stream produced nothing to be mid-flight with"
        gen_result: dict = {}

        def call():
            gen_result["r"] = svc.generate(
                "innocent generate behind the doomed stream",
                max_new_tokens=4, temperature=0.0, timeout_s=120,
            )

        t = threading.Thread(target=call)
        t.start()
        # both requests visible to the service before the fault arms
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            s = svc.stats()
            if s["active_slots"] + s["queued"] + s["queued_inbox"] >= 2:
                break
            time.sleep(0.005)
        faults.arm("paged.step", faults.FaultRule(
            error=RuntimeError("boom"), times=1))
        t.join(timeout=120)
        streamer.join(timeout=120)
        faults.disarm("paged.step")
        assert not streamer.is_alive()
        # the delivered-tokens stream is the casualty...
        assert stream_err, "mid-flight stream should have been failed"
        # ...while the resubmittable generate survived the same tick failure
        assert gen_result["r"].finish_reason in ("stop", "length")
        _assert_pages_conserved(svc)
        svc.close()

    def test_replica_kill_drill_failover_and_rebuild(self):
        """ISSUE 8 acceptance drill (sanitizer armed for this module): one
        of 2 replicas is killed mid-traffic — a decode tick fails AND its
        ``engine.reset()`` is forced to fail, so the replica latches broken
        — under ≥8 concurrent mixed generate/stream callers. The contract:

        * every caller terminates with a TYPED outcome (a result, text, or
          a SentioError — never a bare RuntimeError);
        * the surviving replica keeps serving during the outage;
        * the supervisor quarantines the corpse, rebuilds it in place from
          the shared weights, and the REBUILT replica serves a request
          before the test ends;
        * page pools conserve on both sides and no pump/supervisor threads
          leak."""
        from sentio_tpu.runtime.replica import HEALTH_HEALTHY, ReplicaSet

        e0 = ContinuousBatchingEngine(
            max_slots=2, page_size=8, max_pages_per_seq=4, steps_per_tick=2,
        )
        e1 = ContinuousBatchingEngine(
            params=e0.params, tokenizer=e0.tokenizer,
            max_slots=2, page_size=8, max_pages_per_seq=4, steps_per_tick=2,
        )
        svc0 = PagedGenerationService(e0, retry_budget=1)
        svc1 = PagedGenerationService(e1, retry_budget=1)
        # pre-compile both engines so the drill's traffic exercises the
        # failure machinery instead of waiting out XLA compiles
        svc0.generate("drill warm zero", max_new_tokens=2, timeout_s=180)
        svc1.generate("drill warm one", max_new_tokens=2, timeout_s=180)
        rs = ReplicaSet(
            [svc0, svc1],
            probe_interval_s=0.05, quarantine_backoff_s=0.1,
            breaker_tick_failures=2, failover_budget=2,
        )
        outcomes: dict[str, object] = {}

        def call_generate(i):
            try:
                outcomes[f"g{i}"] = rs.generate(
                    f"replica drill generate {i}", max_new_tokens=6,
                    temperature=0.0, timeout_s=120,
                )
            except Exception as exc:  # noqa: BLE001 — typed errors terminal
                outcomes[f"g{i}"] = exc

        def call_stream(i):
            try:
                outcomes[f"s{i}"] = "".join(rs.generate_stream(
                    f"replica drill stream {i}", max_new_tokens=6,
                    temperature=0.0, timeout_s=120,
                ))
            except Exception as exc:  # noqa: BLE001
                outcomes[f"s{i}"] = exc

        try:
            # armed BEFORE traffic: whichever replica ticks first dies with
            # an unrecoverable reset (deterministically exactly one kill)
            faults.arm("paged.step", faults.FaultRule(
                error=RuntimeError("drill: replica kill"), times=1))
            faults.arm("engine.reset", faults.FaultRule(
                error=RuntimeError("drill: reset denied"), times=1))
            threads = (
                [threading.Thread(target=call_generate, args=(i,))
                 for i in range(5)]
                + [threading.Thread(target=call_stream, args=(i,))
                   for i in range(4)]
            )
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert not any(t.is_alive() for t in threads), (
                "caller thread hung across the replica kill"
            )
            faults.reset()
            # exactly one replica latched broken
            dead = [i for i, svc in enumerate((svc0, svc1)) if svc.broken]
            assert len(dead) == 1, f"expected one broken replica, got {dead}"
            # EVERY caller terminated with a typed outcome; the survivor
            # absorbed failed-over load (successes exist despite the kill)
            assert len(outcomes) == 9
            successes = 0
            for name, out in outcomes.items():
                if isinstance(out, Exception):
                    assert isinstance(out, SentioError), (
                        f"{name}: untyped {type(out).__name__}: {out}"
                    )
                else:
                    assert isinstance(out, (PagedResult, str)), (name, out)
                    if isinstance(out, PagedResult):
                        assert out.finish_reason in ("stop", "length"), (
                            name, out,
                        )
                    successes += 1
            assert successes >= 1, (
                f"survivor never served during the outage: {outcomes}"
            )
            # the supervisor rebuilds the corpse in place and the set
            # returns to full health
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if rs.health_summary()["status"] == "healthy":
                    break
                time.sleep(0.05)
            summary = rs.health_summary()
            assert summary["status"] == "healthy", summary
            assert summary["replicas"][dead[0]]["rebuilds"] == 1, summary
            # the REBUILT replica itself serves (not just the survivor):
            # route directly at the fresh service occupying the dead slot
            rebuilt = rs._services[dead[0]]
            assert rebuilt is not (svc0, svc1)[dead[0]]
            ok = rebuilt.generate("rebuilt replica serves again",
                                  max_new_tokens=3, timeout_s=180)
            assert ok.finish_reason in ("stop", "length")
            # ... and through the router too
            ok2 = rs.generate("post drill routed sanity", max_new_tokens=3,
                              timeout_s=120)
            assert ok2.finish_reason in ("stop", "length")
            # health transitions were evented to the flight recorder
            from sentio_tpu.infra.flight import get_flight_recorder

            events = [t for t in get_flight_recorder().timeline()
                      if t.get("event") == "replica_health"]
            seen = {(e["state_from"], e["state_to"]) for e in events}
            assert ("HEALTHY", "QUARANTINED") in seen, seen
            assert ("QUARANTINED", "REBUILDING") in seen, seen
            assert ("REBUILDING", "HEALTHY") in seen, seen
            # page-pool conservation on BOTH sides of the kill (sanitizer
            # checked every tick; this is the end-state audit)
            for s in rs.stats()["replicas"]:
                assert s["free_pages"] + s.get("prefix_cache_pages", 0) \
                    == s["total_pages"] - 1, s
            assert rs.stats()["health"]["replicas"][dead[0]]["state"] \
                == HEALTH_HEALTHY
        finally:
            faults.reset()
            rs.close()
        _assert_no_pump_threads()

    def test_replica_stall_drill_watchdog_handoff_and_rebuild(self):
        """ISSUE 10 acceptance drill (sanitizer armed for this module): one
        of 2 replicas is WEDGED mid-traffic — its next decode tick blocks
        inside a stall fault, raising nothing, exactly like a hung device
        dispatch. The contract:

        * the watchdog quarantines the stalled replica within 2x its
          ``TICK_STALL_BUDGET_S`` (no exception required — heartbeat age
          with pending work is the whole signal);
        * the wedged replica's never-dispatched INBOX tickets are handed
          off directly to the survivor and complete there WITHOUT their
          callers observing any failure (failover budget untouched);
        * its admitted ticket fails typed and fails over (one failover);
        * every caller outcome is typed, pages conserve on the surviving
          replica, the abandoned pump is accounted in ``stats()``
          (pump_leaked survives the rebuild swap via carryover), and the
          rebuilt replica serves again."""
        from sentio_tpu.runtime.replica import HEALTH_HEALTHY, ReplicaSet

        # generous budget: a LEGITIMATE tick on the survivor may include a
        # multi-second cold XLA compile (a new prefill width/row variant
        # for the adopted tickets) and must never read as a stall
        budget_s = 5.0
        e0 = ContinuousBatchingEngine(
            max_slots=2, page_size=8, max_pages_per_seq=4, num_pages=65,
            steps_per_tick=2,
        )
        e1 = ContinuousBatchingEngine(
            params=e0.params, tokenizer=e0.tokenizer,
            max_slots=2, page_size=8, max_pages_per_seq=4, num_pages=65,
            steps_per_tick=2,
        )
        svc0 = PagedGenerationService(e0, retry_budget=1,
                                      tick_stall_budget_s=budget_s)
        svc1 = PagedGenerationService(e1, retry_budget=1,
                                      tick_stall_budget_s=budget_s)
        # pre-compile + seed a distinct radix session per replica: after
        # the wedge, follow-ups on the wedged replica's session prefix
        # route to it by affinity and pile into its (never-drained) inbox
        sessions = ["session zero affinity head spanning pages easily ",
                    "session one affinity head spanning pages easily "]
        svc0.generate(sessions[0] + "seed", max_new_tokens=2, timeout_s=180)
        svc1.generate(sessions[1] + "seed", max_new_tokens=2, timeout_s=180)
        rs = ReplicaSet(
            [svc0, svc1],
            probe_interval_s=0.05, quarantine_backoff_s=0.1,
            rebuild_drain_s=0.3, failover_budget=2,
        )
        release = threading.Event()
        outcomes: dict[str, object] = {}

        def call(tag, prompt):
            try:
                outcomes[tag] = rs.generate(prompt, max_new_tokens=4,
                                            temperature=0.0, timeout_s=120)
            except Exception as exc:  # noqa: BLE001 — typed errors terminal
                outcomes[tag] = exc
        try:
            # one-shot wedge: the next decode tick anywhere blocks until
            # release (120s worst-case cap); both pumps are idle, so the
            # single request below deterministically picks the victim
            rule = faults.FaultRule(stall_event=release, stall_s=120.0,
                                    times=1)
            faults.arm("paged.step", rule)
            t_a = threading.Thread(target=call,
                                   args=("admitted", "cold wedge probe"))
            t_a.start()
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and rule.stalled == 0:
                time.sleep(0.005)
            assert rule.stalled == 1, "no pump ever wedged"
            t_wedge = time.monotonic()
            dead = max(range(2), key=lambda i: (svc0, svc1)[i].backlog())
            wedged_svc = (svc0, svc1)[dead]
            assert wedged_svc.backlog() >= 1
            # inbox load for the wedged replica, routed there by affinity
            inbox_callers = []
            for k in range(2):
                t = threading.Thread(
                    target=call,
                    args=(f"inbox{k}", sessions[dead] + f"turn {k}"))
                t.start()
                inbox_callers.append(t)
            deadline = time.monotonic() + min(budget_s * 0.8, 2.0)
            while time.monotonic() < deadline and wedged_svc.backlog() < 3:
                time.sleep(0.005)
            assert wedged_svc.backlog() >= 3, (
                "inbox tickets did not land on the wedged replica before "
                "detection"
            )
            # the watchdog quarantines on heartbeat age alone, within
            # 2x the stall budget of the wedge
            deadline = time.monotonic() + 3 * budget_s
            quarantined_at = None
            while time.monotonic() < deadline:
                state = rs.health_summary()["replicas"][dead]["state"]
                if state != HEALTH_HEALTHY:
                    quarantined_at = time.monotonic()
                    break
                time.sleep(0.01)
            assert quarantined_at is not None, "watchdog never fired"
            assert quarantined_at - t_wedge <= 2 * budget_s, (
                f"detection took {quarantined_at - t_wedge:.2f}s "
                f"(budget {budget_s}s)"
            )
            t_a.join(timeout=120)
            for t in inbox_callers:
                t.join(timeout=120)
            assert not t_a.is_alive() and not any(
                t.is_alive() for t in inbox_callers), (
                "caller thread hung across the stall"
            )
            # every caller terminated typed; the inbox tickets completed on
            # the SURVIVOR without their callers failing over
            assert len(outcomes) == 3
            for name, out in outcomes.items():
                if isinstance(out, Exception):
                    assert isinstance(out, SentioError), (
                        f"{name}: untyped {type(out).__name__}: {out}")
                else:
                    assert out.finish_reason in ("stop", "length"), (name, out)
            for k in range(2):
                assert isinstance(outcomes[f"inbox{k}"], PagedResult), (
                    f"handed-off ticket inbox{k} did not complete: "
                    f"{outcomes[f'inbox{k}']}"
                )
            stats = rs.stats()
            assert stats["handed_off"] == 2, stats["handed_off"]
            assert stats["stall_quarantines"] == 1
            # only the ADMITTED ticket's caller spent failover budget; the
            # handed-off tickets moved without touching it
            assert stats["failovers"] <= 1, stats["failovers"]
            # the supervisor abandons the wedged engine and rebuilds the
            # slot in place; the abandoned pump is ACCOUNTED even though
            # its service incarnation left rotation
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if rs.health_summary()["status"] == "healthy":
                    break
                time.sleep(0.05)
            summary = rs.health_summary()
            assert summary["status"] == "healthy", summary
            assert summary["replicas"][dead]["rebuilds"] == 1, summary
            assert rs.stats()["pump_leaked"] >= 1, (
                "abandoned wedged pump vanished from stats"
            )
            # pages conserve on the surviving replica (sanitizer checked
            # every tick; this is the end-state audit) and the REBUILT
            # replica serves again
            survivor_stats = rs.stats()["replicas"][1 - dead]
            assert survivor_stats["free_pages"] \
                + survivor_stats.get("prefix_cache_pages", 0) \
                == survivor_stats["total_pages"] - 1, survivor_stats
            rebuilt = rs._services[dead]
            assert rebuilt is not wedged_svc
            ok = rebuilt.generate("rebuilt after stall", max_new_tokens=3,
                                  timeout_s=180)
            assert ok.finish_reason in ("stop", "length")
            ok2 = rs.generate("post stall routed sanity", max_new_tokens=3,
                              timeout_s=120)
            assert ok2.finish_reason in ("stop", "length")
            # the stall was evented for operators
            from sentio_tpu.infra.flight import get_flight_recorder

            events = get_flight_recorder().timeline()
            assert any(e.get("event") == "pump_stall" for e in events)
            assert any(e.get("event") == "inbox_handoff"
                       and e.get("handed_off") == 2 for e in events)
        finally:
            release.set()  # unwedge the abandoned pump so it can exit
            faults.reset()
            rs.close()
        _assert_no_pump_threads()

    def test_process_replica_sigkill_drill(self):
        """ISSUE 13 acceptance drill: one of 2 PROCESS-mode replicas takes
        a real ``SIGKILL`` mid-traffic — no exception raised in any Python
        frame, the worker process is simply gone. The contract:

        * every caller terminates with a TYPED outcome (in-flight RPCs
          against the corpse fail ReplicaUnavailable and fail over);
        * the survivor keeps serving during the outage;
        * the supervisor detects the corpse from the OUTSIDE (broken pipe /
          ``proc.is_alive()``), quarantines, and rebuilds by RESPAWNING the
          process; the respawned worker serves before the test ends;
        * detection and recovery land within budget;
        * zero orphan worker processes at teardown."""
        import dataclasses
        import multiprocessing

        from sentio_tpu.models.llama import LlamaConfig
        from sentio_tpu.models.tokenizer import ByteTokenizer
        from sentio_tpu.runtime.replica import ReplicaSet
        from sentio_tpu.runtime.worker import ProcessReplica, WorkerSpec

        cfg = LlamaConfig.tiny()
        spec = WorkerSpec(factory_kwargs=dict(
            model_config=dataclasses.asdict(cfg),
            engine_kwargs=dict(max_slots=2, page_size=8, max_pages_per_seq=4,
                               steps_per_tick=2),
            service_kwargs=dict(retry_budget=1),
        ))
        tok = ByteTokenizer(cfg.vocab_size)
        p0, p1 = _build_parallel(lambda i: ProcessReplica(
            spec, tok, replica_id=i, build_timeout_s=300.0))
        # pre-compile both workers (concurrently — separate processes) so
        # the drill's traffic exercises the failure machinery instead of
        # waiting out XLA compiles
        _build_parallel(lambda i: [p0, p1][i].generate(
            f"drill warm {i}", max_new_tokens=2, timeout_s=180))
        rs = ReplicaSet(
            [p0, p1],
            probe_interval_s=0.05, quarantine_backoff_s=0.1,
            failover_budget=2, rebuild_drain_s=0.5,
        )
        outcomes: dict[str, object] = {}
        stop_traffic = threading.Event()

        def call_generate(i):
            try:
                outcomes[f"g{i}"] = rs.generate(
                    f"sigkill drill generate {i}", max_new_tokens=8,
                    temperature=0.0, timeout_s=120,
                )
            except Exception as exc:  # noqa: BLE001 — typed errors terminal
                outcomes[f"g{i}"] = exc

        def call_stream(i):
            try:
                outcomes[f"s{i}"] = "".join(rs.generate_stream(
                    f"sigkill drill stream {i}", max_new_tokens=8,
                    temperature=0.0, timeout_s=120,
                ))
            except Exception as exc:  # noqa: BLE001
                outcomes[f"s{i}"] = exc

        try:
            threads = (
                [threading.Thread(target=call_generate, args=(i,))
                 for i in range(5)]
                + [threading.Thread(target=call_stream, args=(i,))
                   for i in range(3)]
            )
            for t in threads:
                t.start()
            # the kill lands while traffic is in flight (workers decode for
            # several ticks at 8 tokens / 2 steps-per-tick)
            time.sleep(0.1)
            t_kill = time.monotonic()
            p1.kill()  # real SIGKILL: no handlers run, no frames unwind
            # detection: the supervisor (or a failing caller) must move the
            # corpse out of HEALTHY from the OUTSIDE
            t_detect = None
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if rs.health_summary()["replicas"][1]["state"] != "HEALTHY":
                    t_detect = time.monotonic()
                    break
                time.sleep(0.01)
            assert t_detect is not None, "corpse never left HEALTHY"
            assert t_detect - t_kill <= 15.0, (
                f"detection took {t_detect - t_kill:.1f}s"
            )
            for t in threads:
                t.join(timeout=180)
            assert not any(t.is_alive() for t in threads), (
                "caller thread hung across the worker SIGKILL"
            )
            # EVERY caller terminated with a typed outcome; the survivor
            # absorbed failed-over load
            assert len(outcomes) == 8
            successes = 0
            for name, out in outcomes.items():
                if isinstance(out, Exception):
                    assert isinstance(out, SentioError), (
                        f"{name}: untyped {type(out).__name__}: {out}"
                    )
                else:
                    assert isinstance(out, (PagedResult, str)), (name, out)
                    if isinstance(out, PagedResult):
                        assert out.finish_reason in ("stop", "length"), (
                            name, out,
                        )
                    successes += 1
            assert successes >= 1, (
                f"survivor never served during the outage: {outcomes}"
            )
            # the supervisor RESPAWNS the dead worker process and the set
            # returns to full health within budget
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if rs.health_summary()["status"] == "healthy":
                    break
                time.sleep(0.05)
            summary = rs.health_summary()
            assert summary["status"] == "healthy", summary
            assert summary["replicas"][1]["rebuilds"] == 1, summary
            rebuilt = rs._services[1]
            assert rebuilt is not p1, "slot was not respawned"
            assert rebuilt.pid != p1.pid, "respawn reused the corpse's pid?"
            ok = rebuilt.generate("respawned replica serves again",
                                  max_new_tokens=3, timeout_s=180)
            assert ok.finish_reason in ("stop", "length")
            ok2 = rs.generate("post sigkill routed sanity", max_new_tokens=3,
                              timeout_s=120)
            assert ok2.finish_reason in ("stop", "length")
        finally:
            stop_traffic.set()
            rs.close()
        # zero orphan worker processes at teardown: close() reaps
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and multiprocessing.active_children():
            time.sleep(0.05)
        assert multiprocessing.active_children() == [], (
            "orphan replica worker processes leaked"
        )
        _assert_no_pump_threads()

    def test_process_replica_stall_drill_reaps_wedged_worker(self):
        """The stall drill against PROCESS-mode replicas: one of 2 workers
        wedges inside a decode tick (an in-worker stall fault armed over the
        RPC fault surface) — nothing raises, nothing returns, the process
        stays alive. The contract:

        * the wedge is detected from the OUTSIDE, by the heartbeat age its
          status frames carry, and the replica leaves HEALTHY within budget;
        * every caller terminates with a typed outcome and the survivor
          serves during the outage;
        * the rebuild reaps the whole wedged process (there is no thread to
          abandon) and respawns it; the new worker serves;
        * zero orphan worker processes at teardown."""
        import dataclasses
        import multiprocessing

        from sentio_tpu.models.llama import LlamaConfig
        from sentio_tpu.models.tokenizer import ByteTokenizer
        from sentio_tpu.runtime.replica import ReplicaSet
        from sentio_tpu.runtime.worker import ProcessReplica, WorkerSpec

        # generous next to a warmed tick on a machine that six test
        # workers share, small next to the test
        budget_s = 8.0
        cfg = LlamaConfig.tiny()
        spec = WorkerSpec(factory_kwargs=dict(
            model_config=dataclasses.asdict(cfg),
            engine_kwargs=dict(max_slots=2, page_size=8, max_pages_per_seq=4,
                               steps_per_tick=2),
            service_kwargs=dict(retry_budget=1,
                                tick_stall_budget_s=budget_s),
        ))
        tok = ByteTokenizer(cfg.vocab_size)
        p0, p1 = _build_parallel(lambda i: ProcessReplica(
            spec, tok, replica_id=i, build_timeout_s=300.0))
        # both workers compile what the drill will ask of them (drill-shaped
        # prompts, one row and two rows an admission), so that no tick of
        # the SURVIVOR spends the stall budget inside a cold compile
        _build_parallel(lambda i: [p0, p1][i % 2].generate(
            f"stall drill generate w{i}", max_new_tokens=8,
            temperature=0.0, timeout_s=180), n=4)
        rs = ReplicaSet(
            [p0, p1],
            probe_interval_s=0.05, quarantine_backoff_s=0.1,
            failover_budget=2, rebuild_drain_s=0.5,
        )
        outcomes: dict[str, object] = {}

        def call(i):
            # caller 0 goes to the victim itself, so the wedge does not
            # hang on how the router breaks a tie between two idle replicas
            target = rs if i else p1
            try:
                outcomes[f"g{i}"] = target.generate(
                    f"stall drill generate {i}", max_new_tokens=8,
                    temperature=0.0, timeout_s=180,
                )
            except Exception as exc:  # noqa: BLE001 — typed errors terminal
                outcomes[f"g{i}"] = exc

        try:
            # the victim's next decode tick blocks far past the test
            p1.inject_fault("paged.step", stall_s=600.0, times=1)
            t_wedge = time.monotonic()
            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            t_detect = None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if rs.health_summary()["replicas"][1]["state"] != "HEALTHY":
                    t_detect = time.monotonic()
                    break
                time.sleep(0.01)
            assert t_detect is not None, "wedged worker never left HEALTHY"
            assert t_detect - t_wedge <= 2 * budget_s + 10.0, (
                f"detection took {t_detect - t_wedge:.1f}s"
            )
            for t in threads:
                t.join(timeout=240)
            assert not any(t.is_alive() for t in threads), (
                "caller thread hung on the wedged worker"
            )
            assert len(outcomes) == 6
            successes = 0
            for name, out in outcomes.items():
                if isinstance(out, Exception):
                    assert isinstance(out, SentioError), (
                        f"{name}: untyped {type(out).__name__}: {out}"
                    )
                else:
                    assert out.finish_reason in ("stop", "length"), (name, out)
                    successes += 1
            assert successes >= 1, (
                f"survivor never served during the outage: {outcomes}"
            )
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if rs.health_summary()["status"] == "healthy":
                    break
                time.sleep(0.05)
            summary = rs.health_summary()
            assert summary["status"] == "healthy", summary
            assert summary["replicas"][1]["rebuilds"] == 1, summary
            rebuilt = rs._services[1]
            assert rebuilt is not p1 and rebuilt.pid != p1.pid, (
                "the wedged worker was not respawned"
            )
            ok = rebuilt.generate("respawned replica serves again",
                                  max_new_tokens=3, timeout_s=180)
            assert ok.finish_reason in ("stop", "length")
        finally:
            rs.close()
        # the wedged process is reaped too, not left sleeping in its stall
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and multiprocessing.active_children():
            time.sleep(0.05)
        assert multiprocessing.active_children() == [], (
            "orphan replica worker processes leaked"
        )
        _assert_no_pump_threads()

    def test_warmup_stall_quarantined_by_budget(self):
        """ISSUE 13 satellite: a wedge DURING warmup. WARMING is
        watchdog-exempt (cold compiles legitimately dwarf any stall
        budget), so pre-budget this hang was only caught by caller
        timeouts — the spawn/rebuild path just sat there. With
        ``WARMUP_BUDGET_S`` the exemption EXPIRES: the watchdog
        quarantines the replica (typed, supervisor-visible) and the
        blocked warmup caller gets the typed abandonment error."""
        from sentio_tpu.runtime.replica import (
            HEALTH_HEALTHY,
            HEALTH_QUARANTINED,
            ReplicaSet,
        )

        eng = ContinuousBatchingEngine(
            max_slots=2, page_size=8, max_pages_per_seq=4, steps_per_tick=2,
        )
        budget_s = 2.0
        svc = PagedGenerationService(eng, tick_stall_budget_s=budget_s,
                                     warmup_budget_s=budget_s)
        rs = ReplicaSet([svc], supervise=False)
        release = threading.Event()
        warm_outcome: list = []
        rule = faults.FaultRule(stall_event=release, stall_s=120.0, times=1)
        faults.arm("paged.step", rule)
        try:
            warmer = threading.Thread(
                target=lambda: warm_outcome.append(
                    _catch(svc.warmup, max_new_tokens=2)),
            )
            warmer.start()
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and rule.stalled == 0:
                time.sleep(0.005)
            assert rule.stalled == 1, "warmup never wedged"
            t_wedge = time.monotonic()
            # inside the budget the stand-down holds: warming is exempt
            rs._supervise_once()
            assert rs.health_summary()["replicas"][0]["state"] \
                == HEALTH_HEALTHY
            # past the budget the exemption expires and the watchdog fires
            deadline = time.monotonic() + 6 * budget_s
            state = HEALTH_HEALTHY
            while time.monotonic() < deadline:
                rs._supervise_once()
                state = rs.health_summary()["replicas"][0]["state"]
                if state != HEALTH_HEALTHY:
                    break
                time.sleep(0.05)
            assert state in (HEALTH_QUARANTINED, "REBUILDING"), (
                "watchdog never fired on the stalled warmup"
            )
            assert time.monotonic() - t_wedge <= 4 * budget_s, (
                "stalled-warmup detection exceeded 2x budget + slack"
            )
            assert rs.health_summary()["replicas"][0].get("reason", "") \
                .startswith("pump stalled"), rs.health_summary()
            # the blocked warmup caller wakes with the TYPED abandonment
            # error instead of hanging out its generate timeouts
            warmer.join(timeout=60)
            assert not warmer.is_alive(), "warmup still hung post-quarantine"
            assert isinstance(warm_outcome[0], ReplicaUnavailable), (
                warm_outcome
            )
            assert rs.stats()["stall_quarantines"] == 1
        finally:
            release.set()  # unwedge the abandoned pump so it can exit
            faults.reset()
            rs.close()
        _assert_no_pump_threads()

    def test_admission_shed_and_deadline_at_submit(self, engine):
        """Typed sheds: a full queue answers 429-style ServiceOverloaded
        with a retry hint; an already-expired deadline is a typed
        DeadlineExceededError. Neither touches the engine."""
        svc = PagedGenerationService(engine, max_queue=0)
        with pytest.raises(ServiceOverloaded) as exc_info:
            svc.generate("cannot even queue", max_new_tokens=2)
        assert exc_info.value.status == 429
        assert exc_info.value.details["retry_after_s"] >= 0
        with pytest.raises(ServiceOverloaded):
            svc.check_admission()  # pre-commit probe sheds identically
        svc2 = PagedGenerationService(engine)
        with pytest.raises(DeadlineExceededError):
            svc2.generate("expired before submit", max_new_tokens=2,
                          deadline_ts=time.perf_counter() - 0.5)
        stats = svc2.stats()
        assert stats["shed"] >= 1
        svc.close()
        svc2.close()

    def test_drain_sheds_new_work_and_finishes_in_flight(self, engine):
        """drain(): in-flight decode completes, concurrent submits shed with
        503/draining, and the service ends closed."""
        svc = PagedGenerationService(engine)
        result: dict = {}

        def call():
            # long enough (24 ticks at 2 steps/tick) that the drain below
            # provably starts while this is mid-decode; the old 150-token
            # budget bought ~40 extra seconds of tiny-model decode without
            # widening any assertion
            result["r"] = svc.generate(
                "long generation that must finish during drain",
                max_new_tokens=48, temperature=0.0, timeout_s=120,
            )

        t = threading.Thread(target=call)
        t.start()
        # let the pump admit it before draining
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and svc.stats()["active_slots"] == 0:
            time.sleep(0.01)
        drain_out: dict = {}

        def drain():
            drain_out.update(svc.drain(deadline_s=60.0))

        d = threading.Thread(target=drain)
        d.start()
        # shed while draining: a submit racing the drain gets a typed 503
        shed = None
        probe_deadline = time.monotonic() + 60
        while time.monotonic() < probe_deadline:
            try:
                svc.generate("late arrival", max_new_tokens=2, timeout_s=30)
            except ServiceOverloaded as exc:
                shed = exc
                break
            except ReplicaUnavailable:
                break  # drain already closed the service — also typed
            time.sleep(0.005)
        t.join(timeout=120)
        d.join(timeout=120)
        assert result["r"].finish_reason in ("stop", "length")
        assert drain_out.get("drained") is True, drain_out
        if shed is not None:
            assert shed.status == 503
        with pytest.raises(ReplicaUnavailable, match="closed"):
            svc.generate("after drain-close")
        _assert_no_pump_threads()


class TestResumableStreams:
    """ISSUE 14 acceptance drills: a stream that already DELIVERED tokens
    survives its replica's death by replay-prefill — the delivered prefix
    re-admits on a survivor as a prior context suffix, decode continues
    from the splice point, and the client sees one uninterrupted stream
    whose output is token-identical to a run that never saw a fault."""

    PROMPT = "resumable stream drill with a reasonably long prompt body"

    def test_midstream_death_resumes_token_exact_thread_mode(self):
        """Injected mid-stream death (thread mode): one of 2 replicas
        fails a decode tick AFTER delivering at least one chunk of a live
        stream. The stream must complete with output byte-identical to the
        no-fault greedy run (zero duplicated, zero missing tokens), emit
        the ``stream_resumed`` flight event, count into stats, and leave
        the survivor's page pool conserved (sanitizer armed throughout)."""
        from sentio_tpu.runtime.replica import ReplicaSet

        e0 = ContinuousBatchingEngine(**_MULTI_TICK)
        e1 = ContinuousBatchingEngine(
            params=e0.params, tokenizer=e0.tokenizer, **_MULTI_TICK)
        svc0 = PagedGenerationService(e0, retry_budget=1)
        svc1 = PagedGenerationService(e1, retry_budget=1)
        svc1.generate("drill warm one", max_new_tokens=2, timeout_s=180)
        # the no-fault reference ALSO warms svc0's radix with the full
        # prompt, so the drill stream deterministically routes to svc0
        # (prefix affinity) — the replica the fault will kill
        expected = svc0.generate(self.PROMPT, max_new_tokens=16,
                                 temperature=0.0, timeout_s=180)
        assert len(expected.tokens) >= 4, "drill needs a multi-chunk answer"
        rs = ReplicaSet([svc0, svc1], supervise=False, failover_budget=1)
        try:
            # every tick sleeps, so the stream cannot outrun the test: the
            # death arms once the client HOLDS a piece (a seeded model's
            # first bytes may be half a UTF-8 sequence, which the stream
            # withholds — tokens the client never saw restart, not resume)
            # and lands on the next tick. The reset succeeds, so this is a
            # pure mid-stream casualty (the service requeues fresh work
            # but can never restart a delivered-token stream itself).
            faults.arm("paged.step", faults.FaultRule(delay_s=0.05))
            stats_out: dict = {}
            stream = rs.generate_stream(
                self.PROMPT, max_new_tokens=16, temperature=0.0,
                timeout_s=120, stats_out=stats_out,
            )
            pieces = [next(stream)]
            faults.arm("paged.step", faults.FaultRule(
                error=RuntimeError("drill: midstream death"), times=1))
            pieces.extend(stream)
            faults.reset()
            # token-exact vs the no-fault run: zero duplicated, zero
            # missing tokens, one uninterrupted stream
            assert "".join(pieces) == expected.text
            assert stats_out.get("resumed") == 1, stats_out
            assert stats_out.get("replayed_tokens", 0) >= 1, stats_out
            assert stats_out.get("tokens") == len(expected.tokens), stats_out
            stats = rs.stats()
            assert stats["stream_resumes"] == 1
            assert stats["resume_replayed_tokens"] >= 1
            assert stats["resume_exhausted"] == 0
            # the resume was evented for operators
            from sentio_tpu.infra.flight import get_flight_recorder

            events = [t for t in get_flight_recorder().timeline()
                      if t.get("event") == "stream_resumed"]
            assert events, "stream_resumed flight event missing"
            assert events[-1]["replica_from"] == 0
            assert events[-1]["replica_to"] == 1
            assert events[-1]["replayed_tokens"] >= 1
            # pages conserve on the survivor (and on the reset victim)
            _assert_pages_conserved(svc1)
            _assert_pages_conserved(svc0)
            # the survivor still serves routed traffic afterwards
            ok = rs.generate("post resume routed sanity", max_new_tokens=3,
                             temperature=0.0, timeout_s=120)
            assert ok.finish_reason in ("stop", "length")
        finally:
            faults.reset()
            rs.close()
        _assert_no_pump_threads()

    def test_midstream_sigkill_resumes_token_exact_process_mode(self):
        """ISSUE 14 process-mode drill: a REAL ``SIGKILL`` lands between
        delivered stream chunks (the ``worker.stream_chunk`` injection
        point, armed in-worker over the RPC fault surface, composes a
        stall — the determinism window — with ``kill_process``). The
        contract:

        * the stream completes token-identical to a no-fault greedy run
          (the resume replays the delivered prefix on the survivor);
        * the dead worker's never-answered SHADOWED tickets hand off to
          the survivor and complete WITHOUT spending caller failover
          budget (``handed_off`` > 0 — thread-mode handoff parity);
        * the supervisor respawns the worker; zero orphans at teardown."""
        import dataclasses
        import multiprocessing

        from sentio_tpu.models.llama import LlamaConfig
        from sentio_tpu.models.tokenizer import ByteTokenizer
        from sentio_tpu.runtime.replica import ReplicaSet
        from sentio_tpu.runtime.worker import ProcessReplica, WorkerSpec

        cfg = LlamaConfig.tiny()
        spec = WorkerSpec(factory_kwargs=dict(
            model_config=dataclasses.asdict(cfg),
            engine_kwargs=dict(_MULTI_TICK),
            service_kwargs=dict(retry_budget=1),
        ))
        tok = ByteTokenizer(cfg.vocab_size)
        p0, p1 = _build_parallel(lambda i: ProcessReplica(
            spec, tok, replica_id=i, build_timeout_s=300.0))
        # no-fault reference from the survivor (seeded inits are identical
        # across workers — pinned by test_worker's parity suite); p0's
        # radix is primed DEEPER than p1's reference insert so prefix
        # affinity deterministically routes the drill stream to p0.
        # Independent workers: both (compile-heavy) warms run concurrently
        ref_out = _build_parallel(lambda i: (
            p1.generate(self.PROMPT, max_new_tokens=16, temperature=0.0,
                        timeout_s=180)
            if i else
            p0.generate(self.PROMPT, max_new_tokens=2, temperature=0.0,
                        timeout_s=180)))
        expected = ref_out[1]
        assert len(expected.tokens) >= 4
        rs = ReplicaSet(
            [p0, p1],
            probe_interval_s=0.05, quarantine_backoff_s=0.1,
            failover_budget=1, rebuild_drain_s=0.5,
        )
        probe_results: dict = {}

        def probe(i):
            try:
                probe_results[i] = p0.generate(
                    f"handoff probe {i}", max_new_tokens=24, timeout_s=120)
            except Exception as exc:  # noqa: BLE001 — asserted below
                probe_results[i] = exc

        try:
            # between delivered chunks: wedge 3s (the window the test uses
            # to queue handoff probes), then a REAL SIGKILL — no handler
            # runs, no frame unwinds
            p0.inject_fault("worker.stream_chunk", stall_s=3.0,
                            kill_process=True, times=1)
            stats_out: dict = {}
            it = rs.generate_stream(self.PROMPT, max_new_tokens=16,
                                    temperature=0.0, timeout_s=120,
                                    stats_out=stats_out)
            pieces = [next(it)]  # chunk 1 delivered; chunk 2 arms the fault
            # inside the stall window: wedge p0's pump so the probes cannot
            # complete before the kill, then queue them (they register in
            # the router-side shadow)
            p0.inject_fault("paged.step", stall_s=30.0, times=1)
            time.sleep(0.1)
            threads = [threading.Thread(target=probe, args=(i,), daemon=True)
                       for i in range(2)]
            for t in threads:
                t.start()
            time.sleep(0.3)
            for piece in it:
                pieces.append(piece)
            # token-exact across a real SIGKILL
            assert "".join(pieces) == expected.text
            assert stats_out.get("resumed") == 1, stats_out
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads), (
                "handoff probe hung across the SIGKILL"
            )
            # the shadowed probes completed on the survivor via handoff —
            # typed results, no failover budget spent
            for i, out in probe_results.items():
                assert isinstance(out, PagedResult), (i, out)
                assert out.finish_reason in ("stop", "length"), (i, out)
                assert out.replica_id == 1, (i, out)
            stats = rs.stats()
            assert stats["handed_off"] >= 2, stats["handed_off"]
            assert stats["stream_resumes"] >= 1
            assert stats["resume_replayed_tokens"] >= 1
            # the supervisor respawns the corpse and the set heals
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if rs.health_summary()["status"] == "healthy":
                    break
                time.sleep(0.05)
            summary = rs.health_summary()
            assert summary["status"] == "healthy", summary
            ok = rs.generate("post sigkill routed sanity", max_new_tokens=3,
                             temperature=0.0, timeout_s=120)
            assert ok.finish_reason in ("stop", "length")
        finally:
            rs.close()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and multiprocessing.active_children():
            time.sleep(0.05)
        assert multiprocessing.active_children() == [], (
            "orphan replica worker processes leaked"
        )
        _assert_no_pump_threads()

    def test_half_open_partition_drill_socket_transport(self):
        """ISSUE 15 acceptance drill: a HALF-OPEN network partition of 1
        of 2 SOCKET-transport workers mid-traffic — router reads from the
        victim stall (no EOF, no error; its process stays alive and keeps
        decoding) while writes still land. The contract:

        * the partition is DETECTED from status-frame staleness alone
          (transport-liveness contract) and the victim is quarantined
          typed within budget;
        * a delivered-token stream in flight RESUMES token-exact on the
          survivor (same machinery as replica death — partitions ride the
          HEALTHY→QUARANTINED path unchanged);
        * the victim's shadowed never-answered tickets hand off to the
          survivor without spending caller failover budget;
        * the partitioned worker re-registers at a HIGHER incarnation
          epoch (heal: same process — its engine and radix survive), and
          every pre-partition frame it sent — buffered status frames AND
          the answers it kept computing for handed-off work — is dropped
          by the epoch fence (stale_frames > 0): a healed worker can
          never resurrect dead tickets or double-deliver stream chunks;
        * zero orphan processes/threads at teardown."""
        import dataclasses
        import multiprocessing

        from sentio_tpu.models.llama import LlamaConfig
        from sentio_tpu.models.tokenizer import ByteTokenizer
        from sentio_tpu.runtime.replica import ReplicaSet, WorkerRegistry
        from sentio_tpu.runtime.worker import ProcessReplica, WorkerSpec

        cfg = LlamaConfig.tiny()
        registry = WorkerRegistry("partition-drill", slots=2)
        spec = WorkerSpec(
            factory_kwargs=dict(
                model_config=dataclasses.asdict(cfg),
                engine_kwargs=dict(_MULTI_TICK),
                service_kwargs=dict(retry_budget=1),
            ),
            auth_token="partition-drill", status_interval_s=0.05,
            reconnect=True, reconnect_backoff_s=0.2,
            router_silence_timeout_s=0.8,
        )
        tok = ByteTokenizer(cfg.vocab_size)
        kw = dict(build_timeout_s=300.0, transport_mode="socket",
                  registry=registry, partition_timeout_s=1.0,
                  ping_interval_s=0.2, heal_grace_s=15.0)
        # fresh collector for the drill: the zero-double-count assertion
        # below is an EQUALITY against the worker's cumulative registry,
        # which needs merge baselines that start at zero
        from sentio_tpu.infra.metrics import (MetricsCollector, get_metrics,
                                              set_metrics)

        old_collector = get_metrics()
        metrics = MetricsCollector()
        set_metrics(metrics)
        p0, p1 = _build_parallel(lambda i: ProcessReplica(
            spec, tok, replica_id=i, **kw))
        old_pid, old_epoch = p0.pid, p0.epoch
        # no-fault greedy reference from the survivor (seeded inits are
        # identical across workers — pinned by the parity suites) and the
        # VICTIM's radix primed so prefix affinity routes the drill
        # stream onto the replica that will be partitioned — concurrent
        # warms, the workers are independent processes
        ref_out = _build_parallel(lambda i: (
            p1.generate(self.PROMPT, max_new_tokens=16, temperature=0.0,
                        timeout_s=180)
            if i else
            p0.generate(self.PROMPT, max_new_tokens=2, temperature=0.0,
                        timeout_s=180)))
        expected = ref_out[1]
        assert len(expected.tokens) >= 4
        rs = ReplicaSet(
            [p0, p1],
            probe_interval_s=0.05, quarantine_backoff_s=0.1,
            failover_budget=1, rebuild_drain_s=0.5,
        )
        release = threading.Event()
        probe_results: dict = {}
        t_state: dict = {"armed": None, "detect": None}

        def probe(i):
            try:
                probe_results[i] = p0.generate(
                    f"partition handoff probe {i}", max_new_tokens=12,
                    timeout_s=120)
            except Exception as exc:  # noqa: BLE001 — asserted below
                probe_results[i] = exc

        def watch_detection():
            while t_state["detect"] is None:
                if t_state["armed"] is not None:
                    state = rs.health_summary()["replicas"][0]["state"]
                    if state != "HEALTHY":
                        t_state["detect"] = time.monotonic()
                        return
                time.sleep(0.01)

        watcher = threading.Thread(target=watch_detection, daemon=True)
        watcher.start()
        try:
            # a PRE-partition telemetry frame must merge at the victim's
            # original epoch — the fence assertions after heal need a
            # baseline that the stale buffer could plausibly double-count
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and \
                    metrics.worker_telemetry_epoch(0) != old_epoch:
                time.sleep(0.05)
            assert metrics.worker_telemetry_epoch(0) == old_epoch, (
                "no pre-partition telemetry frame merged")
            stats_out: dict = {}
            it = rs.generate_stream(self.PROMPT, max_new_tokens=16,
                                    temperature=0.0, timeout_s=120,
                                    stats_out=stats_out)
            pieces = [next(it)]  # ≥1 chunk DELIVERED before the partition
            # half-open partition: the router's reads from p0 wedge (its
            # frames buffer unread); router→worker writes keep succeeding
            faults.arm("transport.recv.r0", faults.FaultRule(
                stall_event=release, stall_s=120.0, times=1))
            t_state["armed"] = time.monotonic()
            # probes launched INTO the partition: their request frames
            # reach the live worker (writes work), its answers never come
            # back (reads stall) — they stay router-side shadowed until
            # the quarantine hands them to the survivor
            threads = [threading.Thread(target=probe, args=(i,),
                                        daemon=True) for i in range(2)]
            for t in threads:
                t.start()
            # the stream blocks at the partition, gets the typed death,
            # and resumes on the survivor — one uninterrupted iterator
            for piece in it:
                pieces.append(piece)
            assert "".join(pieces) == expected.text
            assert stats_out.get("resumed") == 1, stats_out
            assert stats_out.get("replayed_tokens", 0) >= 1, stats_out
            # detection came from staleness, within budget
            watcher.join(timeout=30)
            assert t_state["detect"] is not None, "partition never detected"
            assert t_state["detect"] - t_state["armed"] <= 5.0
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads), (
                "probe hung across the partition")
            for i, out in probe_results.items():
                assert isinstance(out, PagedResult), (i, out)
                assert out.finish_reason in ("stop", "length"), (i, out)
                assert out.replica_id == 1, (i, out)
            stats = rs.stats()
            assert stats["handed_off"] >= 2, stats["handed_off"]
            assert stats["stream_resumes"] >= 1
            # HEAL: the live partitioned worker re-registers at a higher
            # epoch — same process, fresh incarnation
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if rs.health_summary()["status"] == "healthy":
                    break
                time.sleep(0.05)
            summary = rs.health_summary()
            assert summary["status"] == "healthy", summary
            healed = rs._services[0]
            assert healed.epoch > old_epoch, "reconnect must bump the epoch"
            assert healed.pid == old_pid, (
                "expected HEAL (same process re-registered), got a respawn")
            # release the wedged read: the old connection drains its
            # buffered pre-partition frames straight into the epoch fence
            release.set()
            deadline = time.monotonic() + 30
            while registry.stale_frames(0) == 0 and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            assert registry.stale_frames(0) > 0, (
                "pre-partition frames were not stale-dropped")
            # ISSUE 16: telemetry continuity across the heal. The healed
            # incarnation's frames merge (the fence advances to its epoch)
            # and the age gauge snaps back from its partition climb
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and \
                    metrics.worker_telemetry_epoch(0) != healed.epoch:
                time.sleep(0.05)
            assert metrics.worker_telemetry_epoch(0) == healed.epoch, (
                "healed worker's telemetry never merged")
            assert healed.telemetry_age() is not None
            age = metrics.memory.gauges.get("worker_telemetry_age('0',)")
            assert age is not None and age < 10.0, (
                f"telemetry age gauge never recovered: {age}")
            # ZERO double count: the worker process survived the heal, so
            # its cumulative registry is one monotone series — the router's
            # merged total must EQUAL the last accepted cumulative. Any
            # pre-partition frame slipping past the fence would telescope
            # the deltas to MORE than the cumulative. (Retry around the
            # 1 Hz cadence: a frame landing between the two reads moves
            # both sides.)
            for _ in range(20):
                snap = (healed._telemetry or {}).get("series") or {}
                counts = snap.get("histo_count") or {}
                phase_keys = [k for k in counts
                              if k.startswith("tick_phase(")]
                totals_match = bool(phase_keys)
                for key in phase_keys:
                    phase = key[len("tick_phase('"):-len("',)")]
                    merged = metrics.memory.counters.get(
                        f"worker_tick_phase_ticks{('0', phase)}", 0.0)
                    if merged != counts[key]:
                        totals_match = False
                        break
                if totals_match and \
                        (healed._telemetry or {}).get("series") is snap:
                    break
                time.sleep(0.3)
            assert totals_match, (
                "router totals drifted from the worker's cumulative "
                "registry — pre-partition telemetry double-counted")
            # the healed set serves routed traffic
            ok = rs.generate("post partition routed sanity",
                             max_new_tokens=3, temperature=0.0,
                             timeout_s=120)
            assert ok.finish_reason in ("stop", "length")
        finally:
            release.set()
            faults.reset()
            rs.close()
            registry.close()
            set_metrics(old_collector)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and multiprocessing.active_children():
            time.sleep(0.05)
        assert multiprocessing.active_children() == [], (
            "orphan replica worker processes leaked"
        )
        _assert_no_pump_threads()
