"""Runtime sanitizer (SENTIO_SANITIZE=1) — the dynamic half of sentio lint.

Verifies the five checks the sanitizer provides: lock ownership recording
on annotated locks, the single-driver-thread contract on engine entry
points (a cross-thread engine call raises), per-tick engine invariants (an
injected page leak and an injected radix refcount leak are both caught on
the next tick, not at pool exhaustion later), runtime lock-order tracking
(the first acquisition reversing an observed order raises before taking
the lock), and Eraser-style lockset enforcement on ``guard_locksets``
classes (a second thread writing a guarded attribute without the lock
empties the candidate lockset and raises).
"""

import threading

import pytest

from sentio_tpu.analysis.sanitizer import (
    OwnedLock,
    SanitizerError,
    _reset_lock_order,
    assert_held,
    check_engine_invariants,
    enabled,
    guard_locksets,
    held_lock_names,
    make_lock,
)

# conftest enables SENTIO_SANITIZE=1 for this module; every engine below is
# constructed with the sanitizer armed


def _engine(**kw):
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages_per_seq", 4)
    kw.setdefault("steps_per_tick", 4)
    return ContinuousBatchingEngine(**kw)


PROMPT = "a reasonably long prompt that spans multiple cache pages easily"


class TestLockOwnership:
    def test_make_lock_returns_owned_lock(self):
        assert enabled()
        lock = make_lock("test")
        assert isinstance(lock, OwnedLock)

    def test_assert_held_raises_when_not_held(self):
        lock = make_lock("test")
        with pytest.raises(SanitizerError, match="not held"):
            assert_held(lock)

    def test_assert_held_passes_inside_with(self):
        lock = make_lock("test")
        with lock:
            assert_held(lock)
        with pytest.raises(SanitizerError):
            assert_held(lock)

    def test_plain_lock_no_ops(self, monkeypatch):
        monkeypatch.delenv("SENTIO_SANITIZE")
        lock = make_lock("test")
        assert not isinstance(lock, OwnedLock)
        assert_held(lock)  # no-op, never raises

    def test_held_by_other_thread_raises(self):
        lock = make_lock("test")
        lock.acquire()
        err: list = []

        def other():
            try:
                assert_held(lock)
            except SanitizerError as exc:
                err.append(exc)

        t = threading.Thread(target=other)
        t.start()
        t.join()
        lock.release()
        assert err, "assert_held must reject a non-owner thread"


class TestThreadGuard:
    def test_cross_thread_step_raises(self):
        eng = _engine()
        eng.submit(PROMPT, max_new_tokens=4)  # binds this thread as driver
        caught: list = []

        def intruder():
            try:
                eng.step()
            except SanitizerError as exc:
                caught.append(exc)

        t = threading.Thread(target=intruder, name="intruder")
        t.start()
        t.join()
        assert caught, "cross-thread engine.step must raise under sanitize"
        assert "single-threaded" in str(caught[0])
        # the rightful driver still works
        while eng.has_work:
            eng.step()

    def test_cross_thread_submit_raises(self):
        eng = _engine()
        eng.step()  # bind
        caught: list = []

        def intruder():
            try:
                eng.submit("hi", max_new_tokens=2)
            except SanitizerError as exc:
                caught.append(exc)

        t = threading.Thread(target=intruder)
        t.start()
        t.join()
        assert caught

    def test_ownership_migrates_from_dead_thread(self):
        eng = _engine()

        def first_driver():
            eng.submit(PROMPT, max_new_tokens=2)

        t = threading.Thread(target=first_driver)
        t.start()
        t.join()
        # the binding thread is dead: the next driver inherits cleanly
        while eng.has_work:
            eng.step()


class TestCrossReplicaOwnership:
    """Multi-replica tier: pump-thread ownership is PER REPLICA — each
    replica's pump owns only its own engine, and a thread that legitimately
    drives replica 0 is still an intruder on replica 1."""

    def test_cross_replica_mutation_raises(self):
        e0 = _engine()
        e1 = _engine()
        ready = threading.Event()
        release = threading.Event()

        def replica_one_pump():
            e1.submit("replica one work", max_new_tokens=2)  # binds e1
            ready.set()
            release.wait(timeout=60)

        t = threading.Thread(target=replica_one_pump, name="r1-pump")
        t.start()
        ready.wait(timeout=60)
        # this thread legitimately drives replica 0...
        e0.submit("replica zero work", max_new_tokens=2)
        caught: list = []
        try:
            # ...but replica 1 is owned by its own (live) pump: a
            # cross-replica mutation must raise, not silently interleave
            try:
                e1.step()
            except SanitizerError as exc:
                caught.append(exc)
        finally:
            release.set()
            t.join(timeout=60)
        assert caught, "cross-replica engine.step must raise under sanitize"
        assert "single-threaded" in str(caught[0])
        # replica 0 was never poisoned: its rightful driver finishes
        while e0.has_work:
            e0.step()
        # replica 1's owner died: ownership migrates and IT finishes too
        while e1.has_work:
            e1.step()

    def test_replica_set_names_guards_per_replica(self):
        from sentio_tpu.runtime.replica import ReplicaSet
        from sentio_tpu.runtime.service import PagedGenerationService

        e0 = _engine()
        e1 = _engine()
        rs = ReplicaSet([PagedGenerationService(e0),
                         PagedGenerationService(e1)])
        try:
            assert "[r0]" in e0._san.name and "[r1]" in e1._san.name
        finally:
            rs.close()


class TestEngineInvariants:
    # the conservation/refcount checks are representation-blind, but the
    # quantized dict pool must ride through the same per-tick verification
    # — every injected-corruption scenario runs at both pool reprs
    @pytest.mark.parametrize("kv_quant", ["none", "int8"])
    def test_clean_run_passes(self, kv_quant):
        eng = _engine(kv_quant=kv_quant)
        results = eng.run_all([PROMPT, "short one"], max_new_tokens=6)
        assert len(results) == 2
        check_engine_invariants(eng)  # idle state is also conserved

    @pytest.mark.parametrize("kv_quant", ["none", "int8"])
    def test_injected_page_leak_caught(self, kv_quant):
        eng = _engine(kv_quant=kv_quant)
        eng.run_all([PROMPT], max_new_tokens=4)
        # simulate a lost page: it vanishes from the free list without any
        # owner — the very next tick must fail loudly
        leaked = eng.allocator._free.pop()
        assert leaked > 0
        eng.submit("short one", max_new_tokens=2)
        with pytest.raises(SanitizerError, match="leaked"):
            while eng.has_work:
                eng.step()

    def test_injected_double_own_caught(self):
        eng = _engine()
        eng.run_all([PROMPT], max_new_tokens=4)
        # a double-free: the free list gains a second copy of a page id
        # (inserted at the head — allocation pops the tail, so the duplicate
        # survives to the next tick's check instead of being immediately
        # handed out and retired away)
        eng.allocator._free.insert(0, eng.allocator._free[0])
        with pytest.raises(SanitizerError, match="duplicates"):
            eng.submit("short one", max_new_tokens=2)
            while eng.has_work:
                eng.step()

    @pytest.mark.parametrize("kv_quant", ["none", "int8"])
    def test_injected_refcount_leak_caught(self, kv_quant):
        eng = _engine(kv_quant=kv_quant)
        eng.run_all([PROMPT], max_new_tokens=4)
        radix = eng._radix
        assert radix is not None and not radix.empty
        # a pin with no live slot behind it (the bug class: a retire path
        # that forgets unlock) — caught on the next tick
        node = next(iter(radix.root.children.values()))
        radix.lock(node)
        eng.submit("short one", max_new_tokens=2)
        with pytest.raises(SanitizerError, match="refcount"):
            while eng.has_work:
                eng.step()

    def test_disabled_engine_skips_checks(self, monkeypatch):
        monkeypatch.delenv("SENTIO_SANITIZE")
        eng = _engine()
        assert eng._san is None
        eng.run_all([PROMPT], max_new_tokens=2)
        # injected corruption goes UNnoticed without the sanitizer — the
        # checks are genuinely opt-in
        eng.allocator._free.pop()
        eng.submit("short one", max_new_tokens=2)
        while eng.has_work:
            eng.step()


class TestQuantPoolRepr:
    """The sanitizer's pool-representation half: the ``{"q","s"}`` dict
    pool is held to per-tick metadata invariants (int8 payload, bf16 scales
    mirroring the payload shape), so a refactor that silently densifies or
    drops the scale tree fails the tick that did it."""

    def test_clean_int8_tick_passes(self):
        eng = _engine(kv_quant="int8")
        eng.run_all([PROMPT], max_new_tokens=4)
        check_engine_invariants(eng)

    def test_densified_pool_caught(self):
        eng = _engine(kv_quant="int8")
        eng.run_all([PROMPT], max_new_tokens=2)
        eng.pool.k = eng.pool.k["q"]  # the dense-copy regression
        with pytest.raises(SanitizerError, match="pytree"):
            check_engine_invariants(eng)

    def test_scale_dtype_drift_caught(self):
        import jax.numpy as jnp

        eng = _engine(kv_quant="int8")
        eng.run_all([PROMPT], max_new_tokens=2)
        eng.pool.k = dict(eng.pool.k)
        eng.pool.k["s"] = eng.pool.k["s"].astype(jnp.float32)
        with pytest.raises(SanitizerError, match="dtypes"):
            check_engine_invariants(eng)

    def test_scale_shape_mismatch_caught(self):
        eng = _engine(kv_quant="int8")
        eng.run_all([PROMPT], max_new_tokens=2)
        eng.pool.v = dict(eng.pool.v)
        eng.pool.v["s"] = eng.pool.v["s"][:, :-1]
        with pytest.raises(SanitizerError, match="scale shape"):
            check_engine_invariants(eng)

    def test_dict_pool_on_unquantized_engine_caught(self):
        eng = _engine()
        eng.run_all([PROMPT], max_new_tokens=2)
        from sentio_tpu.runtime.paged import quantize_kv

        q, s = quantize_kv(eng.pool.k)
        eng.pool.k = {"q": q, "s": s.swapaxes(-1, -2)}
        with pytest.raises(SanitizerError, match="unquantized"):
            check_engine_invariants(eng)


class TestLockOrderRuntime:
    """Per-thread acquisition stacks + the global order-edge set: the
    dynamic twin of the static ``lock-order-inversion`` rule."""

    def test_inversion_raises_and_leaves_nothing_held(self):
        _reset_lock_order()
        a, b = make_lock("tsan-A"), make_lock("tsan-B")
        with a:
            with b:
                pass  # establishes A -> B
        with b:
            with pytest.raises(SanitizerError, match="inversion"):
                with a:
                    pass
            # the check runs BEFORE the underlying acquire: the raise
            # left the reversed lock untaken, so nothing is wedged
            assert not a.locked()
        assert held_lock_names() == frozenset()

    def test_inversion_caught_across_threads(self):
        _reset_lock_order()
        a, b = make_lock("tsan-X"), make_lock("tsan-Y")

        def establishes():
            with a:
                with b:
                    pass

        t = threading.Thread(target=establishes, name="edge-setter")
        t.start()
        t.join()
        # the edge set is process-global: THIS thread's reversal trips it
        with b:
            with pytest.raises(SanitizerError, match="pick one global order"):
                with a:
                    pass

    def test_consistent_order_never_raises(self):
        _reset_lock_order()
        a, b = make_lock("tsan-C"), make_lock("tsan-D")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert held_lock_names() == frozenset()

    def test_reentrant_blocking_acquire_raises(self):
        lock = make_lock("tsan-E")
        with lock:
            with pytest.raises(SanitizerError, match="self-deadlock"):
                lock.acquire()

    def test_same_name_nesting_is_not_an_inversion(self):
        _reset_lock_order()
        # two instances sharing one class-qualified name: order between
        # them is an instance hierarchy, which name-granular edges cannot
        # judge — both nestings must pass (mirrors the static rule)
        a1, a2 = make_lock("tsan-F"), make_lock("tsan-F")
        with a1:
            with a2:
                pass
        with a2:
            with a1:
                pass


@guard_locksets
class _Seeded:
    """Lockset-checker fixture: one annotated counter, one locked and one
    unlocked write path."""

    def __init__(self):
        self._mu = make_lock("_Seeded._mu")
        self._count = 0  # guarded-by: _mu

    def locked_bump(self):
        with self._mu:
            self._count += 1

    def unlocked_bump(self):
        self._count += 1


class TestLocksets:
    def test_cross_thread_unlocked_mutation_raises(self):
        s = _Seeded()
        s.unlocked_bump()  # first thread: exclusive phase, anything goes
        caught: list = []

        def second_thread():
            try:
                s.unlocked_bump()
            except SanitizerError as exc:
                caught.append(exc)

        t = threading.Thread(target=second_thread, name="racer")
        t.start()
        t.join()
        assert caught, "second-thread unlocked write must empty the lockset"
        assert "_Seeded._count" in str(caught[0])
        assert "_mu" in str(caught[0])

    def test_lockset_empties_on_late_unlocked_write(self):
        # disciplined shared phase first (candidates = {_mu}), then the
        # owning thread itself regresses to an unlocked write: the
        # intersection with its empty held set raises — the checker is
        # not a second-thread-only tripwire
        s = _Seeded()
        t = threading.Thread(target=s.locked_bump, name="sharer")
        t.start()
        t.join()
        s.locked_bump()
        with pytest.raises(SanitizerError, match="candidate lockset"):
            s.unlocked_bump()

    def test_locked_discipline_never_raises(self):
        s = _Seeded()
        threads = [
            threading.Thread(target=s.locked_bump, name=f"bumper-{i}")
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        s.locked_bump()
        assert s._count == 5

    def test_disabled_construction_is_unarmed(self, monkeypatch):
        monkeypatch.delenv("SENTIO_SANITIZE")
        s = _Seeded()
        assert "_san_lockset_state" not in s.__dict__
        # unlocked cross-thread writes go unnoticed: genuinely opt-in
        t = threading.Thread(target=s.unlocked_bump)
        s.unlocked_bump()
        t.start()
        t.join()
        assert s._count == 2

    def test_serving_classes_are_armed(self):
        """The chaos-drill-facing classes carry the decorator and parse
        their own annotations into a non-empty spec."""
        from sentio_tpu.infra.flight import FlightRecorder
        from sentio_tpu.infra.metrics import InMemoryMetrics

        fr = FlightRecorder()
        assert "_san_lockset_state" in fr.__dict__
        assert "_tick_seq" in fr.__dict__["_san_lockset_state"].spec
        m = InMemoryMetrics()
        assert "counters" in m.__dict__["_san_lockset_state"].spec


class TestServiceUnderSanitizer:
    def test_pump_handoff_and_locks(self):
        """The serving pump rebinding engine ownership + OwnedLock on
        _mutex: a full generate round trip under the sanitizer."""
        from sentio_tpu.runtime.service import PagedGenerationService

        eng = _engine()
        svc = PagedGenerationService(eng)
        assert isinstance(svc._mutex, OwnedLock)
        out = svc.generate(PROMPT, max_new_tokens=4)
        assert out.finish_reason in ("stop", "length")
        # pump bursts rebind: a second generation after the first pump died
        out2 = svc.generate("another prompt entirely", max_new_tokens=4)
        assert out2.finish_reason in ("stop", "length")
        svc.close()
