"""Continuous-batching service + cross-thread coalescing (runtime/service.py,
parallel/batcher.ThreadBatcher, embedder query coalescing).

The round-1 gap these close: the paged engine and the batcher existed but
nothing in the serving path used them. The bar here: concurrent callers on
worker threads actually SHARE device batches — staggered requests share
decode ticks, concurrent single-query embeds share one padded forward.
"""

import threading
import time

import numpy as np
import pytest

from conftest import CacheFreeGreedy
from sentio_tpu.config import EmbedderConfig
from sentio_tpu.models.llama import LlamaConfig
from sentio_tpu.parallel.batcher import BatcherClosed, ThreadBatcher
from sentio_tpu.runtime.paged import ContinuousBatchingEngine
from sentio_tpu.runtime.service import (
    GenerationTimeout,
    PagedGenerationService,
    ReplicaUnavailable,
)

@pytest.fixture(scope="module")
def oracle():
    return CacheFreeGreedy(LlamaConfig.tiny(), rng_seed=0)


@pytest.fixture()
def service(oracle):
    engine = ContinuousBatchingEngine(
        model_config=oracle.model_config,
        params=oracle.params,
        tokenizer=oracle.tokenizer,
        max_slots=4,
        page_size=16,
        max_pages_per_seq=8,
    )
    svc = PagedGenerationService(engine)
    yield svc
    svc.close()


class TestThreadBatcher:
    def test_batches_concurrent_submits(self):
        calls: list[list[int]] = []

        def process(items):
            calls.append(list(items))
            return [i * 10 for i in items]

        batcher = ThreadBatcher(process, max_size=8, deadline_ms=50.0)
        results = {}

        def worker(i):
            results[i] = batcher.submit(i)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == {i: i * 10 for i in range(6)}
        # 6 items arriving within one 50 ms window must not take 6 batches
        assert batcher.stats.batches < 6
        assert batcher.stats.snapshot()["avg_occupancy"] > 1.0 / 8.0
        batcher.close()

    def test_failing_batch_fails_only_its_callers(self):
        def process(items):
            if "bad" in items:
                raise RuntimeError("boom")
            return [i.upper() for i in items]

        batcher = ThreadBatcher(process, max_size=1, deadline_ms=0.0)
        with pytest.raises(RuntimeError, match="boom"):
            batcher.submit("bad")
        assert batcher.submit("ok") == "OK"  # batcher survived
        batcher.close()

    def test_closed_batcher_raises(self):
        batcher = ThreadBatcher(lambda items: items, max_size=2)
        batcher.close()
        with pytest.raises(BatcherClosed):
            batcher.submit(1)

    def test_wrong_result_count_raises(self):
        batcher = ThreadBatcher(lambda items: [], max_size=1, deadline_ms=0.0)
        with pytest.raises(RuntimeError, match="returned 0 results"):
            batcher.submit("x")
        batcher.close()


class TestPagedGenerationService:
    def test_single_request_matches_engine(self, service, oracle):
        prompt = "service equivalence check"
        want = oracle.generate([prompt], max_new_tokens=12, temperature=0.0)[0]
        got = service.generate(prompt, max_new_tokens=12, temperature=0.0)
        assert got.tokens == want.tokens
        assert got.finish_reason in ("stop", "length")

    def test_int8_engine_service_roundtrip_with_top_k(self, oracle):
        """KV_QUANT=int8 parametrization of the service path under the
        sanitizer: the pump drives a quantized dict-repr pool through
        admit/decode/retire, and per-request top_k rides the ticket into
        the fused tick (traced — no per-k recompile)."""
        engine = ContinuousBatchingEngine(
            model_config=oracle.model_config,
            params=oracle.params,
            tokenizer=oracle.tokenizer,
            max_slots=4,
            page_size=16,
            max_pages_per_seq=8,
            kv_quant="int8",
        )
        svc = PagedGenerationService(engine)
        try:
            want = oracle.generate(
                ["int8 service check"], max_new_tokens=8, temperature=0.0)[0]
            got = svc.generate("int8 service check", max_new_tokens=8,
                               temperature=0.0)
            # greedy int8 usually tracks bf16 on the tiny model; require a
            # valid completion plus first-token agreement (least noise)
            assert got.finish_reason in ("stop", "length")
            if want.tokens and got.tokens:
                assert got.tokens[0] == want.tokens[0]
            hot = svc.generate("sampled int8 request", max_new_tokens=6,
                               temperature=0.8, top_k=4)
            assert hot.finish_reason in ("stop", "length")
            assert engine.stats()["kv_quant"] == "int8"
        finally:
            svc.close()

    def test_staggered_requests_share_decode_ticks(self, service):
        """Request B arrives while A is mid-decode; continuous batching must
        run them in the same fused step (max_active_slots >= 2) and both
        must complete."""
        results = {}

        def call(name, prompt, max_new):
            results[name] = service.generate(prompt, max_new_tokens=max_new, temperature=0.0)

        a = threading.Thread(target=call, args=("a", "first long running request", 64))
        # NB: prompt chosen to not greedy-sample EOS as its very first token
        # (random-init weights) — that would retire B at admission
        b = threading.Thread(target=call, args=("b", "hello world from request two", 8))
        # hold the inbox mutex while both submit threads start: both requests
        # are enqueued before the first admission tick can run, so they must
        # share decode ticks (B would otherwise race A's whole generation)
        with service._mutex:
            a.start()
            b.start()
            time.sleep(0.2)
        a.join(timeout=120)
        b.join(timeout=120)
        assert "a" in results and "b" in results
        stats = service.stats()
        assert stats["completed"] >= 2
        assert stats["max_active_slots"] >= 2, (
            f"requests never shared a decode tick: {stats}"
        )

    def test_many_concurrent_requests(self, service):
        n = 6  # > max_slots=4: forces queueing + slot reuse
        out = {}

        def call(i):
            out[i] = service.generate(f"prompt number {i}", max_new_tokens=6, temperature=0.0)

        threads = [threading.Thread(target=call, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert len(out) == n
        assert all(r.finish_reason in ("stop", "length") for r in out.values())
        # all pages reclaimed after the burst — free, or retained by the
        # radix prefix cache (minus the reserved scratch page)
        s = service.stats()
        assert s["free_pages"] + s.get("prefix_cache_pages", 0) \
            == s["total_pages"] - 1

    def test_tick_failure_fails_waiters_and_recovers(self, oracle):
        """A failing decode tick must (a) fail the in-flight waiters with
        finish_reason='error' and (b) reset the engine so the NEXT request
        works — a transient device error must not poison the pool forever."""
        engine = ContinuousBatchingEngine(
            model_config=oracle.model_config,
            params=oracle.params,
            tokenizer=oracle.tokenizer,
            max_slots=2,
            page_size=16,
            max_pages_per_seq=4,
        )
        svc = PagedGenerationService(engine)
        original_step = engine.step

        def boom():
            raise RuntimeError("injected device failure")

        engine.step = boom
        try:
            failed = svc.generate("doomed request", max_new_tokens=4)
            assert failed.finish_reason == "error"
        finally:
            engine.step = original_step
        # engine was reset by the pump; a new request must succeed
        ok = svc.generate("hello world from request two", max_new_tokens=4)
        assert ok.finish_reason in ("stop", "length")
        s = svc.stats()
        assert s["free_pages"] + s.get("prefix_cache_pages", 0) \
            == s["total_pages"] - 1
        svc.close()

    def test_closed_service_rejects(self, oracle):
        engine = ContinuousBatchingEngine(
            model_config=oracle.model_config,
            params=oracle.params,
            tokenizer=oracle.tokenizer,
            max_slots=2,
            page_size=16,
            max_pages_per_seq=4,
        )
        svc = PagedGenerationService(engine)
        svc.close()
        # typed 503 (ReplicaUnavailable) — closed/broken admissions carry a
        # Retry-After instead of the old untyped RuntimeError → 500
        with pytest.raises(ReplicaUnavailable, match="closed") as exc_info:
            svc.generate("x")
        assert exc_info.value.status == 503
        assert exc_info.value.details["retry_after_s"] > 0


class TestRobustness:
    """Deadline propagation, crash-requeue budget, and drain ordering —
    the request-lifecycle robustness surface over the paged pump."""

    def _engine(self, oracle, **kw):
        kw.setdefault("max_slots", 2)
        kw.setdefault("page_size", 16)
        kw.setdefault("max_pages_per_seq", 8)
        kw.setdefault("steps_per_tick", 1)
        return ContinuousBatchingEngine(
            model_config=oracle.model_config, params=oracle.params,
            tokenizer=oracle.tokenizer, **kw,
        )

    def test_deadline_cancels_mid_decode(self, oracle):
        from sentio_tpu.infra.exceptions import DeadlineExceededError

        svc = PagedGenerationService(self._engine(oracle))
        try:
            with pytest.raises(DeadlineExceededError):
                svc.generate("expire me mid decode", max_new_tokens=400,
                             deadline_s=0.3)
            # the cancelled slot's pages are reclaimed, not stranded
            deadline = time.time() + 30
            while time.time() < deadline:
                s = svc.stats()
                if s["active_slots"] == 0 and s["free_pages"] \
                        + s.get("prefix_cache_pages", 0) == s["total_pages"] - 1:
                    break
                time.sleep(0.05)
            s = svc.stats()
            assert s["active_slots"] == 0, s
            assert s["expired"] >= 1, s
        finally:
            svc.close()

    def test_timeout_completion_race_returns_result(self, oracle):
        """event.wait timing out while the pump completes the very same
        ticket must return the finished result, not raise + cancel it."""
        svc = PagedGenerationService(self._engine(oracle))
        try:
            # warm so the next generate is fast relative to the timeout
            svc.generate("warm the compile path", max_new_tokens=2)
            # a timeout the generation usually BEATS: across repetitions the
            # wait/complete race window is crossed both ways; either way the
            # caller must never see a timeout for work that finished
            for i in range(5):
                try:
                    out = svc.generate(f"race window probe {i}",
                                       max_new_tokens=2, timeout_s=0.05)
                    assert out.finish_reason in ("stop", "length")
                except GenerationTimeout:
                    pass  # genuinely unfinished: acceptable, just not both
        finally:
            svc.close()

    def test_crash_requeue_budget_recovers_single_failure(self, oracle):
        engine = self._engine(oracle)
        svc = PagedGenerationService(engine, retry_budget=1)
        original_step = engine.step
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient device fault")
            return original_step()

        engine.step = flaky
        try:
            out = svc.generate("survives one bad tick", max_new_tokens=4)
            assert out.finish_reason in ("stop", "length")
            stats = svc.stats()
            assert stats["requeued"] == 1, stats
            assert stats["tick_failures"] == 1, stats
        finally:
            engine.step = original_step
            svc.close()

    def test_queue_full_sheds_with_retry_after(self, oracle):
        from sentio_tpu.infra.exceptions import ServiceOverloaded

        svc = PagedGenerationService(self._engine(oracle), max_queue=0)
        try:
            with pytest.raises(ServiceOverloaded) as exc_info:
                svc.generate("no room at the inn", max_new_tokens=2)
            assert exc_info.value.status == 429
            assert "retry_after_s" in exc_info.value.details
            assert svc.stats()["shed"] == 1
        finally:
            svc.close()

    def test_drain_then_close_ordering(self, oracle):
        """drain() must (1) flip to draining, (2) wait out in-flight work,
        (3) close — a submit observed after drain returns must fail closed,
        and the drained flag must be visible in stats while draining."""
        from sentio_tpu.infra.exceptions import ServiceOverloaded

        svc = PagedGenerationService(self._engine(oracle))
        result = {}

        def call():
            result["r"] = svc.generate("drain waits for me", max_new_tokens=100,
                                       temperature=0.0, timeout_s=120)

        t = threading.Thread(target=call)
        t.start()
        deadline = time.time() + 30
        while time.time() < deadline and svc.stats()["active_slots"] == 0:
            time.sleep(0.01)
        out = svc.drain(deadline_s=60.0)
        t.join(timeout=120)
        assert out["drained"] is True
        assert result["r"].finish_reason in ("stop", "length")
        with pytest.raises((ReplicaUnavailable, ServiceOverloaded)):
            svc.generate("too late")

    def test_drain_deadline_bounds_wedged_pump_join(self, oracle):
        """ISSUE 10 satellite: drain() must honor its deadline against a
        pump wedged inside a device dispatch — the final pump join derives
        from the drain deadline's remainder (not the old hardcoded 10s),
        the wedged pump is counted leaked exactly once, and a second
        close() neither re-joins nor double-counts."""
        from sentio_tpu.infra import faults

        svc = PagedGenerationService(self._engine(oracle))
        release = threading.Event()
        rule = faults.FaultRule(stall_event=release, stall_s=60.0, times=1)
        faults.arm("paged.step", rule)
        try:
            result: dict = {}

            def call():
                try:
                    result["r"] = svc.generate("wedge me", max_new_tokens=4,
                                               timeout_s=60)
                except Exception as exc:  # noqa: BLE001
                    result["r"] = exc

            t = threading.Thread(target=call, daemon=True)
            t.start()
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and rule.stalled == 0:
                time.sleep(0.005)
            assert rule.stalled == 1, "pump never wedged"
            t0 = time.monotonic()
            out = svc.drain(deadline_s=1.5)
            elapsed = time.monotonic() - t0
            # deadline honored: drain window + the (deadline-derived) join,
            # nowhere near the old hardcoded 10s join on top
            assert elapsed < 6.0, f"drain took {elapsed:.1f}s against a 1.5s deadline"
            assert out["drained"] is False and out["abandoned"] >= 1
            assert svc.stats()["pump_leaked"] == 1
            # second close: counted and logged once, not re-joined
            t0 = time.monotonic()
            svc.close()
            assert time.monotonic() - t0 < 1.0, "close() re-joined the leaked pump"
            assert svc.stats()["pump_leaked"] == 1
            # unwedge and let the abandoned pump die cleanly (it sees the
            # closed latch, fails its waiters, exits) — the leak count
            # keeps its history
            release.set()
            t.join(timeout=60)
            assert result, "wedged caller never reached a terminal outcome"
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and any(
                th.name == "paged-decode-pump" and th.is_alive()
                for th in threading.enumerate()
            ):
                time.sleep(0.05)
            assert svc.stats()["pump_leaked"] == 1
        finally:
            faults.disarm("paged.step")
            release.set()

    def test_leaked_pump_surfaces_in_stats(self, oracle):
        """A pump that outlives close()'s join shows up as pump_leaked
        instead of being silently dropped."""
        svc = PagedGenerationService(self._engine(oracle))
        release = threading.Event()
        started = threading.Event()

        class StuckPump:
            name = "paged-decode-pump"
            daemon = True

            def join(self, timeout=None):
                started.set()

            def is_alive(self):
                return not release.is_set()

        with svc._mutex:
            svc._pump = StuckPump()
        svc.close()
        assert started.is_set()
        assert svc.stats()["pump_leaked"] == 1
        release.set()


class TestEmbedderCoalescing:
    def test_concurrent_queries_share_batches(self):
        from sentio_tpu.ops.embedder import TpuEmbedder

        emb = TpuEmbedder(
            EmbedderConfig(
                provider="tpu", model_preset="tiny", coalesce=True,
                coalesce_deadline_ms=50.0, coalesce_max=8, cache_size=0,
            )
        )
        # warm the compile so all threads hit a fast path inside the window
        emb.embed_device(["warmup query"])
        texts = [f"coalesced query {i}" for i in range(6)]
        out = {}

        def worker(t):
            out[t] = np.asarray(emb.embed_device([t]))

        threads = [threading.Thread(target=worker, args=(t,)) for t in texts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = emb.get_stats()["coalescer"]
        assert stats["items"] >= 6
        assert stats["batches"] < stats["items"], f"no coalescing happened: {stats}"
        # coalesced vectors must equal the direct batch path
        direct = np.asarray(emb._embed_device_batch(texts))
        for i, t in enumerate(texts):
            np.testing.assert_allclose(out[t][0], direct[i], rtol=2e-2, atol=2e-2)

    def test_multi_text_calls_bypass_coalescer(self):
        from sentio_tpu.ops.embedder import TpuEmbedder

        emb = TpuEmbedder(EmbedderConfig(provider="tpu", model_preset="tiny", coalesce=True))
        out = emb.embed_device(["a b c", "d e f"])
        assert out.shape == (2, emb.dimension)
        assert emb._query_batcher.stats.batches == 0
        emb.close()


class TestCancellation:
    def test_timeout_cancels_engine_request(self, oracle):
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.service import GenerationTimeout, PagedGenerationService

        eng = ContinuousBatchingEngine(
            model_config=oracle.model_config, params=oracle.params,
            tokenizer=oracle.tokenizer, max_slots=2, page_size=16,
            max_pages_per_seq=8, steps_per_tick=1,
        )
        svc = PagedGenerationService(eng)
        try:
            with pytest.raises(GenerationTimeout):
                svc.generate("slow request", max_new_tokens=100, timeout_s=0.05)
            # the pump must reclaim the abandoned slot's pages
            deadline = time.time() + 30
            while time.time() < deadline:
                s = svc.stats()
                if s["free_pages"] + s.get("prefix_cache_pages", 0) \
                        == s["total_pages"] - 1 and s["active_slots"] == 0:
                    break
                time.sleep(0.05)
            s = svc.stats()
            assert s["active_slots"] == 0, s
            assert s["free_pages"] + s.get("prefix_cache_pages", 0) \
                == s["total_pages"] - 1, s
        finally:
            svc.close()

    def test_abandoned_stream_cancels(self, oracle):
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.service import PagedGenerationService

        eng = ContinuousBatchingEngine(
            model_config=oracle.model_config, params=oracle.params,
            tokenizer=oracle.tokenizer, max_slots=2, page_size=16,
            max_pages_per_seq=8, steps_per_tick=1,
        )
        svc = PagedGenerationService(eng)
        try:
            it = svc.generate_stream("stream to abandon", max_new_tokens=200)
            next(it)  # consume a first chunk so decode is mid-flight
            it.close()  # consumer disconnects
            deadline = time.time() + 30
            while time.time() < deadline:
                s = svc.stats()
                if s["active_slots"] == 0 and s["queued_inbox"] == 0:
                    break
                time.sleep(0.05)
            s = svc.stats()
            assert s["active_slots"] == 0, s
            assert s["free_pages"] + s.get("prefix_cache_pages", 0) \
                == s["total_pages"] - 1, s
        finally:
            svc.close()


class TestPipelinedService:
    def test_concurrent_requests_through_depth2_engine(self, oracle):
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.service import PagedGenerationService

        eng = ContinuousBatchingEngine(
            model_config=oracle.model_config, params=oracle.params,
            tokenizer=oracle.tokenizer, max_slots=4, page_size=16,
            max_pages_per_seq=8, steps_per_tick=4, max_tick_steps=8,
            pipeline_depth=2,
        )
        svc = PagedGenerationService(eng)
        try:
            out = {}

            def call(i):
                out[i] = svc.generate(f"pipelined service {i}", max_new_tokens=10,
                                      temperature=0.0)

            threads = [threading.Thread(target=call, args=(i,)) for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert len(out) == 6
            refs = {
                i: oracle.generate([f"pipelined service {i}"],
                                       max_new_tokens=10, temperature=0.0)[0]
                for i in range(6)
            }
            for i in range(6):
                assert out[i].tokens == refs[i].tokens
            s = svc.stats()
            assert s["free_pages"] + s.get("prefix_cache_pages", 0) \
                == s["total_pages"] - 1
        finally:
            svc.close()

    def test_streaming_through_depth2_engine(self, oracle):
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.service import PagedGenerationService

        eng = ContinuousBatchingEngine(
            model_config=oracle.model_config, params=oracle.params,
            tokenizer=oracle.tokenizer, max_slots=2, page_size=16,
            max_pages_per_seq=8, steps_per_tick=4, pipeline_depth=2,
        )
        svc = PagedGenerationService(eng)
        try:
            want = oracle.generate(["stream depth two"], max_new_tokens=12,
                                       temperature=0.0)[0]
            got = "".join(svc.generate_stream("stream depth two",
                                              max_new_tokens=12, temperature=0.0))
            assert got == want.text
        finally:
            svc.close()
