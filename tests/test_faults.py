"""Fault injection: the framework's failure seams are armed with
deterministic fault rules and the degradation ladder must hold — the
pipeline never turns a component failure into a hard error (SURVEY.md §5:
every reference graph node absorbs errors and degrades; here that contract
is actually testable instead of mock-simulated)."""

from __future__ import annotations

import numpy as np
import pytest

from sentio_tpu.config import (
    EmbedderConfig,
    GeneratorConfig,
    RerankConfig,
    Settings,
)
from sentio_tpu.infra import faults
from sentio_tpu.models.document import Document


@pytest.fixture(autouse=True)
def clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture()
def stack(docs):
    """hash-embedder + echo-generator pipeline over the shared doc fixture."""
    from sentio_tpu.graph.factory import GraphConfig, build_basic_graph
    from sentio_tpu.ops.bm25 import BM25Index
    from sentio_tpu.ops.dense_index import TpuDenseIndex
    from sentio_tpu.ops.embedder import get_embedder
    from sentio_tpu.ops.generator import create_generator
    from sentio_tpu.ops.reranker import get_reranker
    from sentio_tpu.ops.retrievers import DenseRetriever, HybridRetriever, SparseRetriever

    settings = Settings(
        embedder=EmbedderConfig(provider="hash", dim=32),
        generator=GeneratorConfig(provider="echo", use_verifier=False),
        rerank=RerankConfig(enabled=True, kind="passthrough"),
    )
    embedder = get_embedder(settings.embedder)
    dense = TpuDenseIndex(dim=32, dtype="float32")
    dense.add(docs, embedder.embed_many([d.text for d in docs]))
    sparse = BM25Index().build(docs)
    retriever = HybridRetriever(
        retrievers=[DenseRetriever(embedder, dense), SparseRetriever(sparse)],
        config=settings.retrieval,
    )
    generator = create_generator(settings=settings)
    graph = build_basic_graph(
        retriever, generator,
        reranker=get_reranker("passthrough", config=settings.rerank),
        config=GraphConfig(settings=settings),
    )
    return graph


def run_graph(graph, query="what does the fox do?"):
    from sentio_tpu.graph.state import create_initial_state

    return graph.invoke(create_initial_state(query, metadata={"mode": "fast"}))


class TestRuleMechanics:
    def test_unarmed_hit_is_noop(self):
        faults.hit("nowhere")  # must not raise

    def test_times_limits_firing(self):
        with faults.inject("p", error=RuntimeError("boom"), times=2) as rule:
            for _ in range(2):
                with pytest.raises(RuntimeError):
                    faults.hit("p")
            faults.hit("p")  # third hit passes
            assert rule.hits == 3 and rule.fired == 2

    def test_skip_passes_the_first_hits_then_fires(self):
        """``skip=N`` arms 'the N+1th dispatch dies' BEFORE the work
        starts — the deterministic mid-stream kill shape (at least one
        delivered chunk, then death), no consumer-timing race."""
        with faults.inject("p", error=RuntimeError("late boom"),
                           times=1, skip=2) as rule:
            faults.hit("p")  # skipped
            faults.hit("p")  # skipped
            with pytest.raises(RuntimeError):
                faults.hit("p")
            faults.hit("p")  # times=1 exhausted
            assert rule.hits == 4 and rule.fired == 1

    def test_probability_is_seed_deterministic(self):
        def count(seed):
            n = 0
            with faults.inject("p", error=ValueError("x"), probability=0.5, seed=seed):
                for _ in range(50):
                    try:
                        faults.hit("p")
                    except ValueError:
                        n += 1
            return n

        assert count(7) == count(7)
        assert 10 < count(7) < 40

    def test_delay_only(self):
        import time

        with faults.inject("p", delay_s=0.05):
            t0 = time.perf_counter()
            faults.hit("p")
            assert time.perf_counter() - t0 >= 0.05

    def test_context_exit_disarms(self):
        with faults.inject("p", error=RuntimeError("x")):
            pass
        faults.hit("p")
        assert faults.active_rules() == {}


class TestDegradationLadder:
    def test_dense_leg_down_hybrid_still_answers(self, stack):
        with faults.inject("retriever.dense", error=TimeoutError("device lost")):
            state = run_graph(stack)
        assert state["metadata"]["num_retrieved"] > 0  # sparse leg carried it
        assert state["response"]

    def test_both_legs_down_soft_fails_to_empty(self, stack):
        with faults.inject("retriever.dense", error=TimeoutError("x")), \
             faults.inject("retriever.sparse", error=TimeoutError("y")):
            state = run_graph(stack)
        # retrieval failed entirely; the graph absorbs it (retrieve_error
        # metadata) and the pipeline still produces a response rather than
        # erroring the request
        assert not state.get("retrieved_documents")
        assert "retrieval_error" in state["metadata"]
        assert state["response"] is not None

    def test_reranker_down_keeps_original_order(self, docs):
        from sentio_tpu.ops.reranker import CrossEncoderReranker
        from sentio_tpu.models.transformer import EncoderConfig

        rr = CrossEncoderReranker(RerankConfig(batch_size=8),
                                  model_config=EncoderConfig.tiny())
        with faults.inject("reranker.score", error=RuntimeError("kernel oom")):
            result = rr.rerank("query", docs, top_k=3)
        assert [d.id for d in result.documents] == [d.id for d in docs[:3]]

    def test_embedder_batch_fault_then_recovers(self):
        from sentio_tpu.ops.embedder import get_embedder

        embedder = get_embedder(EmbedderConfig(provider="hash", dim=32))
        with faults.inject("embedder.batch",
                           error=RuntimeError("embed kernel oom"),
                           times=1) as rule:
            with pytest.raises(RuntimeError):
                embedder.embed_many(["hello"])
            out = embedder.embed_many(["hello"])  # recovered
        assert rule.fired == 1
        assert out.shape == (1, 32)

    def test_decode_tick_fault_exhausts_then_recovers(self):
        from sentio_tpu.models.llama import LlamaConfig
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine

        engine = ContinuousBatchingEngine(
            model_config=LlamaConfig.tiny(), max_slots=2, page_size=16,
            max_pages_per_seq=4,
        )
        with faults.inject("paged.step", error=TimeoutError("deadline"), times=1):
            with pytest.raises(TimeoutError):
                engine.run_all(["hello"], max_new_tokens=4)
            engine.reset()
            out = engine.run_all(["hello"], max_new_tokens=4)  # recovered
        assert len(out) == 1


class TestStallFaults:
    """The hang fault class (ISSUE 10): a stall rule blocks INSIDE the
    injection point — for a bounded duration, or until the test releases
    an event — and composes with raise. This is how chaos wedges a pump
    exactly like a hung device dispatch (nothing raises, nothing returns)."""

    def test_stall_duration_bounded_by_budget(self):
        import time

        with faults.inject("p", stall_s=0.15) as rule:
            t0 = time.perf_counter()
            faults.hit("p")
            dt = time.perf_counter() - t0
        assert dt >= 0.15
        assert rule.stalled == 1

    def test_stall_event_released_mid_test_at_paged_step(self):
        """A pump-shaped thread wedges at ``paged.step`` until the test
        sets the release event; the stall_s cap bounds the worst case."""
        import threading
        import time

        release = threading.Event()
        unwedged = threading.Event()

        def pump():
            faults.hit("paged.step")
            unwedged.set()

        with faults.inject("paged.step", stall_event=release, stall_s=30.0,
                           times=1) as rule:
            t = threading.Thread(target=pump, daemon=True)
            t.start()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and rule.stalled == 0:
                time.sleep(0.005)
            assert rule.stalled == 1, "pump never entered the stall"
            assert not unwedged.is_set(), "stall did not actually block"
            release.set()
            t.join(timeout=5)
            assert unwedged.is_set(), "release did not free the stalled hit"
            # times=1: a second hit passes straight through
            faults.hit("paged.step")
            assert rule.stalled == 1

    def test_stall_at_engine_reset(self):
        """``engine.reset`` — the crash-containment path itself — can be
        wedged: the reset blocks for the stall duration, then completes
        normally (stall, unlike raise, does not fail the reset)."""
        import time

        from sentio_tpu.runtime.paged import ContinuousBatchingEngine

        engine = ContinuousBatchingEngine(
            max_slots=2, page_size=8, max_pages_per_seq=4,
        )
        with faults.inject("engine.reset", stall_s=0.1, times=1) as rule:
            t0 = time.perf_counter()
            engine.reset()
            assert time.perf_counter() - t0 >= 0.1
        assert rule.stalled == 1
        assert engine.allocator.free_pages == engine.allocator.num_pages - 1

    def test_stall_then_raise_composition(self):
        """stall + error on one rule: the hit blocks first, THEN raises —
        a dispatch that hangs and then dies, the worst-case compound."""
        import time

        with faults.inject("p", stall_s=0.1,
                           error=RuntimeError("died after the hang")) as rule:
            t0 = time.perf_counter()
            with pytest.raises(RuntimeError, match="died after the hang"):
                faults.hit("p")
            assert time.perf_counter() - t0 >= 0.1
        assert rule.stalled == 1 and rule.fired == 1

    def test_unfired_rule_never_stalls(self):
        import time

        with faults.inject("p", stall_s=5.0, times=0):
            t0 = time.perf_counter()
            faults.hit("p")
            assert time.perf_counter() - t0 < 1.0


class TestSupervisorFaultPoints:
    """The replica-supervision seams (ISSUE 8): ``engine.reset`` lets chaos
    force the crash-containment reset itself to fail (the path that latches
    a service broken), and ``replica.rebuild`` lets chaos exercise
    rebuild-fails-then-succeeds with the supervisor's backoff."""

    def test_engine_reset_fault_point_fires_then_clears(self):
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine

        engine = ContinuousBatchingEngine(
            max_slots=2, page_size=8, max_pages_per_seq=4,
        )
        with faults.inject("engine.reset",
                           error=RuntimeError("reset denied"),
                           times=1) as rule:
            with pytest.raises(RuntimeError, match="reset denied"):
                engine.reset()
            engine.reset()  # second attempt proceeds normally
        assert rule.hits == 2 and rule.fired == 1
        # the reset actually rebuilt the decode state
        assert engine.allocator.free_pages == engine.allocator.num_pages - 1

    def test_replica_rebuild_fails_then_succeeds(self):
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.replica import (
            HEALTH_HEALTHY,
            HEALTH_QUARANTINED,
            ReplicaSet,
        )
        from sentio_tpu.runtime.service import PagedGenerationService

        engine = ContinuousBatchingEngine(
            max_slots=2, page_size=8, max_pages_per_seq=4, steps_per_tick=2,
        )
        svc = PagedGenerationService(engine)
        rs = ReplicaSet([svc], supervise=False, quarantine_backoff_s=0.0)
        try:
            rs._quarantine(0, "seeded by test")
            with faults.inject("replica.rebuild",
                               error=RuntimeError("no rebuild capacity"),
                               times=1) as rule:
                assert rs._rebuild(0) is False
                assert rule.fired == 1
            replica = rs.health_summary()["replicas"][0]
            assert replica["state"] == HEALTH_QUARANTINED
            assert "rebuild failed" in replica["reason"]
            assert replica["rebuilds"] == 0
            # backoff 0 → immediately due again; unarmed point now passes
            # and the replica re-enters rotation on a working fresh engine
            assert rs._rebuild(0) is True
            replica = rs.health_summary()["replicas"][0]
            assert replica["state"] == HEALTH_HEALTHY
            assert replica["rebuilds"] == 1
            ok = rs.generate("post rebuild request", max_new_tokens=2,
                             temperature=0.0, timeout_s=180)
            assert ok.finish_reason in ("stop", "length")
        finally:
            rs.close()
