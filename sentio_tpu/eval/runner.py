"""Eval orchestration: the five BASELINE.json configs + the measured baseline.

This is the wiring that turns the eval subsystem into published numbers
(BASELINE.json's ``configs`` matrix, EVAL.json): build the retrieval-QA
bundle, stand up the framework components once, run each config through
:func:`sentio_tpu.eval.harness.run_queries`, and measure the
reference-architecture loopback baseline (:mod:`sentio_tpu.eval.baseline`).

Config map (BASELINE.json → this framework):

1. ``sparse_api``   — BM25-only retrieve + LLM over a REAL loopback HTTP hop
                      (the OpenAI-compatible provider against the mock model
                      server) — the reference's cheapest shape.
2. ``dense``        — on-device bi-encoder embed → in-HBM exact top-k.
3. ``hybrid_rerank``— concurrent dense+sparse legs, RRF fusion, on-device
                      cross-encoder rerank.
4. ``full_paged``   — the whole graph (retrieve → rerank → select → generate
                      → verify) with generation through the continuous-
                      batching paged-KV service; sequential callers.
5. ``batched``      — same graph, N concurrent callers sharing the paged
                      decode batch (concurrency IS the batch).

Run via ``python -m sentio_tpu.cli eval``.
"""

from __future__ import annotations

import sys
import time
from typing import Optional

from sentio_tpu.eval.dataset import EvalBundle, build_bundle
from sentio_tpu.eval.harness import EvalResult, run_queries


def _log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def _build_models(scale: str):
    from sentio_tpu.models.llama import LlamaConfig
    from sentio_tpu.models.transformer import EncoderConfig

    if scale == "tiny":
        return EncoderConfig.tiny(), LlamaConfig.tiny()
    # "bench": MXU-friendly mini models (dims multiples of 128, bf16)
    enc = EncoderConfig(
        vocab_size=512, dim=512, n_layers=8, n_heads=8, mlp_dim=2048, max_len=512
    )
    llm = LlamaConfig(
        vocab_size=512, dim=512, n_layers=12, n_heads=8, n_kv_heads=4,
        mlp_dim=1536, max_len=2048, rope_theta=500_000.0,
    )
    return enc, llm


def run_eval(
    scale: str = "bench",
    n_docs: int = 1024,
    n_queries: int = 64,
    concurrency: int = 8,
    new_tokens: int = 48,
    verifier_tokens: int = 64,
    rtt_ms: float = 0.0,
    seed: int = 0,
    skip_baseline: bool = False,
    configs: Optional[set] = None,
    encoder_checkpoint: str = "",
    kv_quant: str = "none",
    verify_mode: str = "sync",
    verify_threshold: Optional[float] = None,
) -> dict:
    """Run the eval matrix; returns the EVAL.json payload (pure dict)."""
    import jax

    from sentio_tpu.config import EmbedderConfig, RerankConfig, Settings
    from sentio_tpu.graph.factory import GraphConfig, build_basic_graph
    from sentio_tpu.graph.state import create_initial_state
    from sentio_tpu.ops.bm25 import BM25Index
    from sentio_tpu.ops.dense_index import TpuDenseIndex
    from sentio_tpu.ops.embedder import TpuEmbedder
    from sentio_tpu.ops.generator import LLMGenerator, OpenAIProvider, TpuProvider
    from sentio_tpu.ops.reranker import CrossEncoderReranker
    from sentio_tpu.ops.retrievers import (
        DenseRetriever, HybridRetriever, SparseRetriever,
    )
    from sentio_tpu.ops.verifier import AnswerVerifier
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.replica import ReplicaSet
    from sentio_tpu.runtime.service import PagedGenerationService

    t_start = time.perf_counter()
    known = {"sparse_api", "dense", "hybrid_rerank", "full_paged", "batched"}
    want = set(configs) if configs else set(known)
    unknown = want - known
    if unknown:
        raise ValueError(f"unknown eval configs {sorted(unknown)}; known: {sorted(known)}")
    enc_cfg, llm_cfg = _build_models(scale)
    devices = jax.devices()
    _log(f"eval: {len(devices)} x {devices[0].platform} ({devices[0].device_kind}); "
         f"scale={scale} docs={n_docs} queries={n_queries} concurrency={concurrency}")

    bundle: EvalBundle = build_bundle(n_docs=n_docs, n_queries=n_queries, seed=seed)
    queries = bundle.queries

    settings = Settings()
    settings.generator.max_new_tokens = new_tokens
    # confidence-gated verification (ops/confidence.py): the verify quality
    # gate (tests/test_eval.py::TestVerifyGate) runs gated vs sync over the
    # SAME bundle/params and compares per-query verdicts
    settings.generator.verify_mode = verify_mode
    if verify_threshold is not None:
        settings.generator.verify_confidence_threshold = verify_threshold
    # the verifier emits a short JSON verdict; with random-init weights it
    # never hits EOS, so an uncapped budget would decode to the full default
    settings.generator.verifier_max_tokens = verifier_tokens
    # ByteTokenizer ≈ 1 token/char while the selector budget assumes 4
    # chars/token — size the doc budget so the ASSEMBLED prompt (docs +
    # instruction + question) fits the model window with generation headroom,
    # instead of letting paged admission truncate the prompt tail silently
    settings.generator.context_token_budget = max(
        (llm_cfg.max_len - new_tokens - 256) // 4, 32
    )
    settings.retrieval.top_k = 10
    # recall@10 must be measured over 10 documents end to end — the serving
    # default (rerank keeps 5) would silently turn the metric into recall@5
    settings.rerank.top_k = 10

    # ------------------------------------- shared stack (built only if used)
    needs_dense = bool(want & {"dense", "hybrid_rerank", "full_paged", "batched"})
    needs_sparse = bool(want & {"sparse_api", "hybrid_rerank", "full_paged", "batched"})
    rows: list[dict] = []
    extras: dict = {}

    embedder = dense_index = None
    if needs_dense:
        # trained weights (eval/train_encoder.py): the dense leg stops being
        # a random-init architecture statement and measures real retrieval
        # quality. The checkpoint's config applies to the EMBEDDER ONLY —
        # the reranker and mock-API server keep the scale's enc_cfg so the
        # rest of the matrix stays comparable to a no-checkpoint run.
        emb_params, emb_cfg = None, enc_cfg
        if encoder_checkpoint:
            from sentio_tpu.runtime.weights import load_model

            emb_params, emb_cfg, _ = load_model(
                encoder_checkpoint, expect_family="encoder"
            )
            extras["encoder_checkpoint"] = encoder_checkpoint
        _log("eval: embedding corpus on device ...")
        embedder = TpuEmbedder(
            EmbedderConfig(provider="tpu", batch_size=128),
            params=emb_params, model_config=emb_cfg,
        )
        t0 = time.perf_counter()
        vecs = embedder.embed_many([d.text for d in bundle.documents])
        ingest_s = time.perf_counter() - t0
        _log(f"eval: embedded {n_docs} docs in {ingest_s:.1f}s "
             f"({n_docs / max(ingest_s, 1e-9):.0f} docs/s)")
        dense_index = TpuDenseIndex(dim=emb_cfg.dim)
        dense_index.add(bundle.documents, vecs)
        extras["ingest_docs_per_s"] = round(n_docs / max(ingest_s, 1e-9), 1)
    bm25 = BM25Index().build(bundle.documents) if needs_sparse else None

    # ------------------------------------------- config 1: sparse + API LLM
    if "sparse_api" in want:
        from sentio_tpu.eval.baseline import MockModelServer

        server = MockModelServer(dim=enc_cfg.dim, rtt_ms=rtt_ms).start()
        try:
            sparse = SparseRetriever(bm25)
            api_gen = LLMGenerator(
                provider=OpenAIProvider(base_url=server.base_url + "/v1"),
                config=settings.generator,
            )

            def cfg1(question: str):
                docs = sparse.retrieve(question, top_k=10)
                answer = api_gen.generate(question, docs, mode="fast")
                return docs, answer

            _log("eval: [1/5] sparse_api ...")
            rows.append(run_queries("1-bm25+api-llm", cfg1, queries).row())
        finally:
            server.stop()

    # ------------------------------------------------ config 2: dense on TPU
    if "dense" in want:
        dense_ret = DenseRetriever(embedder, dense_index)

        def cfg2(question: str):
            return dense_ret.retrieve(question, top_k=10), ""

        _log("eval: [2/5] dense ...")
        rows.append(run_queries("2-dense-tpu", cfg2, queries).row())

    # ------------------------------- config 3: hybrid RRF + x-encoder rerank
    hybrid = reranker = None
    if want & {"hybrid_rerank", "full_paged", "batched"}:
        hybrid = HybridRetriever(
            retrievers=[DenseRetriever(embedder, dense_index), SparseRetriever(bm25)],
            config=settings.retrieval,
        )
        reranker = CrossEncoderReranker(RerankConfig(batch_size=32), model_config=enc_cfg)
    if "hybrid_rerank" in want:
        def cfg3(question: str):
            docs = hybrid.retrieve(question, top_k=10)
            return reranker.rerank(question, docs, top_k=10).documents, ""

        _log("eval: [3/5] hybrid_rerank ...")
        rows.append(run_queries("3-hybrid+rerank", cfg3, queries).row())

    # ---------------------- configs 4+5: full graph over paged continuous
    # batching (generator + verifier share one set of weights)
    service = None
    try:
        if want & {"full_paged", "batched"}:
            paged = ContinuousBatchingEngine(
                model_config=llm_cfg,
                max_slots=max(concurrency, 4),
                page_size=16,
                # per-sequence window = the model's full context — prompts
                # sized by context_token_budget above always fit
                max_pages_per_seq=llm_cfg.max_len // 16,
                steps_per_tick=16,
                max_tick_steps=64,
                pipeline_depth=2,
                # int8 KV pages: the quality-gate run (tests/test_eval.py)
                # measures this config's recall/answers against bf16
                kv_quant=kv_quant,
                # random-init weights greedy-sample EOS almost immediately;
                # fixed-length generation keeps configs 4/5 measuring the
                # full decode+verify cost real tuned models pay
                ignore_eos=True,
            )
            # the serving tier's front-end, N=1: eval measures the same
            # routed path production serves (a degenerate single-replica
            # route is a pass-through, so config outputs stay pinned).
            # supervise=False: eval never closes the set, and a leaked
            # supervisor thread would outlive the config run
            service = ReplicaSet([PagedGenerationService(paged)],
                                 supervise=False)
            generator = LLMGenerator(
                provider=TpuProvider(service=service),
                config=settings.generator,
            )
            verifier = AnswerVerifier(generator=generator, config=settings.generator)
            graph = build_basic_graph(
                hybrid, generator, reranker=reranker, verifier=verifier,
                config=GraphConfig(settings=settings),
            )

            # answer metric for the quantization quality gate: mean emitted
            # answer length (chars) — a degenerate int8 decode (empty /
            # collapsed answers) moves this even when retrieval recall
            # cannot see it. list.append is atomic under the GIL, so the
            # concurrent "batched" config needs no extra lock.
            answer_chars: list[int] = []
            # per-question FINAL verdicts (async/gated verdicts are awaited
            # off the flight record) — what TestVerifyGate compares between
            # a gated and an always-verify run; dict so the harness warmup
            # repeat of question 0 just overwrites
            verdicts: dict[str, str] = {}

            def _await_verdict(query_id: str, timeout_s: float = 60.0):
                """Poll the flight record for a detached verify's verdict
                (VERIFY_MODE=async|gated leave the graph before the audit
                lands)."""
                from sentio_tpu.infra.flight import get_flight_recorder

                deadline = time.perf_counter() + timeout_s
                while time.perf_counter() < deadline:
                    rec = get_flight_recorder().get(query_id) or {}
                    outcome = rec.get("verify", {}).get("outcome")
                    if outcome is not None:
                        return outcome
                    time.sleep(0.05)
                return None

            def full(question: str):
                import uuid

                query_id = f"eval-{uuid.uuid4().hex[:10]}"
                state = graph.invoke(create_initial_state(
                    question, metadata={"mode": "fast", "query_id": query_id}
                ))
                docs = state.get("reranked_documents") or state.get("retrieved_documents") or []
                answer = state.get("response", "") or ""
                answer_chars.append(len(answer))
                verdict = (state.get("evaluation") or {}).get("verdict")
                if verdict is None and state.get("metadata", {}).get(
                        "verify_pending"):
                    verdict = _await_verdict(query_id)
                if verdict is not None:
                    verdicts[question] = str(verdict)
                return docs, answer

            if "full_paged" in want:
                _log("eval: [4/5] full_paged ...")
                answer_chars.clear()
                verdicts.clear()
                res4 = run_queries("4-full-graph-paged", full, queries)
                if answer_chars:
                    res4.extras["answer_chars_mean"] = round(
                        sum(answer_chars) / len(answer_chars), 1)
                if verdicts:
                    res4.extras["verdicts"] = dict(verdicts)
                    skipped = sum(1 for v in verdicts.values()
                                  if v == "skipped_confident")
                    res4.extras["verify_skip_rate"] = round(
                        skipped / len(verdicts), 4)
                rows.append(res4.row())
            if "batched" in want:
                _log(f"eval: [5/5] batched x{concurrency} ...")
                before = service.stats()  # stats are service-lifetime
                answer_chars.clear()
                result = run_queries(
                    "5-batched-dp", full, queries, concurrent=concurrency
                )
                if answer_chars:
                    result.extras["answer_chars_mean"] = round(
                        sum(answer_chars) / len(answer_chars), 1)
                stats = service.stats()
                ticks = stats["ticks"] - before["ticks"]
                active = (
                    stats["avg_active_slots"] * stats["ticks"]
                    - before["avg_active_slots"] * before["ticks"]
                )
                result.extras["avg_active_slots"] = round(active / max(ticks, 1), 3)
                result.extras["max_active_slots"] = stats["max_active_slots"]
                result.extras["decode_ticks"] = ticks
                rows.append(result.row())

        # ------------------------------------- measured reference baseline
        baseline_row = None
        if not skip_baseline:
            from sentio_tpu.eval.baseline import measure_baseline

            _log("eval: measuring reference-architecture loopback baseline ...")
            baseline = measure_baseline(
                bundle.documents, queries, dim=min(enc_cfg.dim, 1024), rtt_ms=rtt_ms
            )
            baseline_row = baseline.row()
    finally:
        if service is not None:
            # detached verify threads (VERIFY_MODE=async|gated) still hold
            # tickets on this service — join them before tearing it down
            from sentio_tpu.graph.executor import wait_detached

            wait_detached()
            service.close()

    payload: dict = {
        "metric": "synthetic NQ-style retrieval-QA: recall@10, p50 ms, QPS",
        "bundle": {"n_docs": n_docs, "n_queries": n_queries, "seed": seed,
                   "n_facts": bundle.n_facts},
        "platform": {
            "devices": len(devices),
            "kind": devices[0].device_kind,
            "backend": devices[0].platform,
        },
        "models": {
            "encoder": {"dim": enc_cfg.dim, "layers": enc_cfg.n_layers},
            "llm": {"dim": llm_cfg.dim, "layers": llm_cfg.n_layers,
                    "vocab": llm_cfg.vocab_size},
            "new_tokens": new_tokens,
        },
        "rows": rows,
        "baseline": baseline_row,
        "rtt_ms": rtt_ms,
        "wall_s": round(time.perf_counter() - t_start, 1),
        **({"kv_quant": kv_quant} if kv_quant != "none" else {}),
        **({"verify_mode": verify_mode} if verify_mode != "sync" else {}),
        **extras,
    }

    # the north-star comparison: full graph p50 vs the measured baseline p50
    full_row = next((r for r in rows if r["config"].startswith("4-")), None)
    if full_row and baseline_row:
        payload["north_star"] = {
            "target_speedup": 10.0,
            "measured_p50_speedup": round(
                baseline_row["p50_ms"] / max(full_row["p50_ms"], 1e-9), 2
            ),
            "recall_delta": round(
                full_row["recall@10"] - baseline_row["recall@10"], 3
            ),
        }
    return payload
