"""Eval subsystem: the runner wiring, the OpenAI-compatible provider, and
the loopback baseline — the measurement path behind BASELINE.json's matrix."""

import pytest

from sentio_tpu.eval.dataset import build_bundle
from sentio_tpu.eval.runner import run_eval


@pytest.fixture(scope="module")
def mock_server():
    from sentio_tpu.eval.baseline import MockModelServer

    server = MockModelServer(dim=64).start()
    yield server
    server.stop()


class TestOpenAIProvider:
    def test_chat_roundtrip(self, mock_server):
        from sentio_tpu.ops.generator import OpenAIProvider

        provider = OpenAIProvider(base_url=mock_server.base_url + "/v1")
        out = provider.chat("[1] Source: a.md\nhello", max_new_tokens=16, temperature=0.0)
        assert isinstance(out, str) and out

    def test_stream_falls_back_to_chat(self, mock_server):
        # the mock server has no SSE support; stream must still yield text
        from sentio_tpu.ops.generator import OpenAIProvider

        provider = OpenAIProvider(base_url=mock_server.base_url + "/v1")
        chunks = list(provider.stream("question?", max_new_tokens=16, temperature=0.0))
        assert "".join(chunks)

    def test_registered_and_configurable(self):
        from sentio_tpu.config import GeneratorConfig
        from sentio_tpu.ops.generator import OpenAIProvider, create_generator, get_provider

        from sentio_tpu.config import Settings
        from sentio_tpu.ops.generator import EchoProvider

        assert get_provider("openai").name == "openai"
        # default settings (provider=tpu, no service) degrade to echo
        gen = create_generator(settings=None, service=None)
        assert isinstance(gen.provider, EchoProvider)
        cfg = GeneratorConfig(provider="openai", api_base="http://x/v1", api_model="m")
        s = Settings()
        s.generator = cfg
        gen = create_generator(settings=s)
        assert isinstance(gen.provider, OpenAIProvider)
        assert gen.provider.base_url == "http://x/v1"
        assert gen.provider.model == "m"

    def test_retries_then_raises(self):
        from sentio_tpu.ops.generator import OpenAIProvider

        provider = OpenAIProvider(
            base_url="http://127.0.0.1:9/v1", max_retries=1, timeout_s=0.2
        )
        with pytest.raises(RuntimeError, match="after 2 attempts"):
            provider.chat("x", max_new_tokens=4, temperature=0.0)

    def test_api_v1_404_fallback_switches_base(self, mock_server):
        """OpenRouter-style /api/v1 vs /v1 drift (reference openai.py:124-144
        there): a 404 on the configured base retries once against the
        stripped base and keeps it on success."""
        from sentio_tpu.ops.generator import OpenAIProvider

        provider = OpenAIProvider(base_url=mock_server.base_url + "/api/v1")
        out = provider.chat("[1] Source: a.md\nhello", max_new_tokens=8,
                            temperature=0.0)
        assert isinstance(out, str) and out
        assert provider.base_url == mock_server.base_url + "/v1"
        # subsequent calls go straight to the working base
        assert provider.chat("again?", max_new_tokens=8, temperature=0.0)

    def test_usage_tracked_per_call(self, mock_server):
        from sentio_tpu.ops.generator import OpenAIProvider

        provider = OpenAIProvider(base_url=mock_server.base_url + "/v1")
        provider.chat("count my tokens please", max_new_tokens=8, temperature=0.0)
        usage = provider.last_usage
        assert usage["prompt_tokens"] >= 1 and usage["completion_tokens"] >= 1

    def test_switch_base_concurrent_threads_no_flap_no_leak(
        self, mock_server, monkeypatch
    ):
        """Racing 404 fallbacks from concurrent worker threads must
        converge on ONE base-URL switch (compare-and-swap under the
        provider lock), every call must still succeed — including a thread
        whose 404 landed on the retired base mid-switch — and every pooled
        client ever built must reach close()."""
        import threading

        import httpx

        from sentio_tpu.ops.generator import OpenAIProvider

        created = []
        real_client = httpx.Client

        class TrackingClient(real_client):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                created.append(self)

        monkeypatch.setattr(httpx, "Client", TrackingClient)
        provider = OpenAIProvider(base_url=mock_server.base_url + "/api/v1")
        n = 8
        start = threading.Barrier(n)
        errors = []

        def worker(i):
            try:
                start.wait(timeout=10)
                out = provider.chat(f"[1] Source: a.md\nquestion {i}?",
                                    max_new_tokens=4, temperature=0.0)
                assert out
            except Exception as exc:  # noqa: BLE001 — collected for assert
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        # converged on the stripped base, no flapping back
        assert provider.base_url == mock_server.base_url + "/v1"
        assert provider.chat("settled?", max_new_tokens=4, temperature=0.0)
        provider.close()
        assert getattr(provider, "_client_cached", None) is None
        assert getattr(provider, "_retired_clients", []) == []
        # nothing leaked: every client ever constructed was closed
        assert created and all(c.is_closed for c in created)



class TestEvalDataset:
    def test_bundle_deterministic(self):
        a = build_bundle(n_docs=64, n_queries=8, seed=3)
        b = build_bundle(n_docs=64, n_queries=8, seed=3)
        assert [d.text for d in a.documents] == [d.text for d in b.documents]
        assert a.queries == b.queries
        # gold ids all exist in the corpus
        ids = {d.id for d in a.documents}
        assert all(gold in ids for _, gold in a.queries)


class TestRunEval:
    def test_retrieval_configs_produce_rows(self):
        payload = run_eval(
            scale="tiny", n_docs=64, n_queries=6, new_tokens=4,
            skip_baseline=True, configs={"sparse_api", "dense", "hybrid_rerank"},
        )
        rows = {r["config"]: r for r in payload["rows"]}
        assert set(rows) == {"1-bm25+api-llm", "2-dense-tpu", "3-hybrid+rerank"}
        for r in rows.values():
            assert 0.0 <= r["recall@10"] <= 1.0
            assert r["p50_ms"] > 0 and r["qps"] > 0
        # BM25 is near-exact on the entity bundle — the sparse config must
        # find the gold doc for most paraphrased questions
        assert rows["1-bm25+api-llm"]["recall@10"] >= 0.5

    def test_full_graph_config_uses_paged_service(self):
        payload = run_eval(
            scale="tiny", n_docs=48, n_queries=3, concurrency=2,
            new_tokens=4, verifier_tokens=4, skip_baseline=True,
            configs={"batched"},
        )
        (row,) = payload["rows"]
        assert row["config"] == "5-batched-dp"
        assert row["decode_ticks"] > 0, "paged continuous batching must be live"
        assert row.get("errors", 0) == 0

    def test_baseline_measured(self):
        bundle = build_bundle(n_docs=48, n_queries=4)
        from sentio_tpu.eval.baseline import measure_baseline

        result = measure_baseline(bundle.documents, bundle.queries, dim=64)
        assert result.n_queries == 4
        assert result.p50_ms > 0
        assert result.extras["http_calls"]["chat"] >= 4


class TestQuantQualityGate:
    """KV_QUANT=int8 quality gate: the int8 full-graph eval is measured
    against a bf16 run over the same bundle in the same process, and the
    delta is gated by the COMMITTED tolerances in eval/quant_gate.json —
    a quantization quality regression fails tier-1 here instead of being
    suspected in production."""

    GATE_ARGS = dict(
        scale="tiny", n_docs=48, n_queries=4, concurrency=2,
        new_tokens=8, verifier_tokens=4, skip_baseline=True,
        configs={"full_paged"},
    )

    def test_int8_recall_and_answers_within_committed_tolerance(self):
        import json
        from pathlib import Path

        gate_path = (Path(__file__).resolve().parents[1] / "sentio_tpu"
                     / "eval" / "quant_gate.json")
        gate = json.loads(gate_path.read_text())

        bf16 = run_eval(**self.GATE_ARGS)
        int8 = run_eval(**self.GATE_ARGS, kv_quant="int8")
        (bf_row,) = bf16["rows"]
        (i8_row,) = int8["rows"]
        assert int8["kv_quant"] == "int8"

        assert i8_row.get("errors", 0) <= gate["errors_max"], i8_row
        drop = bf_row["recall@10"] - i8_row["recall@10"]
        assert drop <= gate["recall_at_10_max_drop"], (
            f"int8 recall@10 dropped {drop:.3f} vs bf16 "
            f"(gate {gate['recall_at_10_max_drop']}): {bf_row} vs {i8_row}")
        # collapsed/empty int8 decodes move the answer-length metric even
        # when retrieval recall cannot see them
        bf_chars = bf_row.get("answer_chars_mean", 0.0)
        i8_chars = i8_row.get("answer_chars_mean", 0.0)
        assert bf_chars > 0, bf_row
        assert i8_chars >= gate["answer_chars_min_ratio"] * bf_chars, (
            f"int8 mean answer length {i8_chars} vs bf16 {bf_chars} "
            f"(gate ratio {gate['answer_chars_min_ratio']})")


class TestVerifyGate:
    """VERIFY_MODE=gated quality gate: a gated full-graph eval run is
    measured against an always-verify (sync) run over the same bundle in
    the same process, and per-query FINAL verdicts (async verdicts awaited
    off the flight record) are gated by the COMMITTED tolerances in
    eval/verify_gate.json — a confidence-calibration regression that skips
    audits which would have warned/failed drops agreement and fails tier-1
    here instead of shipping silently."""

    GATE_ARGS = dict(
        scale="tiny", n_docs=48, n_queries=4, concurrency=2,
        new_tokens=8, verifier_tokens=4, skip_baseline=True,
        configs={"full_paged"},
    )

    def test_gated_verdicts_agree_with_always_verify(self):
        import json
        from pathlib import Path

        gate_path = (Path(__file__).resolve().parents[1] / "sentio_tpu"
                     / "eval" / "verify_gate.json")
        gate = json.loads(gate_path.read_text())

        sync = run_eval(**self.GATE_ARGS, verify_mode="sync")
        gated = run_eval(**self.GATE_ARGS, verify_mode="gated")
        (sync_row,) = sync["rows"]
        (gated_row,) = gated["rows"]
        assert gated["verify_mode"] == "gated"
        assert gated_row.get("errors", 0) <= gate["errors_max"], gated_row

        sync_v = sync_row.get("verdicts") or {}
        gated_v = gated_row.get("verdicts") or {}
        assert sync_v and gated_v, (
            f"both runs must record per-query verdicts: {sync_row} "
            f"vs {gated_row}")
        common = set(sync_v) & set(gated_v)
        assert common, (sync_v, gated_v)
        # a skipped audit asserts the answer would have PASSED — count it
        # as agreement only against a sync pass
        agree = sum(
            1 for q in common
            if gated_v[q] == sync_v[q]
            or (gated_v[q] == "skipped_confident" and sync_v[q] == "pass")
        )
        agreement = agree / len(common)
        assert agreement >= gate["min_verdict_agreement"], (
            f"gated-vs-sync verdict agreement {agreement:.3f} below the "
            f"committed gate {gate['min_verdict_agreement']}: "
            f"{gated_v} vs {sync_v}")
        skip_rate = gated_row.get("verify_skip_rate", 0.0)
        assert skip_rate <= gate["max_skip_rate"], (
            f"gated skip rate {skip_rate} exceeds the committed ceiling "
            f"{gate['max_skip_rate']} — the confidence score is calling "
            f"random-init decodes confident")
