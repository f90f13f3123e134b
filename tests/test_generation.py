import numpy as np
import pytest

from sentio_tpu.config import GeneratorConfig
from sentio_tpu.models.document import Document
from sentio_tpu.models.llama import LlamaConfig
from sentio_tpu.ops.generator import (
    EchoProvider,
    LLMGenerator,
    TpuProvider,
    create_generator,
    get_provider,
)
from sentio_tpu.ops.prompts import PromptBuilder
from sentio_tpu.ops.reply_extractor import extract_json_block
from sentio_tpu.ops.verifier import AnswerVerifier, VerifyResult


@pytest.fixture(scope="module")
def service():
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.service import PagedGenerationService

    svc = PagedGenerationService(ContinuousBatchingEngine(
        model_config=LlamaConfig.tiny(), max_slots=2, page_size=16,
        max_pages_per_seq=16))
    yield svc
    svc.close()


DOCS = [
    Document(text="The MXU is a systolic array.", id="a", metadata={"score": 0.9, "source": "tpu.md"}),
    Document(text="JAX uses XLA.", id="b", metadata={"score": 0.5, "source": "jax.md"}),
]


def test_device_stats():
    from sentio_tpu.runtime.weights import device_stats

    stats = device_stats(None, LlamaConfig.tiny())
    assert stats["platform"] == "cpu"
    assert stats["n_devices"] == 8
    assert stats["mesh"] is None
    assert stats["model"] == {"layers": 2, "dim": 64, "vocab": 512}


class TestSampling:
    def test_greedy_vs_temp(self):
        import jax
        import jax.numpy as jnp

        from sentio_tpu.runtime.sampling import sample_tokens

        logits = jnp.asarray([[1.0, 5.0, 2.0]])
        rng = jax.random.PRNGKey(0)
        assert int(sample_tokens(logits, rng, 0.0)[0][0]) == 1
        # top_k=1 forces argmax even at high temperature
        assert int(sample_tokens(logits, rng, 10.0, top_k=1)[0][0]) == 1

    def test_top_p_restricts_support(self):
        import jax
        import jax.numpy as jnp

        from sentio_tpu.runtime.sampling import sample_tokens

        logits = jnp.asarray([[10.0, 0.0, -10.0, -10.0]])
        picks = {
            int(sample_tokens(logits, jax.random.PRNGKey(i), 2.0, top_p=0.5)[0][0])
            for i in range(20)
        }
        assert picks == {0}

    def test_logprob_is_chosen_tokens_raw_log_softmax(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from sentio_tpu.runtime.sampling import sample_tokens

        logits = jnp.asarray([[1.0, 5.0, 2.0]])
        rng = jax.random.PRNGKey(0)
        tok, lp = sample_tokens(logits, rng, 0.0)
        expect = jax.nn.log_softmax(logits, axis=-1)[0, int(tok[0])]
        assert np.isclose(float(lp[0]), float(expect), atol=1e-6)
        assert float(lp[0]) < 0.0
        # the logprob reports the UNSCALED distribution: high temperature
        # with top_k=1 still picks argmax, and the logprob must match the
        # raw log-softmax, not the temperature-flattened one
        tok_t, lp_t = sample_tokens(logits, rng, 10.0, top_k=1)
        assert int(tok_t[0]) == int(tok[0])
        assert np.isclose(float(lp_t[0]), float(expect), atol=1e-6)


class TestPrompts:
    def test_fallback_templates_when_no_dir(self, tmp_path):
        pb = PromptBuilder(prompts_dir=str(tmp_path / "missing"))
        text = pb.build("retrieve", instruction="I", context="C", query="Q")
        assert "C" in text and "Q" in text

    def test_file_templates_cached(self, tmp_path):
        (tmp_path / "retrieve.md").write_text("CUSTOM {query}")
        pb = PromptBuilder(prompts_dir=str(tmp_path))
        assert pb.build("retrieve", query="hi") == "CUSTOM hi"
        (tmp_path / "retrieve.md").write_text("CHANGED {query}")
        assert pb.build("retrieve", query="hi") == "CUSTOM hi"  # cached
        PromptBuilder.clear_cache()

    def test_braces_in_context_safe(self, tmp_path):
        pb = PromptBuilder(prompts_dir=str(tmp_path / "missing"))
        out = pb.build("retrieve", context='{"weird": "json {braces}"}', query="q")
        assert '{"weird": "json {braces}"}' in out


class TestGenerator:
    def test_context_numbering_and_scores(self):
        gen = LLMGenerator(provider=EchoProvider(), config=GeneratorConfig())
        ctx = gen.prepare_context(DOCS)
        assert "[1] Source: tpu.md (score 0.900)" in ctx
        assert "[2] Source: jax.md" in ctx
        assert gen.prepare_context([]) == "(no context documents)"

    def test_echo_provider_quotes_top_source(self):
        gen = LLMGenerator(provider=EchoProvider(), config=GeneratorConfig())
        answer = gen.generate("what is the MXU?", DOCS)
        assert "[1]" in answer

    def test_stream_concat_equals_chat(self):
        gen = LLMGenerator(provider=EchoProvider(), config=GeneratorConfig())
        full = gen.generate("q", DOCS)
        streamed = "".join(gen.stream("q", DOCS))
        assert streamed == full

    def test_temperature_modes(self):
        cfg = GeneratorConfig()
        assert cfg.temperature("fast") == 0.0
        assert cfg.temperature("balanced") == 0.3
        assert cfg.temperature("quality") == 0.2
        assert cfg.temperature("creative") == 0.7
        assert cfg.temperature("bogus") == 0.3

    def test_tpu_provider_end_to_end(self, service):
        gen = LLMGenerator(
            provider=TpuProvider(service=service),
            config=GeneratorConfig(max_new_tokens=8),
        )
        out = gen.generate("tiny question", DOCS, mode="fast")
        assert isinstance(out, str)

    def test_registry(self):
        assert isinstance(get_provider("echo"), EchoProvider)
        with pytest.raises(ValueError):
            get_provider("nope")

    def test_create_generator_falls_back_without_service(self, settings):
        gen = create_generator(settings)
        assert isinstance(gen.provider, EchoProvider)


class TestReplyExtractor:
    def test_plain_json(self):
        r = extract_json_block('{"verdict": "pass"}')
        assert r.ok and r.payload["verdict"] == "pass"

    def test_fenced_json(self):
        r = extract_json_block('Sure!\n```json\n{"a": 1}\n```\nthanks')
        assert r.ok and r.payload == {"a": 1}

    def test_embedded_brace_span(self):
        r = extract_json_block('The audit says {"verdict": "warn", "notes": []} overall.')
        assert r.ok and r.payload["verdict"] == "warn"

    def test_nested_and_string_braces(self):
        r = extract_json_block('x {"outer": {"inner": "has } brace"}} y')
        assert r.ok and r.payload["outer"]["inner"] == "has } brace"

    def test_trailing_comma_relaxed(self):
        r = extract_json_block('{"a": 1, "b": [1, 2,],}')
        assert r.ok and r.payload["b"] == [1, 2]

    def test_garbage_returns_error(self):
        r = extract_json_block("no json here at all")
        assert not r.ok and r.error
        assert not extract_json_block("").ok


class TestVerifier:
    def _verifier(self, reply):
        class CannedProvider:
            name = "canned"

            def chat(self, prompt, max_new_tokens, temperature):
                assert temperature == 0.0  # audit runs at temp 0
                return reply

            def stream(self, *a, **k):
                yield reply

        gen = LLMGenerator(provider=CannedProvider(), config=GeneratorConfig())
        return AnswerVerifier(generator=gen, config=GeneratorConfig())

    def test_pass_verdict(self):
        v = self._verifier('{"verdict": "pass", "citations_ok": true, "notes": []}')
        result = v.verify("q", "answer", DOCS)
        assert result.verdict == "pass" and result.citations_ok

    def test_fail_with_revision(self):
        v = self._verifier(
            '{"verdict": "fail", "citations_ok": false, "notes": ["wrong"], '
            '"revised_answer": "better answer"}'
        )
        result = v.verify("q", "bad answer", DOCS)
        assert result.verdict == "fail"
        assert result.revised_answer == "better answer"

    def test_unparseable_degrades_to_warn(self):
        v = self._verifier("I refuse to emit JSON")
        result = v.verify("q", "a", DOCS)
        assert result.verdict == "warn"
        assert result.notes

    def test_invalid_verdict_normalized(self):
        v = self._verifier('{"verdict": "AMAZING", "notes": "single string"}')
        result = v.verify("q", "a", DOCS)
        assert result.verdict == "warn"
        assert result.notes == ["single string"]

    def test_provider_exception_never_raises(self):
        class BoomProvider:
            name = "boom"

            def chat(self, *a, **k):
                raise RuntimeError("device lost")

            def stream(self, *a, **k):
                raise RuntimeError("device lost")

        gen = LLMGenerator(provider=BoomProvider(), config=GeneratorConfig())
        v = AnswerVerifier(generator=gen, config=GeneratorConfig())
        result = v.verify("q", "a", DOCS)
        assert result.verdict == "warn"
        assert "device lost" in result.notes[0]

    def test_notes_capped_at_8(self):
        v = self._verifier(
            '{"verdict": "warn", "notes": ' + str([f"n{i}" for i in range(20)]).replace("'", '"') + "}"
        )
        assert len(v.verify("q", "a", DOCS).notes) == 8


class TestReviewRegressions:
    def test_single_quoted_json_verifier_reply(self):
        r = extract_json_block("{'verdict': 'fail', 'citations_ok': false, 'notes': ['x']}")
        assert r.ok
        assert r.payload["verdict"] == "fail"
        assert r.payload["citations_ok"] is False

    def test_prompt_value_containing_placeholder_not_reexpanded(self, tmp_path):
        pb = PromptBuilder(prompts_dir=str(tmp_path / "missing"))
        out = pb.build("verify", instruction="answer quoting {context} literally",
                       context="SOURCES", query="q")
        assert "answer quoting {context} literally" in out
        assert out.count("SOURCES") == 1


def test_relaxed_parse_preserves_true_inside_strings():
    r = extract_json_block("{'verdict': 'fail', 'revised_answer': 'the claim is true'}")
    assert r.ok
    assert r.payload["revised_answer"] == "the claim is true"
