"""Speculative decoding inside the paged engine (runtime/paged_spec.py).

Correctness bar: greedy rows are BIT-EXACT against the plain paged engine
(float32 configs — bf16 argmax ties flip between the dense-verify and
paged-decode float paths on degenerate random-init models, which is a
precision artifact, not a logic difference). Sampled rows go through
accept_and_correct, whose marginal-exactness is checked empirically below.
"""

from dataclasses import replace

import pytest

from sentio_tpu.models.llama import LlamaConfig, init_llama
from sentio_tpu.runtime.paged import ContinuousBatchingEngine

def f32_cfg():
    return replace(LlamaConfig.tiny(), dtype="float32")


def draft_cfg(cfg):
    return replace(
        LlamaConfig(vocab_size=cfg.vocab_size, dim=32, n_layers=1, n_heads=2,
                    n_kv_heads=2, mlp_dim=64, max_len=cfg.max_len),
        dtype="float32",
    )


@pytest.fixture(scope="module")
def stack():
    import jax

    cfg = f32_cfg()
    params = init_llama(jax.random.PRNGKey(0), cfg)
    dcfg = draft_cfg(cfg)
    dparams = init_llama(jax.random.PRNGKey(7), dcfg)
    return cfg, params, dcfg, dparams


def make(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_pages_per_seq", 8)
    return ContinuousBatchingEngine(model_config=cfg, params=params, **kw)


PROMPTS = ["speculate on this prompt", "another about mxu arrays",
           "third request", "and a fourth"]


class TestGreedyParity:
    def test_weak_draft_bit_exact(self, stack):
        cfg, params, dcfg, dparams = stack
        want = make(cfg, params, ignore_eos=True).run_all(PROMPTS, max_new_tokens=24)
        got = make(cfg, params, ignore_eos=True, draft_params=dparams,
                   draft_config=dcfg, spec_k=4).run_all(PROMPTS, max_new_tokens=24)
        assert [w.tokens for w in want] == [g.tokens for g in got]

    def test_perfect_draft_bit_exact(self, stack):
        cfg, params, _, _ = stack
        want = make(cfg, params, ignore_eos=True).run_all(PROMPTS, max_new_tokens=24)
        eng = make(cfg, params, ignore_eos=True, draft_params=params,
                   draft_config=cfg, spec_k=4)
        got = eng.run_all(PROMPTS, max_new_tokens=24)
        assert [w.tokens for w in want] == [g.tokens for g in got]
        # a perfect draft accepts ~everything: well above 1 token/verify
        # (tick-boundary budget caps keep it below the k+1 ceiling)
        stats = eng.stats()
        assert stats["spec_tokens_per_verify"] > 2.0, stats

    def test_eos_semantics_match(self, stack):
        """With EOS honored, spec must stop each row exactly where the
        plain engine does (same tokens, same finish reasons)."""
        cfg, params, dcfg, dparams = stack
        want = make(cfg, params).run_all(PROMPTS, max_new_tokens=24)
        got = make(cfg, params, draft_params=dparams, draft_config=dcfg,
                   spec_k=4).run_all(PROMPTS, max_new_tokens=24)
        assert [(w.tokens, w.finish_reason) for w in want] == \
               [(g.tokens, g.finish_reason) for g in got]

    def test_continuous_batching_waves(self, stack):
        """Requests joining and leaving across ticks (more requests than
        slots) keep greedy parity — speculation composes with the
        continuous-batching lifecycle, not just a single batch."""
        cfg, params, dcfg, dparams = stack
        prompts = [f"wave request number {i} about pallas" for i in range(10)]
        lens = [8 + (i * 5) % 20 for i in range(10)]

        def run(eng):
            rids = [eng.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, lens)]
            done = {}
            while eng.has_work:
                for r in eng.step():
                    done[r.request_id] = r
            return [done[r].tokens for r in rids]

        want = run(make(cfg, params, max_slots=3, ignore_eos=True))
        got = run(make(cfg, params, max_slots=3, ignore_eos=True,
                       draft_params=dparams, draft_config=dcfg, spec_k=3))
        assert want == got


class TestCompositions:
    def test_prefix_cache_composes(self, stack):
        cfg, params, dcfg, dparams = stack
        header = "System header: be terse and cite. "
        prompts = [header + q for q in ("what is a mesh?", "why bfloat16?")]
        want = make(cfg, params, ignore_eos=True).run_all(prompts, max_new_tokens=16)
        spec = make(cfg, params, ignore_eos=True, draft_params=dparams,
                    draft_config=dcfg, spec_k=4)
        assert spec.warm_prefix(header) > 0
        got = spec.run_all(prompts, max_new_tokens=16)
        assert [w.tokens for w in want] == [g.tokens for g in got]
        assert spec.prefix_hits == 2

    def test_int8_kv_composes(self, stack):
        """Spec gathers quantized pages through dequantize and re-quantizes
        on scatter-back (idempotent absmax scales). Outputs are NOT
        bit-compared to the plain int8 engine: within a tick the verify
        attends the current rounds' KV at full precision while the plain
        engine reads every step through int8 — spec output differs within
        quantization noise (and is at least as close to the unquantized
        model). The invariants: the compose path runs, budgets hold, and
        the first token (identical prefill path both sides) matches."""
        cfg, params, dcfg, dparams = stack
        want = make(cfg, params, ignore_eos=True,
                    kv_quant="int8").run_all(PROMPTS[:2], max_new_tokens=16)
        got = make(cfg, params, ignore_eos=True, kv_quant="int8",
                   draft_params=dparams, draft_config=dcfg,
                   spec_k=4).run_all(PROMPTS[:2], max_new_tokens=16)
        for w, g in zip(want, got):
            assert len(g.tokens) == 16
            assert g.tokens[0] == w.tokens[0]

    def test_long_prompt_bucket_exceeding_draft_window(self, stack):
        """Draft-cache overrun regression: with max_pages_per_seq=6 the
        per-row window is 96 tokens, and a ~70-token prompt buckets its
        prefill width to 128 — before the clamp, draft prefill's
        ``.at[:, rows_idx, :width].set`` overhung the 96-wide draft cache
        axis and failed at trace time, killing the tick thread."""
        cfg, params, dcfg, dparams = stack
        prompt = "overrun " * 9  # 72 bytes + BOS → width bucket 128 > 96
        want = make(cfg, params, ignore_eos=True, max_pages_per_seq=6) \
            .run_all([prompt], max_new_tokens=4)
        eng = make(cfg, params, ignore_eos=True, max_pages_per_seq=6,
                   draft_params=dparams, draft_config=dcfg, spec_k=4)
        got = eng.run_all([prompt], max_new_tokens=4)
        assert [w.tokens for w in want] == [g.tokens for g in got]
        assert got[0].finish_reason in ("stop", "length")

    def test_sampled_and_mixed_batch_complete(self, stack):
        """Sampled rows (rejection sampling) and greedy rows serve in the
        same tick; per-call outputs are rng-path-dependent so only the
        contract is asserted (length, budget) — marginal exactness of the
        accept rule is checked at the end of this file."""
        cfg, params, dcfg, dparams = stack
        eng = make(cfg, params, ignore_eos=True, draft_params=dparams,
                   draft_config=dcfg, spec_k=4)
        rids = [eng.submit(PROMPTS[i], max_new_tokens=12,
                           temperature=0.0 if i % 2 else 0.8)
                for i in range(4)]
        done = {}
        while eng.has_work:
            for r in eng.step():
                done[r.request_id] = r
        assert all(len(done[r].tokens) == 12 for r in rids)


class TestValidation:
    def test_vocab_mismatch_raises(self, stack):
        cfg, params, dcfg, dparams = stack
        bad = replace(dcfg, vocab_size=cfg.vocab_size * 2)
        with pytest.raises(ValueError, match="vocab"):
            make(cfg, params, draft_params=dparams, draft_config=bad)

    def test_chunked_prefill_conflict_raises(self, stack):
        cfg, params, dcfg, dparams = stack
        with pytest.raises(ValueError, match="mutually exclusive"):
            make(cfg, params, draft_params=dparams, draft_config=dcfg,
                 prefill_chunk=16)

    def test_draft_without_config_raises(self, stack):
        cfg, params, _, dparams = stack
        with pytest.raises(ValueError, match="draft_config"):
            make(cfg, params, draft_params=dparams)


class TestServingIntegration:
    def test_draft_checkpoint_activates_paged_spec(self, stack, tmp_path):
        """LLM_DRAFT_CHECKPOINT speculates in the paged service, through
        the real DI container."""
        from sentio_tpu.config import (
            EmbedderConfig, GeneratorConfig, RerankConfig, Settings,
        )
        from sentio_tpu.runtime.checkpoint import save_pytree
        from sentio_tpu.serve.dependencies import DependencyContainer

        _cfg, _params, dcfg, dparams = stack
        from dataclasses import asdict

        ck = tmp_path / "draft-ck"
        save_pytree(ck, dparams,
                    meta={"family": "llama", "config": asdict(dcfg)})

        settings = Settings(
            embedder=EmbedderConfig(provider="hash", dim=32),
            rerank=RerankConfig(enabled=False),
            generator=GeneratorConfig(
                provider="tpu", model_preset="tiny", use_verifier=False,
                max_new_tokens=12, kv_page_size=16,
                kv_max_pages_per_seq=8, max_batch_size=2,
                draft_checkpoint_path=str(ck), speculative_k=3,
                prefix_cache=False,
            ),
        )
        # mesh=None mirrors the real single-chip deployment (the test env's
        # 8 virtual CPU devices would otherwise build a dp mesh, and paged
        # speculation doesn't support meshes yet)
        container = DependencyContainer(settings=settings, mesh=None)
        service = container.generation_service
        assert service is not None
        (replica,) = service._services  # the replica set's one service
        eng = replica.engine
        assert eng.draft_params is not None and eng.spec_k == 3
        try:
            out = service.generate("one request through the spec path",
                                   max_new_tokens=10, temperature=0.0)
            assert len(out.tokens) == 10 or out.finish_reason == "stop"
        finally:
            service.close()


def test_acceptance_kernel_preserves_target_distribution():
    """The whole-point property of rejection-sampling speculation: the
    marginal of the FIRST emitted token equals the target distribution,
    for an arbitrary (mismatched) draft. Empirical check over 40k
    independent single-round draws on a toy vocab."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentio_tpu.runtime.paged_spec import accept_and_correct

    v, k, n = 6, 1, 40_000
    rng = np.random.default_rng(0)
    p_t = rng.dirichlet(np.ones(v))          # target dist
    q = rng.dirichlet(np.ones(v) * 0.3)      # very different draft dist

    tprobs = jnp.asarray(
        np.broadcast_to(p_t, (n, k + 1, v)).copy(), jnp.float32
    )
    qdists = jnp.asarray(np.broadcast_to(q, (n, k, v)).copy(), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(1), n + 1)
    drafts = jax.random.categorical(
        keys[0], jnp.log(qdists[:, 0] + 1e-20), axis=-1
    )[:, None].astype(jnp.int32)

    def one(key, d):
        n_acc, corr = accept_and_correct(
            key, d[None], qdists[:1], tprobs[:1]
        )
        # first emitted token: the draft if accepted, else the correction
        return jnp.where(n_acc[0] > 0, d[0], corr[0])

    emitted = np.asarray(jax.vmap(one)(keys[1:], drafts))
    freq = np.bincount(emitted, minlength=v) / n
    np.testing.assert_allclose(freq, p_t, atol=0.015)
