"""MoE decoder (models/moe.py): routing math, capacity semantics, dense
parity, cache-path parity, and expert-parallel sharding."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentio_tpu.config import MeshConfig
from sentio_tpu.models.moe import (
    MoeConfig,
    expert_capacity,
    init_cache,
    init_moe,
    moe_forward,
    moe_loss,
    moe_mlp,
    route_topk,
)
from sentio_tpu.parallel.mesh import build_mesh
from sentio_tpu.parallel.sharding import MOE_EP_RULES, shard_params

pytestmark = [pytest.mark.slow, pytest.mark.mesh]


@pytest.fixture(scope="module")
def cfg():
    return MoeConfig.tiny()


@pytest.fixture(scope="module")
def f32_cfg():
    return replace(MoeConfig.tiny(), dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return init_moe(jax.random.PRNGKey(0), cfg)


class TestRouting:
    def test_topk_dispatches_to_top_experts(self):
        logits = jnp.asarray(
            [[5.0, 1.0, 0.0, -1.0], [0.0, 0.0, 6.0, 5.0]], jnp.float32
        )
        dispatch, combine, _ = route_topk(logits, k=2, capacity=2)
        d = np.asarray(dispatch)
        # token 0 → experts 0 and 1; token 1 → experts 2 and 3
        assert d[0, 0].any() and d[0, 1].any() and not d[0, 2:].any()
        assert d[1, 2].any() and d[1, 3].any() and not d[1, :2].any()
        # gates renormalize to 1 per token
        c = np.asarray(combine)
        np.testing.assert_allclose(c.sum(axis=(1, 2)), [1.0, 1.0], atol=1e-5)

    def test_capacity_drops_overflow_tokens(self):
        # every token's top-1 is expert 0 with capacity 1: only the first
        # token keeps that choice, later tokens lose it
        logits = jnp.asarray([[9.0, 1.0]] * 4, jnp.float32)
        dispatch, combine, _ = route_topk(logits, k=1, capacity=1)
        d = np.asarray(dispatch)
        assert d[0, 0, 0]
        assert not d[1:, 0].any()

    def test_capacity_formula(self, cfg):
        c = expert_capacity(cfg, 128)
        per = 128 * cfg.experts_per_token / cfg.n_experts
        assert c >= per  # capacity_factor >= 1 never under-provisions


class TestMoeMlp:
    def test_matches_per_token_reference(self, f32_cfg):
        """Dispatch/combine einsums must equal the naive per-token loop when
        capacity is ample (nothing dropped)."""
        cfg = replace(f32_cfg, capacity_factor=8.0)
        p = init_moe(jax.random.PRNGKey(1), cfg)
        mp = p["layers_0"]["moe"]
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((2, 5, cfg.dim)), jnp.float32)

        out, aux = moe_mlp(mp, cfg, x)

        flat = np.asarray(x.reshape(-1, cfg.dim))
        logits = flat @ np.asarray(mp["router"]["kernel"])
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        expected = np.zeros_like(flat)
        for g in range(flat.shape[0]):
            order = np.argsort(-probs[g])[: cfg.experts_per_token]
            gates = probs[g][order]
            gates = gates / gates.sum()
            for e, w in zip(order, gates):
                wg = np.asarray(mp["w_gate"][e])
                wu = np.asarray(mp["w_up"][e])
                wd = np.asarray(mp["w_down"][e])
                h = flat[g]
                silu = lambda v: v / (1 + np.exp(-v))
                expected[g] += w * ((silu(h @ wg) * (h @ wu)) @ wd)
        np.testing.assert_allclose(
            np.asarray(out).reshape(-1, cfg.dim), expected, atol=1e-3
        )
        assert np.isfinite(float(aux))

    def test_dropped_tokens_pass_residual_through(self, f32_cfg):
        """A dropped token's MoE output is zero, so the block reduces to the
        residual stream for it."""
        cfg = replace(f32_cfg, n_experts=2, experts_per_token=1,
                      capacity_factor=0.01)
        p = init_moe(jax.random.PRNGKey(1), cfg)
        mp = p["layers_0"]["moe"]
        x = jnp.ones((1, 8, cfg.dim), jnp.float32)
        out, _ = moe_mlp(mp, cfg, x)
        # capacity 1 per expert, 8 identical tokens → at most 2 kept
        norms = np.linalg.norm(np.asarray(out)[0], axis=-1)
        assert (norms < 1e-6).sum() >= 6


class TestMoeForward:
    def test_decode_matches_full_forward(self, cfg):
        # ample capacity so the T=12 prefill and T=1 decode calls route
        # identically (capacity depends on the token count per call)
        cfg = replace(cfg, capacity_factor=8.0)
        params = init_moe(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(3)
        ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 12)), jnp.int32)

        full_logits, _, _ = moe_forward(params, cfg, ids)

        cache = init_cache(cfg, batch=2, max_len=32)
        _, cache, _ = moe_forward(
            params, cfg, ids[:, :8],
            positions=jnp.broadcast_to(jnp.arange(8)[None], (2, 8)),
            cache=cache, cache_index=0,
        )
        logits = None
        for t in range(8, 12):
            logits, cache, _ = moe_forward(
                params, cfg, ids[:, t : t + 1],
                positions=jnp.full((2, 1), t, jnp.int32),
                cache=cache, cache_index=t,
            )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full_logits[:, 11]),
            atol=0.08,  # bf16 accumulation noise only
        )

    def test_padding_takes_no_expert_capacity(self, f32_cfg):
        """With capacity exactly fitting the real tokens, a front-loaded pad
        run must not evict real tokens from their experts."""
        cfg = replace(f32_cfg, n_experts=2, experts_per_token=1,
                      capacity_factor=1.0)
        p = init_moe(jax.random.PRNGKey(1), cfg)
        mp = p["layers_0"]["moe"]
        rng = np.random.default_rng(8)
        x = jnp.asarray(rng.standard_normal((1, 8, cfg.dim)), jnp.float32)
        pad = np.zeros((1, 8), bool)
        pad[0, 4:] = True  # only the LAST 4 tokens are real
        pad_mask = jnp.asarray(pad)

        out_masked, aux = moe_mlp(mp, cfg, x, pad_mask)
        o = np.asarray(out_masked)[0]
        # real tokens got expert outputs (pads upstream claimed no slots)
        assert (np.linalg.norm(o[4:], axis=-1) > 1e-6).all()
        assert np.isfinite(float(aux))

    def test_serving_adapter_two_tuple(self, params, cfg):
        from sentio_tpu.models.moe import moe_serving_forward

        ids = jnp.ones((2, 4), jnp.int32)
        logits, cache = moe_serving_forward(params, cfg, ids)
        assert logits.shape == (2, 4, cfg.vocab_size)
        assert cache is None

    def test_loss_finite_and_aux_contributes(self, params, cfg):
        rng = np.random.default_rng(4)
        ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 17)), jnp.int32)
        mask = jnp.ones((4, 17), bool)
        loss = float(moe_loss(params, cfg, ids, mask))
        assert np.isfinite(loss)
        no_aux = replace(cfg, router_aux_weight=0.0)
        assert float(moe_loss(params, no_aux, ids, mask)) < loss


class TestMoeServing:
    def test_service_serves_moe(self, params, cfg):
        """The model-family seam: the serving path runs MoE checkpoints
        through the same generate/stream surface as Llama."""
        from sentio_tpu.models.moe import moe_serving_forward
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.service import PagedGenerationService

        svc = PagedGenerationService(ContinuousBatchingEngine(
            model_config=cfg, params=params, forward_fn=moe_serving_forward,
            max_slots=2, page_size=16, max_pages_per_seq=8,
        ))
        try:
            r = svc.generate("hello experts", max_new_tokens=8, temperature=0.0)
            r2 = svc.generate("hello experts", max_new_tokens=8, temperature=0.0)
            assert r.tokens == r2.tokens  # greedy decode is deterministic
            assert r.finish_reason in ("stop", "length")

            streamed = "".join(svc.generate_stream(
                "hello experts", max_new_tokens=8, temperature=0.0))
            assert streamed == r.text
        finally:
            svc.close()

    def test_paged_engine_serves_moe(self, cfg):
        """The DEFAULT serving path (paged continuous batching) runs MoE:
        fused decode ticks route per layer, prefill goes through the family
        seam. Ample capacity makes routing batch-size-independent, so paged
        greedy must match cache-free greedy decoding exactly (with tight
        capacity the two are both valid but can drop different tokens, since
        capacity is a function of the tokens-per-call)."""
        from conftest import CacheFreeGreedy
        from sentio_tpu.models.moe import init_moe, moe_serving_forward
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine

        acfg = replace(cfg, capacity_factor=8.0)
        params = init_moe(jax.random.PRNGKey(0), acfg)
        prompts = ["routed experts on pages", "second request here"]

        paged = ContinuousBatchingEngine(
            model_config=acfg, params=params, forward_fn=moe_serving_forward,
            max_slots=4, page_size=16, max_pages_per_seq=8, steps_per_tick=4,
        )
        res = paged.run_all(prompts, max_new_tokens=8, temperature=0.0)

        dense = CacheFreeGreedy(acfg, params=params).generate(
            prompts, max_new_tokens=8, temperature=0.0)
        assert [r.tokens for r in res] == [r.tokens for r in dense]

    def test_engines_reject_family_mismatch(self, cfg):
        from sentio_tpu.models.llama import LlamaConfig, llama_forward
        from sentio_tpu.models.moe import moe_serving_forward
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine

        # moe forward against a dense config
        with pytest.raises(ValueError, match="does not match"):
            ContinuousBatchingEngine(
                model_config=LlamaConfig.tiny(), forward_fn=moe_serving_forward
            )
        # dense forward against a moe config
        with pytest.raises(ValueError, match="does not match"):
            ContinuousBatchingEngine(model_config=cfg, forward_fn=llama_forward)

    def test_moe_config_alone_auto_selects_family(self, cfg):
        """A MoeConfig with no params random-inits MoE weights and routes —
        never silently degrades to a dense Llama."""
        from sentio_tpu.models.moe import moe_serving_forward
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine

        paged = ContinuousBatchingEngine(
            model_config=cfg, max_slots=2, page_size=16, max_pages_per_seq=4,
        )
        assert paged.forward_fn is moe_serving_forward
        assert "moe" in paged.params["layers_0"]


class TestExpertParallel:
    def test_ep_sharded_loss_matches(self, params, cfg):
        rng = np.random.default_rng(5)
        ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 17)), jnp.int32)
        mask = jnp.ones((4, 17), bool)
        ref = float(moe_loss(params, cfg, ids, mask))
        mesh = build_mesh(MeshConfig(dp_size=2, ep_size=2, tp_size=2))
        sharded = shard_params(params, mesh, MOE_EP_RULES)
        got = float(jax.jit(lambda p, i, m: moe_loss(p, cfg, i, m))(sharded, ids, mask))
        assert abs(got - ref) < 2e-2

    def test_ep_rules_place_experts_on_ep(self, params):
        mesh = build_mesh(MeshConfig(dp_size=2, ep_size=2, tp_size=2))
        sharded = shard_params(params, mesh, MOE_EP_RULES)
        spec = sharded["layers_0"]["moe"]["w_gate"].sharding.spec
        assert spec[0] == "ep" and spec[2] == "tp"
        spec_down = sharded["layers_0"]["moe"]["w_down"].sharding.spec
        assert spec_down[0] == "ep" and spec_down[1] == "tp"
        # router replicated (spec entries all None)
        router_spec = sharded["layers_0"]["moe"]["router"]["kernel"].sharding.spec
        assert all(entry is None for entry in router_spec)

    def test_paged_serving_on_ep_mesh(self, cfg):
        """Mesh-sharded MoE through the DEFAULT serving path: experts on ep,
        kv pool heads on tp, greedy tokens matching the single-device run."""
        from sentio_tpu.models.moe import init_moe, moe_serving_forward
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine

        acfg = replace(cfg, capacity_factor=8.0)
        params = init_moe(jax.random.PRNGKey(0), acfg)
        mesh = build_mesh(MeshConfig(dp_size=2, ep_size=2, tp_size=2))
        sharded = shard_params(params, mesh, MOE_EP_RULES)
        prompts = ["experts on a mesh", "second lane"]

        served = ContinuousBatchingEngine(
            model_config=acfg, params=sharded, mesh=mesh,
            forward_fn=moe_serving_forward,
            max_slots=4, page_size=16, max_pages_per_seq=8, steps_per_tick=4,
        ).run_all(prompts, max_new_tokens=8, temperature=0.0)

        single = ContinuousBatchingEngine(
            model_config=acfg, params=params, forward_fn=moe_serving_forward,
            max_slots=4, page_size=16, max_pages_per_seq=8, steps_per_tick=4,
        ).run_all(prompts, max_new_tokens=8, temperature=0.0)
        assert [r.tokens for r in served] == [r.tokens for r in single]

    def test_ep_train_step(self, params, cfg):
        import optax

        rng = np.random.default_rng(6)
        ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 17)), jnp.int32)
        mask = jnp.ones((4, 17), bool)
        mesh = build_mesh(MeshConfig(dp_size=2, ep_size=2, tp_size=2))
        sharded = shard_params(params, mesh, MOE_EP_RULES)
        tx = optax.adamw(1e-3)
        opt = tx.init(sharded)

        def step(p, o, i, m):
            loss, g = jax.value_and_grad(lambda q: moe_loss(q, cfg, i, m))(p)
            up, o = tx.update(g, o, p)
            return optax.apply_updates(p, up), o, loss

        p2, o2, loss = jax.jit(step)(sharded, opt, ids, mask)
        assert np.isfinite(float(loss))
        # params actually moved
        delta = sum(
            float(jnp.abs(a - b).sum())
            for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(sharded))
        )
        assert delta > 0
