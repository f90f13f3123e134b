"""HF checkpoint conversion parity: build tiny torch models in-memory,
convert their state dicts, and require numerical agreement between our JAX
forward pass and the torch reference forward. This is the strongest form of
the reference's mock-backend strategy (SURVEY.md §4) — instead of canned
outputs, the real conversion path is validated against the source framework.
"""

from __future__ import annotations

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from sentio_tpu.models.convert import (  # noqa: E402
    convert_cross_encoder,
    convert_encoder,
    convert_llama,
    encoder_config_from_hf,
    llama_config_from_hf,
)


@pytest.fixture(scope="module")
def tiny_hf_llama():
    cfg = transformers.LlamaConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rope_theta=10_000.0,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg).eval()
    return model, cfg


class TestLlamaConversion:
    def test_logits_match_torch(self, tiny_hf_llama):
        model, hf_cfg = tiny_hf_llama
        cfg = llama_config_from_hf(hf_cfg, dtype="float32")
        params = convert_llama(model.state_dict(), cfg)

        ids = np.array([[1, 5, 9, 2, 77, 33], [3, 8, 120, 4, 6, 11]], np.int32)
        with torch.no_grad():
            ref = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()

        from sentio_tpu.models.llama import llama_forward

        got, _ = llama_forward(params, cfg, jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(got), ref, atol=2e-4, rtol=2e-3)

    def test_config_mapping(self, tiny_hf_llama):
        _, hf_cfg = tiny_hf_llama
        cfg = llama_config_from_hf(hf_cfg)
        assert cfg.dim == 32 and cfg.n_kv_heads == 2 and cfg.mlp_dim == 64
        assert cfg.rope_theta == 10_000.0

    def test_tied_embeddings_fallback(self, tiny_hf_llama):
        model, hf_cfg = tiny_hf_llama
        cfg = llama_config_from_hf(hf_cfg, dtype="float32")
        sd = {k: v for k, v in model.state_dict().items() if k != "lm_head.weight"}
        params = convert_llama(sd, cfg)
        np.testing.assert_array_equal(
            params["lm_head"]["kernel"], params["embed_tokens"]["embedding"].T
        )


@pytest.fixture(scope="module")
def tiny_hf_bert():
    cfg = transformers.BertConfig(
        vocab_size=100,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=64,
        max_position_embeddings=64,
        type_vocab_size=2,
        attn_implementation="eager",
    )
    torch.manual_seed(1)
    model = transformers.BertModel(cfg).eval()
    return model, cfg


@pytest.fixture(scope="module")
def tiny_hf_mixtral():
    # vocab 512 ≥ ByteTokenizer's 261 floor so the serving round-trip test
    # can use the default tokenizer
    cfg = transformers.MixtralConfig(
        vocab_size=512,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=64,
        rope_theta=10_000.0,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.MixtralForCausalLM(cfg).eval()
    return model, cfg


class TestMoeConversion:
    def test_logits_match_torch(self, tiny_hf_mixtral):
        """HF Mixtral routes top-k with NO capacity limit; the converter's
        default capacity is no-drop (E/k), so logits must agree as-is."""
        from sentio_tpu.models.convert import convert_moe, moe_config_from_hf
        from sentio_tpu.models.moe import moe_forward

        model, hf_cfg = tiny_hf_mixtral
        cfg = moe_config_from_hf(hf_cfg, dtype="float32")
        assert cfg.capacity_factor == cfg.n_experts / cfg.experts_per_token
        params = convert_moe(model.state_dict(), cfg)

        ids = np.array([[1, 5, 9, 2, 77, 33], [3, 8, 120, 4, 6, 11]], np.int32)
        with torch.no_grad():
            ref = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()

        got, _, _ = moe_forward(params, cfg, jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(got), ref, atol=5e-4, rtol=5e-3)

    def test_config_mapping(self, tiny_hf_mixtral):
        from sentio_tpu.models.convert import moe_config_from_hf

        _, hf_cfg = tiny_hf_mixtral
        cfg = moe_config_from_hf(hf_cfg)
        assert cfg.n_experts == 4
        assert cfg.experts_per_token == 2
        assert cfg.dim == 32 and cfg.n_kv_heads == 2

    def test_checkpoint_roundtrip_serves(self, tiny_hf_mixtral, tmp_path):
        """convert → save_pytree → load_model → paged engine greedy."""
        from dataclasses import replace

        from sentio_tpu.config import GeneratorConfig
        from sentio_tpu.models.convert import convert_moe, moe_config_from_hf
        from sentio_tpu.models.moe import moe_serving_forward
        from sentio_tpu.runtime.checkpoint import save_pytree
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.weights import load_decoder, load_model

        model, hf_cfg = tiny_hf_mixtral
        cfg = replace(moe_config_from_hf(hf_cfg, dtype="float32"))
        params = convert_moe(model.state_dict(), cfg)
        ck = str(tmp_path / "moe-ck")
        save_pytree(ck, params, meta={"family": "moe", "config": cfg.__dict__})

        loaded, loaded_cfg, _ = load_model(ck, expect_family="moe")
        assert loaded_cfg.n_experts == cfg.n_experts

        geometry = dict(max_slots=2, page_size=16, max_pages_per_seq=4)
        eng = ContinuousBatchingEngine(
            model_config=loaded_cfg, params=loaded,
            forward_fn=moe_serving_forward, **geometry,
        )
        out = eng.run_all(["hello"], max_new_tokens=6, temperature=0.0)[0]
        assert len(out.tokens) >= 1

        # config-driven path: checkpoint_path alone must auto-select the
        # MoE family from the checkpoint meta (no explicit forward_fn)
        from sentio_tpu.models.moe import MoeConfig

        decoder = load_decoder(GeneratorConfig(checkpoint_path=ck))
        assert isinstance(decoder.model_config, MoeConfig)
        auto = ContinuousBatchingEngine(
            model_config=decoder.model_config, params=decoder.params,
            tokenizer=decoder.tokenizer, **geometry,
        )
        assert auto.forward_fn is moe_serving_forward
        auto_out = auto.run_all(["hello"], max_new_tokens=6, temperature=0.0)[0]
        assert auto_out.tokens == out.tokens


class TestEncoderConversion:
    def test_hidden_states_match_torch(self, tiny_hf_bert):
        model, hf_cfg = tiny_hf_bert
        cfg = encoder_config_from_hf(hf_cfg, dtype="float32")
        params = convert_encoder(model.state_dict(), cfg)

        ids = np.array([[2, 45, 17, 9, 0, 0], [3, 7, 99, 41, 22, 8]], np.int32)
        mask = np.array([[1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 1]], np.int32)
        with torch.no_grad():
            ref = model(
                torch.tensor(ids, dtype=torch.long),
                attention_mask=torch.tensor(mask, dtype=torch.long),
            ).last_hidden_state.numpy()

        from sentio_tpu.models.transformer import encoder_forward

        got = encoder_forward(
            params, cfg, jnp.asarray(ids), jnp.asarray(mask, bool),
            type_ids=jnp.zeros_like(jnp.asarray(ids)),
        )
        # compare only unpadded positions (BERT computes padded ones too but
        # they never feed pooling)
        m = mask.astype(bool)
        np.testing.assert_allclose(np.asarray(got)[m], ref[m], atol=5e-4, rtol=2e-3)

    def test_prefixed_state_dict(self, tiny_hf_bert):
        model, hf_cfg = tiny_hf_bert
        cfg = encoder_config_from_hf(hf_cfg, dtype="float32")
        sd = {f"bert.{k}": v for k, v in model.state_dict().items()}
        params = convert_encoder(sd, cfg)
        assert params["embed_tokens"]["embedding"].shape == (100, 32)


class TestCrossEncoderConversion:
    def test_scores_match_torch_roberta_head(self):
        cfg = transformers.XLMRobertaConfig(
            vocab_size=120,
            hidden_size=32,
            num_hidden_layers=2,
            num_attention_heads=4,
            intermediate_size=64,
            max_position_embeddings=66,  # usable 64 after the 2-slot offset
            type_vocab_size=1,
            num_labels=1,
            pad_token_id=1,
            attn_implementation="eager",
        )
        torch.manual_seed(2)
        model = transformers.XLMRobertaForSequenceClassification(cfg).eval()

        enc_cfg = encoder_config_from_hf(cfg, dtype="float32")
        assert enc_cfg.max_len == 64
        params = convert_cross_encoder(model.state_dict(), enc_cfg, position_offset=2)
        assert "pooler" in params

        ids = np.array([[0, 45, 17, 9, 2], [0, 7, 99, 41, 2]], np.int32)
        mask = np.ones_like(ids)
        with torch.no_grad():
            ref = model(
                torch.tensor(ids, dtype=torch.long),
                attention_mask=torch.tensor(mask, dtype=torch.long),
            ).logits.numpy()[:, 0]

        from sentio_tpu.models.cross_encoder import cross_encoder_scores

        got = cross_encoder_scores(
            params, enc_cfg, jnp.asarray(ids), jnp.asarray(mask, bool),
            type_ids=jnp.zeros_like(jnp.asarray(ids)),
        )
        np.testing.assert_allclose(np.asarray(got), ref, atol=5e-4, rtol=2e-3)


class TestDtypeStorage:
    def test_load_dir_casts_to_requested_dtype(self, tiny_hf_llama, tmp_path):
        """--dtype bfloat16 must reach the stored arrays (half the disk/RAM
        for 8B-class checkpoints), not just the config metadata."""
        model, _ = tiny_hf_llama
        src = tmp_path / "hf"
        model.save_pretrained(src)

        from sentio_tpu.models.convert import load_llama_dir

        params, cfg = load_llama_dir(src, dtype="bfloat16")
        assert str(params["embed_tokens"]["embedding"].dtype) == "bfloat16"
        assert str(params["layers_0"]["attn"]["wq"]["kernel"].dtype) == "bfloat16"

        params32, _ = load_llama_dir(src, dtype="float32")
        assert params32["lm_head"]["kernel"].dtype == np.float32
