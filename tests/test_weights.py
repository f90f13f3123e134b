"""Checkpoint → serving wiring: `cli convert` output loads back through
config (checkpoint_path/tokenizer_path) into live engine/embedder/reranker
instances with real weights and a real HF tokenizer — the full "switch from
hosted APIs to in-process models" path."""

from __future__ import annotations

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from sentio_tpu.config import EmbedderConfig, GeneratorConfig, RerankConfig  # noqa: E402
from sentio_tpu.runtime.checkpoint import save_pytree  # noqa: E402
from sentio_tpu.runtime.weights import WeightsError, load_model  # noqa: E402


@pytest.fixture(scope="module")
def hf_tokenizer_dir(tmp_path_factory):
    """A real HF tokenizer built fully offline (WordLevel over a tiny vocab)."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    words = ["hello", "world", "tpu", "matrix", "the", "what", "is", "a"]
    vocab = {"<pad>": 0, "<s>": 1, "</s>": 2, "<unk>": 3}
    for w in words:
        vocab[w] = len(vocab)
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    fast = transformers.PreTrainedTokenizerFast(
        tokenizer_object=tok, pad_token="<pad>", bos_token="<s>",
        eos_token="</s>", unk_token="<unk>",
    )
    d = tmp_path_factory.mktemp("hf_tok")
    fast.save_pretrained(d)
    return str(d)


@pytest.fixture(scope="module")
def llama_ckpt(tmp_path_factory):
    from sentio_tpu.models.convert import convert_llama, llama_config_from_hf

    cfg = transformers.LlamaConfig(
        vocab_size=32, hidden_size=16, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=64,
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg)
    our_cfg = llama_config_from_hf(cfg, dtype="float32")
    params = convert_llama(model.state_dict(), our_cfg)
    d = tmp_path_factory.mktemp("ck") / "llama"
    save_pytree(d, params, meta={"family": "llama", "config": our_cfg.__dict__})
    return str(d)


class TestLoadModel:
    def test_loads_params_config_tokenizer(self, llama_ckpt, hf_tokenizer_dir):
        params, cfg, tok = load_model(
            llama_ckpt, expect_family="llama", tokenizer_path=hf_tokenizer_dir
        )
        assert cfg.dim == 16 and cfg.n_kv_heads == 1
        assert params["embed_tokens"]["embedding"].shape == (32, 16)
        assert tok is not None and tok.encode("hello world") != []

    def test_family_mismatch_raises(self, llama_ckpt):
        with pytest.raises(WeightsError):
            load_model(llama_ckpt, expect_family="encoder")

    def test_missing_checkpoint_raises(self, tmp_path):
        with pytest.raises(WeightsError):
            load_model(str(tmp_path / "nope"))

    def test_oversized_tokenizer_rejected(self, tmp_path, llama_ckpt):
        """A tokenizer with more ids than the model vocab would index out of
        bounds on device — refuse at load time."""
        from tokenizers import Tokenizer, models, pre_tokenizers

        vocab = {f"w{i}": i for i in range(64)}  # > model vocab of 32
        vocab["<unk>"] = 64
        tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
        tok.pre_tokenizer = pre_tokenizers.Whitespace()
        fast = transformers.PreTrainedTokenizerFast(tokenizer_object=tok, unk_token="<unk>")
        d = tmp_path / "big_tok"
        fast.save_pretrained(d)
        with pytest.raises(WeightsError):
            load_model(llama_ckpt, tokenizer_path=str(d))


class TestEngineFromCheckpoint:
    def test_generate_with_converted_weights(self, llama_ckpt, hf_tokenizer_dir):
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.weights import load_decoder

        decoder = load_decoder(GeneratorConfig(
            checkpoint_path=llama_ckpt, tokenizer_path=hf_tokenizer_dir,
        ))
        assert decoder.model_config.dim == 16  # config came from the checkpoint
        engine = ContinuousBatchingEngine(
            model_config=decoder.model_config, params=decoder.params,
            tokenizer=decoder.tokenizer, max_slots=2, page_size=8,
            max_pages_per_seq=4,
        )
        out = engine.run_all(["hello world"], max_new_tokens=4)
        assert len(out) == 1 and isinstance(out[0].text, str)

    def test_embedder_from_checkpoint(self, tmp_path, hf_tokenizer_dir):
        from sentio_tpu.models.convert import convert_encoder, encoder_config_from_hf
        from sentio_tpu.ops.embedder import TpuEmbedder

        cfg = transformers.BertConfig(
            vocab_size=32, hidden_size=16, num_hidden_layers=1,
            num_attention_heads=2, intermediate_size=32,
            max_position_embeddings=64, type_vocab_size=2,
        )
        torch.manual_seed(1)
        our_cfg = encoder_config_from_hf(cfg, dtype="float32")
        params = convert_encoder(transformers.BertModel(cfg).state_dict(), our_cfg)
        d = tmp_path / "enc"
        save_pytree(d, params, meta={"family": "encoder", "config": our_cfg.__dict__})

        emb = TpuEmbedder(EmbedderConfig(
            provider="tpu", checkpoint_path=str(d), tokenizer_path=hf_tokenizer_dir,
        ))
        vec = emb.embed("hello tpu world")
        assert vec.shape == (16,)
        assert np.isfinite(vec).all()
        np.testing.assert_allclose(np.linalg.norm(vec), 1.0, rtol=1e-4)

    def test_reranker_from_checkpoint(self, tmp_path, hf_tokenizer_dir, docs):
        from sentio_tpu.models.convert import convert_cross_encoder, encoder_config_from_hf
        from sentio_tpu.ops.reranker import CrossEncoderReranker

        cfg = transformers.XLMRobertaConfig(
            vocab_size=32, hidden_size=16, num_hidden_layers=1,
            num_attention_heads=2, intermediate_size=32,
            max_position_embeddings=66, type_vocab_size=1, num_labels=1,
            pad_token_id=1,
        )
        torch.manual_seed(2)
        model = transformers.XLMRobertaForSequenceClassification(cfg)
        our_cfg = encoder_config_from_hf(cfg, dtype="float32")
        params = convert_cross_encoder(model.state_dict(), our_cfg, position_offset=2)
        d = tmp_path / "xenc"
        save_pytree(d, params, meta={"family": "cross-encoder", "config": our_cfg.__dict__})

        rr = CrossEncoderReranker(RerankConfig(
            checkpoint_path=str(d), tokenizer_path=hf_tokenizer_dir, batch_size=4,
        ))
        result = rr.rerank("what is a tpu", docs[:4], top_k=2)
        assert len(result.documents) == 2
        assert all(np.isfinite(s) for s in result.scores)
