import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentio_tpu.models.cross_encoder import cross_encoder_scores, init_cross_encoder
from sentio_tpu.models.llama import (
    LlamaConfig,
    init_cache,
    init_llama,
    llama_forward,
    llama_loss,
)
from sentio_tpu.models.tokenizer import (
    ByteTokenizer,
    WordHashTokenizer,
    batch_encode,
    batch_encode_pairs,
    get_tokenizer,
)
from sentio_tpu.models.transformer import (
    EncoderConfig,
    encoder_forward,
    init_encoder,
    mean_pool,
)

CFG = LlamaConfig.tiny()
ECFG = EncoderConfig.tiny()
F32_CFG = LlamaConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=128, max_len=256, rope_theta=10_000.0, dtype="float32",
)


@pytest.fixture(scope="module")
def llama_params():
    return init_llama(jax.random.PRNGKey(0), F32_CFG)


def _ids(batch=2, t=12):
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(1, 500, size=(batch, t)), jnp.int32)


class TestTokenizers:
    def test_byte_roundtrip(self):
        tok = ByteTokenizer()
        for text in ("hello world", "naïve café 北京 🚀", ""):
            assert tok.decode(tok.encode(text)) == text

    def test_byte_specials(self):
        tok = ByteTokenizer()
        ids = tok.encode("hi", add_bos=True, add_eos=True)
        assert ids[0] == tok.bos_id and ids[-1] == tok.eos_id
        assert tok.decode(ids) == "hi"  # specials skipped in decode

    def test_hash_deterministic(self):
        tok = WordHashTokenizer()
        assert tok.encode("the quick fox") == tok.encode("The Quick FOX")
        assert tok.encode("a b") != tok.encode("a c")
        assert all(0 <= i < tok.vocab_size for i in tok.encode("x y z"))

    def test_batch_encode_pads_and_masks(self):
        tok = ByteTokenizer()
        ids, mask = batch_encode(tok, ["ab", "abcdef"], max_len=10)
        assert ids.shape == (2, 6)
        assert mask[0].sum() == 2 and mask[1].sum() == 6
        assert (ids[0, 2:] == tok.pad_id).all()

    def test_batch_encode_truncates(self):
        tok = ByteTokenizer()
        ids, mask = batch_encode(tok, ["x" * 100], max_len=8)
        assert ids.shape == (1, 8)

    def test_pair_encoding_structure(self):
        tok = ByteTokenizer()
        ids, mask, types = batch_encode_pairs(tok, [("query", "document")], max_len=32)
        row = ids[0][mask[0]]
        assert row[0] == tok.cls_id
        assert (row == tok.sep_id).sum() == 2
        assert types[0][mask[0]].max() == 1  # second segment marked
        assert types[0][0] == 0

    def test_get_tokenizer_registry(self):
        assert isinstance(get_tokenizer("byte"), ByteTokenizer)
        with pytest.raises(ValueError):
            get_tokenizer("nope")


class TestEncoder:
    def test_forward_shape_and_pooling(self):
        params = init_encoder(jax.random.PRNGKey(1), ECFG)
        ids = _ids(3, 16) % ECFG.vocab_size
        mask = jnp.ones_like(ids, bool)
        hidden = encoder_forward(params, ECFG, ids, mask)
        assert hidden.shape == (3, 16, ECFG.dim)
        emb = mean_pool(hidden, mask)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(emb), axis=-1), 1.0, rtol=1e-5)

    def test_padding_does_not_change_embedding(self):
        cfg = EncoderConfig(vocab_size=512, dim=64, n_layers=2, n_heads=2,
                            mlp_dim=128, max_len=64, dtype="float32")
        params = init_encoder(jax.random.PRNGKey(1), cfg)
        ids = _ids(1, 8) % cfg.vocab_size
        mask = jnp.ones_like(ids, bool)
        emb_short = mean_pool(encoder_forward(params, cfg, ids, mask), mask)
        padded = jnp.pad(ids, ((0, 0), (0, 6)))
        pmask = jnp.pad(mask, ((0, 0), (0, 6)))
        emb_padded = mean_pool(encoder_forward(params, cfg, padded, pmask), pmask)
        np.testing.assert_allclose(np.asarray(emb_short), np.asarray(emb_padded), atol=1e-5)


class TestCrossEncoder:
    def test_scores_shape_and_determinism(self):
        params = init_cross_encoder(jax.random.PRNGKey(2), ECFG)
        tok = ByteTokenizer(vocab_size=512)
        ids, mask, types = batch_encode_pairs(
            tok, [("q one", "doc a"), ("q one", "doc b"), ("q two", "doc c")], 48
        )
        args = (jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(types))
        s1 = cross_encoder_scores(params, ECFG, *args)
        s2 = cross_encoder_scores(params, ECFG, *args)
        assert s1.shape == (3,)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


class TestLlama:
    def test_logits_shape(self, llama_params):
        ids = _ids()
        logits, cache = llama_forward(llama_params, F32_CFG, ids)
        assert logits.shape == (2, 12, F32_CFG.vocab_size)
        assert logits.dtype == jnp.float32
        assert cache is None

    def test_causality(self, llama_params):
        """Changing a future token must not affect earlier logits."""
        ids = _ids(1, 10)
        logits_a, _ = llama_forward(llama_params, F32_CFG, ids)
        altered = ids.at[0, 7].set((ids[0, 7] + 1) % 500)
        logits_b, _ = llama_forward(llama_params, F32_CFG, altered)
        np.testing.assert_allclose(
            np.asarray(logits_a[0, :7]), np.asarray(logits_b[0, :7]), atol=1e-5
        )
        assert not np.allclose(np.asarray(logits_a[0, 7]), np.asarray(logits_b[0, 7]))

    def test_prefill_matches_full_forward(self, llama_params):
        ids = _ids(2, 12)
        full, _ = llama_forward(llama_params, F32_CFG, ids)
        cache = init_cache(F32_CFG, 2, 32)
        pre, cache = llama_forward(llama_params, F32_CFG, ids, cache=cache, cache_index=0)
        np.testing.assert_allclose(np.asarray(full), np.asarray(pre), atol=1e-4)

    def test_incremental_decode_matches_full(self, llama_params):
        """Token-by-token decode through the cache == one full forward."""
        ids = _ids(1, 8)
        full, _ = llama_forward(llama_params, F32_CFG, ids)
        cache = init_cache(F32_CFG, 1, 16)
        step_logits = []
        for t in range(8):
            pos = jnp.full((1, 1), t, jnp.int32)
            lg, cache = llama_forward(
                llama_params, F32_CFG, ids[:, t : t + 1],
                positions=pos, cache=cache, cache_index=t,
            )
            step_logits.append(np.asarray(lg[0, 0]))
        np.testing.assert_allclose(
            np.stack(step_logits), np.asarray(full[0]), atol=1e-4
        )

    def test_cache_not_mutated_in_place(self, llama_params):
        ids = _ids(1, 4)
        cache = init_cache(F32_CFG, 1, 8)
        before = np.asarray(cache["k"]).copy()
        llama_forward(llama_params, F32_CFG, ids, cache=cache, cache_index=0)
        np.testing.assert_array_equal(before, np.asarray(cache["k"]))

    def test_loss_finite_and_masked(self, llama_params):
        ids = _ids(2, 12)
        mask = jnp.ones_like(ids, bool)
        loss = llama_loss(llama_params, F32_CFG, ids, mask)
        assert np.isfinite(float(loss))
        # loss over garbage ~ log(vocab) at init
        assert 3.0 < float(loss) < 9.0

    def test_loss_ignores_padding(self, llama_params):
        ids = _ids(1, 8)
        mask = jnp.ones_like(ids, bool)
        loss_a = llama_loss(llama_params, F32_CFG, ids, mask)
        padded = jnp.pad(ids, ((0, 0), (0, 4)), constant_values=7)
        pmask = jnp.pad(mask, ((0, 0), (0, 4)))
        loss_b = llama_loss(llama_params, F32_CFG, padded, pmask)
        np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-5)


class TestRaggedBatchDecode:
    def test_ragged_decode_matches_solo(self, llama_params):
        """Coalesced sequences of unequal length must decode identically to
        solo runs — per-row cache_index writes each row at its own slot."""
        rng = np.random.default_rng(3)
        seq_a = jnp.asarray(rng.integers(1, 500, (1, 5)), jnp.int32)
        seq_b = jnp.asarray(rng.integers(1, 500, (1, 3)), jnp.int32)

        def solo_next(seq):
            cache = init_cache(F32_CFG, 1, 16)
            lg, _ = llama_forward(llama_params, F32_CFG, seq, cache=cache, cache_index=0)
            return np.asarray(lg[0, seq.shape[1] - 1])

        expected_a, expected_b = solo_next(seq_a), solo_next(seq_b)

        # batched: right-pad to common length, aligned prefill
        lens = jnp.asarray([5, 3], jnp.int32)
        batch = jnp.zeros((2, 5), jnp.int32)
        batch = batch.at[0].set(seq_a[0]).at[1, :3].set(seq_b[0])
        cache = init_cache(F32_CFG, 2, 16)
        lg, cache = llama_forward(llama_params, F32_CFG, batch, cache=cache, cache_index=0)
        got_a = np.asarray(lg[0, 4])
        got_b = np.asarray(lg[1, 2])
        np.testing.assert_allclose(got_a, expected_a, atol=1e-4)
        np.testing.assert_allclose(got_b, expected_b, atol=1e-4)

        # now decode one step per row at its own position/index
        next_tok = jnp.asarray([[int(got_a.argmax())], [int(got_b.argmax())]], jnp.int32)
        lg2, cache = llama_forward(
            llama_params, F32_CFG, next_tok,
            positions=lens[:, None], cache=cache, cache_index=lens,
        )

        # solo continuation for row b (the shorter one, previously corrupted)
        cache_b = init_cache(F32_CFG, 1, 16)
        _, cache_b = llama_forward(llama_params, F32_CFG, seq_b, cache=cache_b, cache_index=0)
        lg_b, _ = llama_forward(
            llama_params, F32_CFG, next_tok[1:2],
            positions=jnp.asarray([[3]]), cache=cache_b, cache_index=3,
        )
        np.testing.assert_allclose(np.asarray(lg2[1, 0]), np.asarray(lg_b[0, 0]), atol=1e-4)
