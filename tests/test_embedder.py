import numpy as np
import pytest

from sentio_tpu.config import EmbedderConfig
from sentio_tpu.ops.embedder import (
    EmbeddingCache,
    HashEmbedder,
    TpuEmbedder,
    get_embedder,
)


class TestEmbeddingCache:
    def test_hit_miss_and_stats(self):
        cache = EmbeddingCache(max_size=10, ttl_s=100)
        assert cache.get("a") is None
        cache.put("a", np.ones(4, np.float32))
        assert cache.get("a") is not None
        stats = cache.stats()
        assert stats["hits"] == 1 and stats["misses"] == 1

    def test_lfu_eviction(self):
        cache = EmbeddingCache(max_size=2, ttl_s=0)
        cache.put("hot", np.zeros(2))
        cache.put("cold", np.ones(2))
        for _ in range(5):
            cache.get("hot")
        cache.put("new", np.full(2, 2.0))  # evicts "cold" (fewest hits)
        assert cache.get("hot") is not None
        assert cache.get("cold") is None

    def test_ttl_expiry(self, monkeypatch):
        import time as time_mod

        cache = EmbeddingCache(max_size=10, ttl_s=1.0)
        cache.put("x", np.zeros(2))
        # TTLs clock on the monotonic perf_counter (NTP-step immune)
        real = time_mod.perf_counter()
        monkeypatch.setattr(
            "sentio_tpu.ops.embedder.time.perf_counter", lambda: real + 10
        )
        assert cache.get("x") is None


class TestHashEmbedder:
    def test_deterministic_and_normalized(self):
        emb = HashEmbedder(EmbedderConfig(provider="hash", dim=64))
        a = emb.embed("hello world")
        b = emb.embed("hello world")
        np.testing.assert_array_equal(a, b)
        assert a.shape == (64,)
        assert abs(np.linalg.norm(a) - 1.0) < 1e-5

    def test_related_texts_correlate(self):
        emb = HashEmbedder(EmbedderConfig(provider="hash", dim=256))
        base = emb.embed("the quick brown fox jumps")
        related = emb.embed("the quick brown fox runs")
        unrelated = emb.embed("quantum chromodynamics lattice")
        assert float(base @ related) > float(base @ unrelated)

    def test_cache_and_stats(self):
        emb = HashEmbedder(EmbedderConfig(provider="hash", dim=32))
        emb.embed_many(["a", "b"])
        emb.embed_many(["a", "c"])  # "a" cached
        stats = emb.get_stats()
        assert stats["requests"] == 2
        assert stats["texts"] == 4
        assert stats["cache"]["hits"] == 1

    def test_warm_up(self):
        emb = HashEmbedder(EmbedderConfig(provider="hash", dim=16))
        assert emb.warm_up() is True

    def test_async_paths(self):
        import asyncio

        emb = HashEmbedder(EmbedderConfig(provider="hash", dim=16))

        async def run():
            one = await emb.embed_async("solo")
            many = await emb.embed_many_async(["x", "y"])
            return one, many

        one, many = asyncio.run(run())
        assert one.shape == (16,) and many.shape == (2, 16)


class TestTpuEmbedder:
    @pytest.fixture(scope="class")
    def embedder(self):
        return TpuEmbedder(EmbedderConfig(provider="tpu", model_preset="tiny", batch_size=8))

    def test_shapes_and_norm(self, embedder):
        out = embedder.embed_many(["short", "a rather longer sentence here"])
        assert out.shape == (2, embedder.dimension)
        np.testing.assert_allclose(np.linalg.norm(out, axis=1), 1.0, rtol=1e-4)

    def test_deterministic(self, embedder):
        a = embedder.embed("same text")
        embedder.cache = EmbeddingCache(10, 0)  # bypass cache
        b = embedder.embed("same text")
        np.testing.assert_allclose(a, b, atol=1e-6)

    def test_bucketing_stable(self, embedder):
        """Same text must embed identically whatever batch it rides in
        (padding/bucketing must not leak into results)."""
        solo = embedder.embed("invariant text")
        embedder.cache = EmbeddingCache(10, 0)
        batched = embedder.embed_many(["invariant text", "x" * 200])[0]
        np.testing.assert_allclose(solo, batched, atol=1e-5)


def test_registry_rejects_unknown_provider():
    """A typo in EMBEDDER_PROVIDER must not quietly serve the hash fake."""
    with pytest.raises(ValueError, match="unknown embedder provider"):
        get_embedder(EmbedderConfig(provider="unknown-thing", dim=8))
    assert isinstance(get_embedder(EmbedderConfig(provider="hash", dim=8)), HashEmbedder)


def test_batch_bucketing_avoids_recompiles():
    """Distinct miss-counts within one batch bucket must reuse one program."""
    import jax

    emb = TpuEmbedder(EmbedderConfig(provider="tpu", model_preset="tiny", batch_size=8))
    emb.embed_many(["a", "b", "c"])  # compiles (B=4 bucket, seq=16 bucket)
    compiled = emb._fwd._cache_size() if hasattr(emb._fwd, "_cache_size") else None
    emb.cache = EmbeddingCache(10, 0)
    emb.embed_many(["d", "e", "f", "g"])  # same B=4 bucket -> no new compile
    if compiled is not None:
        assert emb._fwd._cache_size() == compiled


class TestEmbedDevice:
    def test_embed_device_matches_embed_many(self, settings):
        from sentio_tpu.config import EmbedderConfig
        from sentio_tpu.models.transformer import EncoderConfig
        from sentio_tpu.ops.embedder import TpuEmbedder

        emb = TpuEmbedder(EmbedderConfig(provider="tpu", dim=64),
                          model_config=EncoderConfig.tiny())
        texts = ["the quick fox", "jax compiles to xla"]
        dev = np.asarray(emb.embed_device(texts), np.float32)
        host = emb.embed_many(texts)
        np.testing.assert_allclose(dev, host, atol=1e-5)

    def test_embed_device_cache_hit_path(self, settings):
        import time

        from sentio_tpu.config import EmbedderConfig
        from sentio_tpu.models.transformer import EncoderConfig
        from sentio_tpu.ops.embedder import TpuEmbedder

        emb = TpuEmbedder(EmbedderConfig(provider="tpu", dim=64),
                          model_config=EncoderConfig.tiny())
        emb.embed_many(["warm me"])  # populates cache synchronously
        out = emb.embed_device(["warm me"])
        assert isinstance(out, np.ndarray)  # served from cache, no device call

        # miss path fills the cache from the background thread
        emb.embed_device(["fresh text"])
        for _ in range(50):
            if emb.cache.get("fresh text") is not None:
                break
            time.sleep(0.05)
        assert emb.cache.get("fresh text") is not None


# ------------------------------------------------- weights held for serving
#
# PR 52: a serving class holds each leaf in the dtype its forward uses it in,
# cast once at load (models/transformer.py::serving_dtypes); the forward's
# ``astype`` of the dtype held emits nothing, and the numbers are the ones the
# float32 leaves gave when they were cast at every use.


def _float32_parts(tree, under=False):
    """(path, leaf, is it one the forward uses in float32) of a param tree."""
    for key, child in tree.items():
        f32 = under or "scale" in tree or key in ("head", "pooler")
        if isinstance(child, dict):
            yield from _float32_parts(child, f32)
        else:
            yield key, child, f32


class TestWeightsHeldForServing:
    TEXTS = ["what compiles to xla?", "a rather longer sentence " * 6, "x"]

    def test_embeddings_are_those_of_the_float32_leaves_bit_for_bit(self):
        import jax

        from sentio_tpu.models.transformer import EncoderConfig, init_encoder

        cfg = EncoderConfig.tiny()
        params = init_encoder(jax.random.PRNGKey(3), cfg)
        config = EmbedderConfig(provider="tpu", model_preset="tiny", cache_size=0)
        held = TpuEmbedder(config, params=params, model_config=cfg)
        wide = TpuEmbedder(config, model_config=cfg)
        wide.params = jax.device_put(params)  # float32, cast at use: the parent's embedder
        assert str(held.params["embed_tokens"]["embedding"].dtype) == "bfloat16"
        np.testing.assert_array_equal(held.embed_many(self.TEXTS), wide.embed_many(self.TEXTS))
        np.testing.assert_array_equal(np.asarray(held.embed_device(self.TEXTS)),
                                      np.asarray(wide.embed_device(self.TEXTS)))
        # the caller's tree is the caller's still
        assert all(str(leaf.dtype) == "float32" for leaf in jax.tree_util.tree_leaves(params))

    def test_rerank_scores_are_those_of_the_float32_leaves_bit_for_bit(self):
        import jax

        from sentio_tpu.config import RerankConfig
        from sentio_tpu.models.cross_encoder import init_cross_encoder
        from sentio_tpu.models.document import Document
        from sentio_tpu.models.transformer import EncoderConfig
        from sentio_tpu.ops.reranker import CrossEncoderReranker

        cfg = EncoderConfig.tiny()
        params = init_cross_encoder(jax.random.PRNGKey(5), cfg)
        params["pooler"] = {"kernel": params["encoder"]["layers_0"]["attn"]["wq"]["kernel"] * 3.0,
                            "bias": params["encoder"]["layers_0"]["attn"]["wq"]["bias"] + 0.1}
        held = CrossEncoderReranker(RerankConfig(), params=params, model_config=cfg)
        wide = CrossEncoderReranker(RerankConfig(), model_config=cfg)
        wide.params = jax.device_put(params)
        docs = [Document(text=t) for t in self.TEXTS]
        np.testing.assert_array_equal(held._score("what is xla", docs), wide._score("what is xla", docs))
        assert held.param_dtype == "bfloat16"
        assert held.param_bytes < 0.6 * sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params))

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("source", ["init", "checkpoint"])
    def test_every_leaf_is_held_in_the_dtype_the_forward_uses_it_in(self, source, dtype):
        """Norms, pooler and head float32, everything else ``cfg.jdtype`` — from
        float32 device leaves (``init_cross_encoder``) and from the host leaves
        of a checkpoint whose matrices are bf16 and whose biases are float32
        (benchmark/families/cross_encoder.py), which stay on the host."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        from sentio_tpu.models.cross_encoder import init_cross_encoder
        from sentio_tpu.models.transformer import EncoderConfig, param_summary, serving_dtypes

        cfg = dataclasses.replace(EncoderConfig.tiny(), dtype=dtype)
        tree = init_cross_encoder(jax.random.PRNGKey(1), cfg)
        if source == "checkpoint":
            tree = jax.tree_util.tree_map(
                lambda leaf: np.asarray(leaf.astype(jnp.bfloat16) if leaf.ndim == 2 else leaf), tree)
        before = param_summary(tree)[1]
        held, cast, given_back = serving_dtypes(tree, cfg, owned=True)
        assert held is tree  # an owned tree's containers are reused: each wide leaf is let go as it is cast
        for key, leaf, f32 in _float32_parts(held):
            assert str(leaf.dtype) == ("float32" if f32 else dtype), key
            assert isinstance(leaf, np.ndarray) == (source == "checkpoint"), key
        assert param_summary(held) == (dtype, before - given_back)
        assert (cast > 0) == (given_back != 0) == ((source, dtype) != ("init", "float32"))
        assert serving_dtypes(held, cfg)[1:] == (0, 0)  # and a second pass finds nothing to do


def test_embed_gathers_before_it_casts():
    """``L.embed`` over a table wider than the compute dtype converts the rows
    it looked up, never the table: the TPU compiler keeps the order it is
    given, and the other order converted 1.0 GB to embed one query."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models import layers as L

    table, ids = jnp.zeros((512, 64), jnp.float32), jnp.zeros((2, 3), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda t, i: L.embed({"embedding": t}, i, jnp.bfloat16))(table, ids)
    converts = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "convert_element_type"]
    assert [eqn.invars[0].aval.shape for eqn in converts] == [(2, 3, 64)]
    assert jaxpr.jaxpr.invars[0] not in [eqn.invars[0] for eqn in converts]
    # and of a table already in the compute dtype nothing is converted at all
    same = jax.make_jaxpr(lambda t, i: L.embed({"embedding": t}, i, jnp.bfloat16))(
        table.astype(jnp.bfloat16), ids)
    assert "convert_element_type" not in str(same)
