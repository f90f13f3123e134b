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
