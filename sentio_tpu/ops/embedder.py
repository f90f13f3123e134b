"""Embedding service: in-process bi-encoder on the device mesh.

Replaces the reference's remote Jina embeddings API
(/root/reference/src/core/embeddings/providers/jina.py:33) and reproduces its
service contract from the embedder base class (embeddings/base.py:23-423):
LFU+TTL embedding cache, request/hit/error stats, sync + async entry points,
``warm_up`` probe, lazy ``dimension``. Two providers, selected by config:

* ``tpu`` — the Flax-free JAX bi-encoder (models/transformer.py), tokenized
  host-side, batched and bucketed, jitted once per bucket shape.
* ``hash`` — deterministic seeded pseudo-vectors, the reference's offline
  mock mode (jina.py:141-159) kept as the no-hardware test backend.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
import threading
import time
from typing import Optional, Sequence

import numpy as np

from sentio_tpu.config import EmbedderConfig, get_settings
from sentio_tpu.infra import faults
from sentio_tpu.infra.tracing import annotation, current, dispatching

logger = logging.getLogger(__name__)


class EmbeddingError(Exception):
    pass


class EmbeddingCache:
    """LFU with TTL, thread-safe (reference: embeddings/base.py:23-106)."""

    def __init__(self, max_size: int = 10_000, ttl_s: float = 3600.0) -> None:
        self.max_size = max_size
        self.ttl_s = ttl_s
        self._store: dict[str, tuple[np.ndarray, float, int]] = {}  # key -> (vec, t, hits)
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key(text: str) -> str:
        return hashlib.sha256(text.encode()).hexdigest()

    def get(self, text: str) -> Optional[np.ndarray]:
        k = self.key(text)
        with self._lock:
            entry = self._store.get(k)
            if entry is None:
                self.misses += 1
                return None
            vec, t, hits = entry
            if self.ttl_s > 0 and time.perf_counter() - t > self.ttl_s:
                del self._store[k]
                self.misses += 1
                return None
            self._store[k] = (vec, t, hits + 1)
            self.hits += 1
            return vec

    def put(self, text: str, vec: np.ndarray) -> None:
        if self.max_size <= 0:  # caching disabled
            return
        k = self.key(text)
        with self._lock:
            if len(self._store) >= self.max_size and k not in self._store:
                # evict least-frequently-used
                victim = min(self._store.items(), key=lambda kv: kv[1][2])[0]
                del self._store[victim]
            self._store[k] = (vec, time.perf_counter(), 0)

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {
                "size": len(self._store),
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": round(self.hits / total, 4) if total else 0.0,
            }


class BaseEmbedder:
    """Common service wrapper: cache, stats, sync/async, warm-up."""

    def __init__(self, config: Optional[EmbedderConfig] = None) -> None:
        self.config = config or get_settings().embedder
        self.cache = EmbeddingCache(self.config.cache_size, self.config.cache_ttl_s)
        self.stats = {"requests": 0, "texts": 0, "errors": 0, "time_s": 0.0}

    @property
    def dimension(self) -> int:
        return self.config.dim

    # -- provider hook -------------------------------------------------------

    def _embed_batch(self, texts: list[str]) -> np.ndarray:  # [B, dim] float32
        raise NotImplementedError

    # -- public API ----------------------------------------------------------

    def embed_many(self, texts: Sequence[str]) -> np.ndarray:
        faults.hit("embedder.batch")
        t0 = time.perf_counter()
        self.stats["requests"] += 1
        self.stats["texts"] += len(texts)
        out = np.zeros((len(texts), self.dimension), np.float32)
        missing: list[tuple[int, str]] = []
        for i, text in enumerate(texts):
            cached = self.cache.get(text)
            if cached is not None:
                out[i] = cached
            else:
                missing.append((i, text))
        try:
            for start in range(0, len(missing), self.config.batch_size):
                chunk = missing[start : start + self.config.batch_size]
                vecs = self._embed_batch([t for _, t in chunk])
                for (i, text), vec in zip(chunk, vecs):
                    out[i] = vec
                    self.cache.put(text, vec)
        except Exception:
            self.stats["errors"] += 1
            raise
        finally:
            self.stats["time_s"] += time.perf_counter() - t0
        return out

    def embed(self, text: str) -> np.ndarray:
        return self.embed_many([text])[0]

    async def embed_many_async(self, texts: Sequence[str]) -> np.ndarray:
        return await asyncio.get_running_loop().run_in_executor(
            None, self.embed_many, list(texts)
        )

    async def embed_async(self, text: str) -> np.ndarray:
        return (await self.embed_many_async([text]))[0]

    def warm_up(self) -> bool:
        """Probe with a trivial input (reference: base.py:387-416); also
        triggers jit compilation so the first real request doesn't pay it."""
        try:
            vec = self.embed("warm up probe")
            return vec.shape == (self.dimension,)
        except Exception:  # noqa: BLE001 — any probe failure means "unhealthy"
            return False

    def get_stats(self) -> dict:
        return {**self.stats, "cache": self.cache.stats()}


class HashEmbedder(BaseEmbedder):
    """Deterministic hash-seeded unit vectors — same trick as the reference's
    empty-API-key mock mode. Texts sharing content always embed identically,
    so retrieval tests are reproducible with zero hardware."""

    def _embed_batch(self, texts: list[str]) -> np.ndarray:
        out = np.zeros((len(texts), self.dimension), np.float32)
        for i, text in enumerate(texts):
            seed = int.from_bytes(hashlib.sha256(text.lower().encode()).digest()[:8], "little")
            rng = np.random.default_rng(seed)
            vec = rng.standard_normal(self.dimension).astype(np.float32)
            # mix in token-level signal so related texts correlate; sorted so
            # float summation order (and thus the vector) is identical across
            # processes regardless of PYTHONHASHSEED
            for tok in sorted(set(text.lower().split())):
                tseed = int.from_bytes(hashlib.md5(tok.encode()).digest()[:8], "little")
                trng = np.random.default_rng(tseed)
                vec += 4.0 * trng.standard_normal(self.dimension).astype(np.float32)
            out[i] = vec / max(np.linalg.norm(vec), 1e-9)
        return out


class TpuEmbedder(BaseEmbedder):
    """The real path: tokenize host-side, run the bi-encoder on device.

    Sequences bucket to powers of two (one compiled program per bucket);
    params live on the mesh (replicated by default — the encoder is small
    relative to HBM; flip to ENCODER_TP_RULES for TP).
    """

    BUCKETS = (16, 32, 64, 128, 256, 512)
    BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

    def __init__(
        self,
        config: Optional[EmbedderConfig] = None,
        params=None,
        model_config=None,
        tokenizer=None,
        mesh=None,
    ) -> None:
        super().__init__(config)
        import jax

        from sentio_tpu.models.tokenizer import ByteTokenizer
        from sentio_tpu.models.transformer import (
            EncoderConfig,
            encoder_forward,
            init_encoder,
            mean_pool,
        )

        owned = params is None  # a tree made here is this object's alone
        if params is None and self.config.checkpoint_path:
            # real weights: a `cli convert encoder` checkpoint + HF tokenizer
            from sentio_tpu.runtime.weights import load_model

            params, model_config, ck_tok = load_model(
                self.config.checkpoint_path, expect_family="encoder",
                tokenizer_path=self.config.tokenizer_path,
            )
            tokenizer = tokenizer or ck_tok
        self.model_config = model_config or (
            EncoderConfig.tiny() if self.config.model_preset == "tiny" else EncoderConfig.base()
        )
        self.tokenizer = tokenizer or ByteTokenizer(self.model_config.vocab_size)
        if params is None:
            params = init_encoder(jax.random.PRNGKey(0), self.model_config)
        from sentio_tpu.parallel.sharding import place_encoder

        # held in the dtype the forward computes in, cast once, here: a weight
        # cast at use is a weight converted on every call (`/info` says both)
        self.params, self.param_dtype, self.param_bytes = place_encoder(
            "embedder", params, self.model_config, mesh, owned)
        self.mesh = mesh

        cfg = self.model_config
        # bidirectional flash kernel for the encoder pass — policy lives in
        # kernels.select_encoder_attn_fn (shared with the cross-encoder)
        from sentio_tpu.kernels import select_encoder_attn_fn

        attn_fn = select_encoder_attn_fn(mesh, cfg.n_heads)

        def fwd(p, ids, mask):
            return mean_pool(
                encoder_forward(p, cfg, ids, mask, attn_fn=attn_fn), mask
            )

        self._fwd = jax.jit(fwd)

        # built eagerly (no lazy-init race); the dispatcher thread itself
        # only starts on first submit
        self._query_batcher = None
        if self.config.coalesce:
            from sentio_tpu.parallel.batcher import ThreadBatcher

            def process(batch: list[tuple]):
                # (text, the request and span it came from): every request
                # of a coalesced batch is given the batch's device time
                out = self._embed_device_batch(
                    [text for text, _sp in batch], [sp for _text, sp in batch])
                # each caller gets its own [1, D] device slice (no download)
                return [out[i : i + 1] for i in range(len(batch))]

            self._query_batcher = ThreadBatcher(
                process,
                max_size=self.config.coalesce_max,
                deadline_ms=self.config.coalesce_deadline_ms,
                name="embed-coalescer",
            )

    def close(self) -> None:
        """Stop the coalescer dispatcher thread (container cleanup)."""
        if self._query_batcher is not None:
            self._query_batcher.close()

    @property
    def dimension(self) -> int:
        return self.model_config.dim

    def get_stats(self) -> dict:
        stats = super().get_stats()
        if self._query_batcher is not None:
            stats["coalescer"] = self._query_batcher.stats.snapshot()
        return stats

    def _embed_batch(self, texts: list[str]) -> np.ndarray:
        import jax.numpy as jnp

        from sentio_tpu.models.tokenizer import batch_encode
        from sentio_tpu.parallel.batcher import bucket_size

        ids, mask = batch_encode(
            self.tokenizer, texts, max_len=min(self.config.max_tokens, self.model_config.max_len)
        )
        # pad seq AND batch to buckets so jit compiles once per bucket pair,
        # not once per (n_texts, longest_text) combination
        n = ids.shape[0]
        width = bucket_size(ids.shape[1], self.BUCKETS)
        rows = bucket_size(n, self.BATCH_BUCKETS)
        ids = np.pad(
            ids, ((0, rows - n), (0, width - ids.shape[1])),
            constant_values=self.tokenizer.pad_id,
        )
        mask = np.pad(mask, ((0, rows - n), (0, width - mask.shape[1])))
        with annotation("embed.dispatch", rows=rows, width=width), \
                dispatching("embed", spans=[current()]) as stamp:
            out = stamp.out = self._fwd(self.params, jnp.asarray(ids), jnp.asarray(mask))
        with annotation("embed.fetch"):
            return np.asarray(out, np.float32)[:n]

    def embed_device(self, texts: list[str]):
        """Embed → [n, D] array WITHOUT a blocking host download. The dense
        retrieval leg chains this straight into the index's top-k program so
        the query vector never makes a blocking host round trip.

        Single-query calls (the /chat hot path — one worker thread per
        request) coalesce across threads through a deadline batcher so
        concurrent requests share ONE padded device batch; multi-text calls
        are already a batch and dispatch directly.

        Cache contract matches :meth:`embed_many`: full-hit batches return
        cached host vectors (no device work at all); misses compute on
        device and the cache is populated from a BACKGROUND thread so the
        fetch never blocks this request."""
        cached = [self.cache.get(t) for t in texts]
        if all(c is not None for c in cached):
            self.stats["cache_hits"] = self.stats.get("cache_hits", 0) + len(texts)
            return np.stack(cached).astype(np.float32)

        if len(texts) == 1 and self._query_batcher is not None:
            return self._query_batcher.submit((texts[0], current()))
        return self._embed_device_batch(texts)

    def _embed_device_batch(self, texts: list[str], spans: Sequence[tuple] = ()):
        import jax.numpy as jnp

        from sentio_tpu.models.tokenizer import batch_encode
        from sentio_tpu.parallel.batcher import bucket_size

        ids, mask = batch_encode(
            self.tokenizer, texts, max_len=min(self.config.max_tokens, self.model_config.max_len)
        )
        n = ids.shape[0]
        width = bucket_size(ids.shape[1], self.BUCKETS)
        rows = bucket_size(n, self.BATCH_BUCKETS)
        ids = np.pad(
            ids, ((0, rows - n), (0, width - ids.shape[1])),
            constant_values=self.tokenizer.pad_id,
        )
        mask = np.pad(mask, ((0, rows - n), (0, width - mask.shape[1])))
        # the blocking half is the consumer's (ops/dense_index.py, embed.fetch)
        with annotation("embed.dispatch", rows=rows, width=width), \
                dispatching("embed", spans=spans or [current()]) as stamp:
            stamp.out = self._fwd(self.params, jnp.asarray(ids), jnp.asarray(mask))
            out = stamp.out[:n]

        if self.cache.max_size > 0:  # cache off → skip the device download

            def fill_cache() -> None:
                try:
                    host = np.asarray(out, np.float32)  # device fetch can fail
                    for text, vec in zip(texts, host):
                        self.cache.put(text, vec)
                except Exception as exc:  # best-effort, but never silent
                    logger.warning("embed_device background cache fill failed: %s", exc)

            threading.Thread(target=fill_cache, name="embedder-cache-fill",
                             daemon=True).start()
        return out


_PROVIDERS = {"hash": HashEmbedder, "tpu": TpuEmbedder}


def get_embedder(config: Optional[EmbedderConfig] = None, **kwargs) -> BaseEmbedder:
    """Provider registry (reference: embeddings/factory.py:55-120). An
    unknown provider is an error, as in ``get_reranker``: a typo must not
    quietly serve the hash fake in place of the model."""
    config = config or get_settings().embedder
    cls = _PROVIDERS.get(config.provider)
    if cls is None:
        raise ValueError(
            f"unknown embedder provider {config.provider!r}; "
            f"known: {sorted(_PROVIDERS)}"
        )
    return cls(config, **kwargs) if cls is TpuEmbedder else cls(config)
