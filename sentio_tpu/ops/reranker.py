"""Rerankers: TPU cross-encoder scoring with the reference's fallback contract.

Parity with /root/reference/src/core/rerankers/: the ``Reranker`` interface
(base.py:85-131), the registry (``__init__.py:11-30``), and the Jina
reranker's degradation contract (jina_reranker.py:297-322) — on ANY failure
the original order is kept with decaying scores ``1.0 - 0.1*idx``. The
remote API call is replaced by one batched cross-encoder forward: all
(query, doc) pairs ride a single device dispatch (jina_reranker.py:120-154
became models/cross_encoder.py scoring).
"""

from __future__ import annotations

import asyncio
import logging
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from sentio_tpu.config import RerankConfig, get_settings
from sentio_tpu.infra import faults
from sentio_tpu.infra.tracing import current, dispatching, span
from sentio_tpu.models.document import Document

logger = logging.getLogger(__name__)


@dataclass
class RerankingResult:
    documents: list[Document]
    scores: list[float]
    model: str
    fallback_used: bool = False


class Reranker:
    """rerank(query, docs, top_k) → RerankingResult; async executor wrap."""

    name = "base"

    def _score(self, query: str, documents: Sequence[Document]) -> np.ndarray:
        raise NotImplementedError

    def rerank(
        self, query: str, documents: Sequence[Document], top_k: Optional[int] = None
    ) -> RerankingResult:
        documents = list(documents)
        if not documents:
            return RerankingResult([], [], self.name)
        top_k = top_k if top_k is not None else len(documents)
        try:
            faults.hit("reranker.score")
            # the `rerank` request stage: scorer called → scores on the host
            with span("rerank", pairs=len(documents)):
                scores = np.asarray(self._score(query, documents), np.float32)
            if scores.shape != (len(documents),):
                raise ValueError(f"scorer returned shape {scores.shape}")
        except Exception:
            logger.exception("%s rerank failed; keeping original order", self.name)
            return self._default_ranking(documents, top_k)
        order = np.argsort(-scores, kind="stable")[:top_k]
        out_docs, out_scores = [], []
        for i in order:
            doc = documents[int(i)]
            meta = dict(doc.metadata)
            # drop the fused score: Document.score() prefers hybrid_score, and
            # a stale one would make downstream sort-by-score undo the rerank
            meta.pop("hybrid_score", None)
            meta["rerank_score"] = float(scores[int(i)])
            meta["score"] = float(scores[int(i)])
            out_docs.append(Document(text=doc.text, metadata=meta, id=doc.id))
            out_scores.append(float(scores[int(i)]))
        return RerankingResult(out_docs, out_scores, self.name)

    def _default_ranking(self, documents: list[Document], top_k: int) -> RerankingResult:
        """Original order, decaying scores 1.0 − 0.1·idx floored at 0.1."""
        docs, scores = [], []
        for i, doc in enumerate(documents[:top_k]):
            score = max(1.0 - 0.1 * i, 0.1)
            meta = dict(doc.metadata)
            meta.pop("hybrid_score", None)
            meta["rerank_score"] = score
            meta["score"] = score
            docs.append(Document(text=doc.text, metadata=meta, id=doc.id))
            scores.append(score)
        return RerankingResult(docs, scores, self.name, fallback_used=True)

    async def arerank(
        self, query: str, documents: Sequence[Document], top_k: Optional[int] = None
    ) -> RerankingResult:
        # to_thread carries the caller's span context to the `rerank` stage
        return await asyncio.to_thread(self.rerank, query, list(documents), top_k)


class PassthroughReranker(Reranker):
    """Keeps retrieval order (scores preserved) — the USE_RERANKER=false path."""

    name = "passthrough"

    def _score(self, query: str, documents: Sequence[Document]) -> np.ndarray:
        return np.asarray([d.score(1.0 - 0.01 * i) for i, d in enumerate(documents)], np.float32)


class CrossEncoderReranker(Reranker):
    """Batched (query, doc) pair scoring on the device mesh."""

    name = "cross_encoder"

    def __init__(
        self,
        config: Optional[RerankConfig] = None,
        params=None,
        model_config=None,
        tokenizer=None,
        mesh=None,
    ) -> None:
        import jax

        from sentio_tpu.models.cross_encoder import cross_encoder_scores, init_cross_encoder
        from sentio_tpu.models.tokenizer import ByteTokenizer
        from sentio_tpu.models.transformer import EncoderConfig

        self.config = config or get_settings().rerank
        owned = params is None  # a tree made here is this object's alone
        if params is None and self.config.checkpoint_path:
            # real weights: a `cli convert cross-encoder` checkpoint
            from sentio_tpu.runtime.weights import load_model

            params, model_config, ck_tok = load_model(
                self.config.checkpoint_path, expect_family="cross-encoder",
                tokenizer_path=self.config.tokenizer_path,
            )
            tokenizer = tokenizer or ck_tok
        self.model_config = model_config or EncoderConfig.tiny()
        self.tokenizer = tokenizer or ByteTokenizer(self.model_config.vocab_size)
        if params is None:
            params = init_cross_encoder(jax.random.PRNGKey(7), self.model_config)
        from sentio_tpu.parallel.sharding import place_encoder

        # as the embedder's: cast once to what the forward computes in, then placed
        self.params, self.param_dtype, self.param_bytes = place_encoder(
            "reranker", params, self.model_config, mesh, owned)
        cfg = self.model_config
        # bidirectional flash kernel for pair scoring — policy lives in
        # kernels.select_encoder_attn_fn (shared with the embedder)
        from sentio_tpu.kernels import select_encoder_attn_fn

        attn_fn = select_encoder_attn_fn(mesh, cfg.n_heads)

        def fwd(p, ids, mask, types):
            return cross_encoder_scores(p, cfg, ids, mask, types, attn_fn=attn_fn)

        self._fwd = jax.jit(fwd)

    def _score(self, query: str, documents: Sequence[Document]) -> np.ndarray:
        import jax.numpy as jnp

        from sentio_tpu.models.tokenizer import batch_encode_pairs
        from sentio_tpu.parallel.batcher import bucket_size

        max_len = min(self.config.max_pair_tokens, self.model_config.max_len)
        pairs = [(query, d.content) for d in documents]
        scores = np.zeros(len(pairs), np.float32)
        for start in range(0, len(pairs), self.config.batch_size):
            chunk = pairs[start : start + self.config.batch_size]
            ids, mask, types = batch_encode_pairs(self.tokenizer, chunk, max_len)
            rows = bucket_size(len(chunk), (1, 2, 4, 8, 16, 32))
            pad = rows - len(chunk)
            if pad:
                ids = np.pad(ids, ((0, pad), (0, 0)), constant_values=self.tokenizer.pad_id)
                mask = np.pad(mask, ((0, pad), (0, 0)))
                mask[len(chunk):, 0] = True  # keep softmax rows non-degenerate
                types = np.pad(types, ((0, pad), (0, 0)))
            with dispatching("rerank", spans=[current()]) as stamp:
                out = stamp.out = self._fwd(
                    self.params, jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(types))
            scores[start : start + len(chunk)] = np.asarray(out)[: len(chunk)]
        return scores


_RERANKERS = {
    "cross_encoder": CrossEncoderReranker,
    "passthrough": PassthroughReranker,
}


def get_reranker(kind: Optional[str] = None, **kwargs) -> Reranker:
    kind = kind or get_settings().rerank.kind
    cls = _RERANKERS.get(kind)
    if cls is None:
        raise ValueError(f"unknown reranker {kind!r}; known: {sorted(_RERANKERS)}")
    return cls(**kwargs) if cls is CrossEncoderReranker else cls()
