"""Retrievers: dense (TPU index), sparse (host BM25), and hybrid fusion.

Parity with /root/reference/src/core/retrievers/: ``BaseRetriever`` ABC with
an async wrapper (base.py:29-42), dense retrieval (dense.py:21-119 — but the
embedding is an in-process TPU forward and the store is the in-HBM exact
index instead of Qdrant-over-HTTP), BM25 (sparse.py), and the hybrid fuser
(hybrid.py:48-324) with rrf/weighted_rrf/comb_sum and post-fusion scorer
plugins. The dense and sparse legs run concurrently — device matmul and host
CPU scoring overlap (`asyncio.gather` over the executor).
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from sentio_tpu.config import RetrievalConfig, Settings, get_settings
from sentio_tpu.infra import faults
from sentio_tpu.infra.tracing import span, stamp
from sentio_tpu.models.document import Document
from sentio_tpu.ops.bm25 import BM25Index
from sentio_tpu.ops.dense_index import TpuDenseIndex
from sentio_tpu.ops.fusion import fuse
from sentio_tpu.ops.scorers import ScorerPlugin


class RetrieverError(Exception):
    pass


class BaseRetriever:
    """retrieve(query, top_k) → ranked Documents; aretrieve = executor wrap."""

    name = "base"

    def retrieve(self, query: str, top_k: int = 10) -> list[Document]:
        raise NotImplementedError

    async def aretrieve(self, query: str, top_k: int = 10) -> list[Document]:
        # to_thread, not run_in_executor: the leg's stage spans find their
        # request through the caller's context
        return await asyncio.to_thread(self.retrieve, query, top_k)


@dataclass
class DenseRetriever(BaseRetriever):
    embedder: object
    index: TpuDenseIndex
    name: str = "dense"

    def retrieve(self, query: str, top_k: int = 10) -> list[Document]:
        faults.hit("retriever.dense")
        # the `embed` request stage is this whole leg: on the fused path the
        # query vector never visits the host, so the embedding is back only
        # when the index's top-k is (embed.dispatch / embed.fetch inside)
        with span("embed"):
            # fused path: embedder output stays on device and feeds the index's
            # top-k program directly — one host round trip for the whole leg
            if hasattr(self.embedder, "embed_device") and isinstance(self.index, TpuDenseIndex):
                q_dev = self.embedder.embed_device([query])
                return [doc for doc, _ in self._scored(q_dev, top_k)]
            q_vec = self.embedder.embed(query)
            return self.index.retrieve(np.asarray(q_vec, np.float32), top_k)

    def _scored(self, q_dev, top_k: int):
        out = []
        for doc, score in self.index.search_batch(q_dev, top_k)[0]:
            meta = dict(doc.metadata)
            meta["score"] = score
            meta["retriever"] = "dense"
            out.append((Document(text=doc.text, metadata=meta, id=doc.id), score))
        return out


@dataclass
class SparseRetriever(BaseRetriever):
    index: BM25Index
    name: str = "bm25"

    def retrieve(self, query: str, top_k: int = 10) -> list[Document]:
        faults.hit("retriever.sparse")
        return self.index.retrieve(query, top_k)


@dataclass
class HybridRetriever(BaseRetriever):
    """Fuses any number of legs. Candidate pools are over-fetched (top_k * 2,
    min 10) before fusion so the fused head has depth, matching the
    reference's pool-then-truncate behavior.

    ``web_cache`` (optional) is the reference's cached-web-results pre-hit
    (/root/reference/src/core/retrievers/hybrid.py:96-107,146-182): a
    secondary collection consulted alongside the legs whose hits are
    PREPENDED to the dense leg before fusion, so previously fetched web
    results outrank fresh corpus hits at equal rank. A failing cache leg
    degrades silently, like every other leg."""

    retrievers: Sequence[BaseRetriever] = ()
    config: RetrievalConfig = field(default_factory=RetrievalConfig)
    scorers: Sequence[ScorerPlugin] = ()
    web_cache: Optional[BaseRetriever] = None
    name: str = "hybrid"

    def _weights(self) -> list[float]:
        table = {"dense": self.config.dense_weight, "bm25": self.config.sparse_weight}
        return [table.get(r.name, 1.0) for r in self.retrievers]

    def retrieve(self, query: str, top_k: int = 10) -> list[Document]:
        return asyncio.run(self.aretrieve(query, top_k))

    async def aretrieve(self, query: str, top_k: int = 10) -> list[Document]:
        pool = max(top_k * 2, 10)
        t_start = t_dense = time.perf_counter()

        async def timed_dense(retriever: BaseRetriever):
            nonlocal t_dense
            try:
                return await retriever.aretrieve(query, pool)
            finally:
                t_dense = time.perf_counter()

        # the dense leg writes the `embed` stage itself; what this call
        # adds after that leg is back is `sparse_fuse`
        fetchers = [timed_dense(r) if isinstance(r, DenseRetriever)
                    else r.aretrieve(query, pool) for r in self.retrievers]
        if self.web_cache is not None:
            fetchers.append(self.web_cache.aretrieve(query, pool))
        legs = await asyncio.gather(*fetchers, return_exceptions=True)
        cache_hits: list[Document] = []
        if self.web_cache is not None:
            cache_leg = legs[-1]
            legs = legs[:-1]
            if not isinstance(cache_leg, Exception):
                cache_hits = list(cache_leg)
        ok_lists: list[list[Document]] = []
        ok_weights: list[float] = []
        ok_names: list[str] = []
        for retriever, leg, weight in zip(self.retrievers, legs, self._weights()):
            if isinstance(leg, Exception):
                continue  # degraded: a failed leg drops out, fusion continues
            ok_lists.append(leg)
            ok_weights.append(weight)
            ok_names.append(getattr(retriever, "name", ""))
        if cache_hits:
            # prepend to the dense leg (ref hybrid.py:213 `all_dense_hits =
            # dense_cache_hits + dense_hits`), deduped by id, cache first
            if "dense" in ok_names:
                j = ok_names.index("dense")
                seen = {d.id for d in cache_hits}
                ok_lists[j] = cache_hits + [d for d in ok_lists[j] if d.id not in seen]
            else:  # no dense leg survived: the cache rides as its own leg
                ok_lists.append(cache_hits)
                ok_weights.append(self.config.dense_weight)
        if not ok_lists:
            raise RetrieverError("all retrieval legs failed")
        fused = fuse(
            ok_lists,
            method=self.config.fusion_method,
            weights=ok_weights,
            rrf_k=self.config.rrf_k,
        )
        fused = self._apply_scorers(query, fused)
        stamp("sparse_fuse", max(t_dense, t_start), time.perf_counter(),
              legs=len(ok_lists))
        return fused[:top_k]

    def _apply_scorers(self, query: str, docs: list[Document]) -> list[Document]:
        if not self.scorers or not docs:
            return docs
        base = np.asarray([d.score() for d in docs], np.float32)
        lo, hi = float(base.min()), float(base.max())
        mixed = (base - lo) / (hi - lo) if hi > lo else np.ones_like(base)
        total_w = 1.0
        for scorer in self.scorers:
            try:
                s = scorer.score(query, docs)
            except Exception:  # noqa: BLE001 — a broken plugin never kills retrieval
                continue  # a broken plugin never kills retrieval
            mixed = mixed + scorer.weight * np.asarray(s, np.float32)
            total_w += scorer.weight
        mixed = mixed / total_w
        order = np.argsort(-mixed, kind="stable")
        out = []
        for rank, i in enumerate(order):
            doc = docs[int(i)]
            doc.metadata["hybrid_score"] = float(mixed[int(i)])
            doc.metadata["score"] = float(mixed[int(i)])
            out.append(doc)
        return out


def create_retriever(
    settings: Optional[Settings] = None,
    embedder=None,
    dense_index: Optional[TpuDenseIndex] = None,
    bm25_index: Optional[BM25Index] = None,
    scorers: Optional[Sequence[ScorerPlugin]] = None,
    web_cache_index: Optional[TpuDenseIndex] = None,
) -> BaseRetriever:
    """Strategy registry (reference: retrievers/factory.py:21-196): ``dense``,
    ``bm25``, or ``hybrid`` from config; hybrid tolerates a missing leg and
    consults the optional cached-web-results index before fusing."""
    settings = settings or get_settings()
    strategy = settings.retrieval.strategy
    dense = DenseRetriever(embedder, dense_index) if embedder is not None and dense_index is not None else None
    sparse = SparseRetriever(bm25_index) if bm25_index is not None else None

    if strategy == "dense":
        if dense is None:
            raise RetrieverError("dense strategy needs embedder + dense_index")
        return dense
    if strategy in ("bm25", "sparse"):
        if sparse is None:
            raise RetrieverError("bm25 strategy needs a BM25 index")
        return sparse
    if strategy == "hybrid":
        legs = [r for r in (dense, sparse) if r is not None]
        if not legs:
            raise RetrieverError("hybrid strategy needs at least one leg")
        web_cache = None
        if web_cache_index is not None and embedder is not None:
            web_cache = DenseRetriever(embedder, web_cache_index, name="web_cache")
        return HybridRetriever(
            retrievers=legs,
            config=settings.retrieval,
            scorers=scorers or (),
            web_cache=web_cache,
        )
    raise RetrieverError(f"unknown retrieval strategy {strategy!r}")
