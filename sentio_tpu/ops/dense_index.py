"""TpuDenseIndex — exact MIPS retrieval as a sharded matmul + top-k.

The reference delegates dense retrieval to an external Qdrant server (Rust
HNSW over HTTP, /root/reference/src/core/vector_store/qdrant_store.py:37).
TPU-native, the index is the corpus embedding matrix itself, row-sharded
across every mesh device and resident in HBM: a query batch is one
``[Q, D] @ [D, N_local]`` matmul per device (MXU work), a local top-k, and a
k-sized all-gather — exact search, no ANN recall loss, no server. At
NQ scale (millions of chunks × 1k dims) this is a few GB in bf16 spread over
the mesh, and a query costs ~N·D/mesh FLOPs — microseconds, not HTTP.

Host keeps the float32 master copy + Document store (the "collection");
device array rebuilds lazily after mutation with growth padding so appends
don't recompile every time.
"""

from __future__ import annotations

import json
from functools import partial
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

from sentio_tpu.infra.tracing import annotation, current, dispatching
from sentio_tpu.models.document import Document


class DenseIndexError(Exception):
    pass


class TpuDenseIndex:
    """Exact top-k cosine/MIPS index on the device mesh.

    ``mesh=None`` runs the same code single-device (CPU tests, 1-chip dev).
    Embeddings are L2-normalized at add time, so inner product == cosine.
    """

    def __init__(self, dim: int, mesh=None, dtype: str = "bfloat16") -> None:
        self.dim = dim
        self.mesh = mesh
        self.dtype = dtype
        self._embeddings = np.zeros((0, dim), np.float32)  # host master
        self._documents: list[Document] = []
        self._id_to_row: dict[str, int] = {}
        self._alive = np.zeros(0, bool)
        self._device_state = None  # (padded device array, n_pad) — lazy

    # ------------------------------------------------------------------ crud

    @property
    def size(self) -> int:
        return int(self._alive.sum())

    def documents(self) -> list[Document]:
        """Live documents (the "collection scroll" the reference does against
        Qdrant to hydrate BM25, retrievers/factory.py:83-133 there)."""
        return [doc for doc, ok in zip(self._documents, self._alive) if ok]

    def add(self, documents: Sequence[Document], embeddings: np.ndarray) -> None:
        embeddings = np.asarray(embeddings, np.float32)
        if embeddings.ndim != 2 or embeddings.shape[1] != self.dim:
            raise DenseIndexError(
                f"expected embeddings [N, {self.dim}], got {embeddings.shape}"
            )
        if len(documents) != embeddings.shape[0]:
            raise DenseIndexError("documents/embeddings length mismatch")
        norms = np.linalg.norm(embeddings, axis=1, keepdims=True)
        embeddings = embeddings / np.maximum(norms, 1e-9)
        # duplicate ids within one batch: last write wins (otherwise the
        # earlier row would stay alive but unreachable through _id_to_row)
        last_by_id = {doc.id: i for i, doc in enumerate(documents)}
        if len(last_by_id) != len(documents):
            keep = sorted(last_by_id.values())
            documents = [documents[i] for i in keep]
            embeddings = embeddings[keep]
        for doc in documents:
            if doc.id in self._id_to_row:  # upsert: tombstone the old row
                self._alive[self._id_to_row[doc.id]] = False
        base = len(self._documents)
        self._embeddings = np.concatenate([self._embeddings, embeddings])
        self._alive = np.concatenate([self._alive, np.ones(len(documents), bool)])
        for off, doc in enumerate(documents):
            self._documents.append(doc)
            self._id_to_row[doc.id] = base + off
        self._device_state = None
        self._maybe_compact()

    def delete(self, ids: Sequence[str]) -> int:
        n = 0
        for doc_id in ids:
            row = self._id_to_row.pop(doc_id, None)
            if row is not None and self._alive[row]:
                self._alive[row] = False
                n += 1
        if n:
            self._device_state = None
            self._maybe_compact()
        return n

    def _maybe_compact(self, dead_fraction: float = 0.25) -> None:
        """Drop tombstoned rows once they pass ``dead_fraction`` of the table
        so churn (daily re-ingest upserts) can't grow host or HBM footprint
        unboundedly — queries never pay matmul FLOPs over mostly-dead rows."""
        total = len(self._documents)
        dead = total - int(self._alive.sum())
        if total == 0 or dead / total <= dead_fraction:
            return
        keep = np.flatnonzero(self._alive)
        self._embeddings = self._embeddings[keep]
        self._documents = [self._documents[i] for i in keep]
        self._alive = np.ones(len(keep), bool)
        self._id_to_row = {doc.id: i for i, doc in enumerate(self._documents)}
        self._device_state = None

    def clear(self) -> None:
        self._embeddings = np.zeros((0, self.dim), np.float32)
        self._documents = []
        self._id_to_row = {}
        self._alive = np.zeros(0, bool)
        self._device_state = None

    # ---------------------------------------------------------------- search

    def _n_shards(self) -> int:
        return int(np.prod(list(self.mesh.shape.values()))) if self.mesh is not None else 1

    def _ensure_device(self):
        """Upload [n_pad, D] corpus (dead rows zeroed → score 0 after the
        -inf masking margin; padded rows likewise) sharded over all axes."""
        if self._device_state is not None:
            return self._device_state
        import jax
        import jax.numpy as jnp

        shards = self._n_shards()
        n = len(self._documents)
        # grow in 25% steps (min 1 row per shard) so appends amortize uploads
        n_pad = max(shards, int(np.ceil(n * 1.25 / shards)) * shards)
        corpus = np.zeros((n_pad, self.dim), np.float32)
        if n:
            corpus[:n] = self._embeddings * self._alive[:, None]
        valid = np.zeros(n_pad, bool)
        valid[:n] = self._alive
        dt = jnp.dtype(self.dtype)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            row_spec = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names), None))
            corpus_dev = jax.device_put(jnp.asarray(corpus, dt), row_spec)
            valid_dev = jax.device_put(
                jnp.asarray(valid), NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
            )
        else:
            corpus_dev = jnp.asarray(corpus, dt)
            valid_dev = jnp.asarray(valid)
        self._device_state = (corpus_dev, valid_dev, n_pad)
        return self._device_state

    def search_batch(
        self, queries, top_k: int = 10
    ) -> list[list[tuple[Document, float]]]:
        """queries [Q, D] → per-query (Document, cosine score) descending.

        Accepts host numpy OR a device array (the fused retrieval path hands
        the embedder's output over without a host round trip — queries are
        L2-normalized on whichever side they already live)."""
        import jax
        import jax.numpy as jnp

        on_device = isinstance(queries, jax.Array)
        if not on_device:
            queries = np.asarray(queries, np.float32)
        if queries.ndim != 2 or queries.shape[1] != self.dim:
            raise DenseIndexError(f"expected queries [Q, {self.dim}], got {queries.shape}")
        if self.size == 0:
            return [[] for _ in range(len(queries))]
        if on_device:
            qn = queries / jnp.maximum(
                jnp.linalg.norm(queries, axis=1, keepdims=True), 1e-9
            )
        else:
            qn = queries / np.maximum(np.linalg.norm(queries, axis=1, keepdims=True), 1e-9)
            qn = jnp.asarray(qn)

        corpus_dev, valid_dev, n_pad = self._ensure_device()
        k = min(top_k, self.size)
        shards = self._n_shards()
        k_local = min(max(k, 1), n_pad // shards)
        k_out = min(k, shards * k_local)

        # on the fused retrieval path this is the `embed` span's second program
        with dispatching("other", spans=[current()]) as stamp:
            scores, rows = _topk_fn(self.mesh, self.dtype, k_local, k_out)(
                corpus_dev, valid_dev, qn
            )
            stamp.out = scores
        # one blocking fetch for both outputs, not two sequential ones. On
        # the fused retrieval path it waits for the query's embedding too
        with annotation("embed.fetch"):
            scores, rows = jax.device_get((scores, rows))
        scores = np.asarray(scores, np.float32)

        out: list[list[tuple[Document, float]]] = []
        for qi in range(len(queries)):
            hits = []
            for s, r in zip(scores[qi], rows[qi]):
                if s <= -1e29 or len(hits) >= k:
                    break
                hits.append((self._documents[int(r)], float(s)))
            out.append(hits)
        return out

    def search(self, query: np.ndarray, top_k: int = 10) -> list[tuple[Document, float]]:
        return self.search_batch(query[None, :], top_k)[0]

    def retrieve(self, query_embedding: np.ndarray, top_k: int = 10) -> list[Document]:
        out = []
        for doc, score in self.search(query_embedding, top_k):
            meta = dict(doc.metadata)
            meta["score"] = score
            meta["retriever"] = "dense"
            out.append(Document(text=doc.text, metadata=meta, id=doc.id))
        return out

    # ------------------------------------------------------------ persistence

    def save(self, path: str | Path) -> None:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        keep = self._alive
        np.savez_compressed(path.with_suffix(".npz"), embeddings=self._embeddings[keep])
        docs = [self._documents[i].to_dict() for i in np.flatnonzero(keep)]
        path.with_suffix(".json").write_text(json.dumps({"dim": self.dim, "documents": docs}))

    @classmethod
    def load(cls, path: str | Path, mesh=None, dtype: str = "bfloat16") -> "TpuDenseIndex":
        path = Path(path)
        meta = json.loads(path.with_suffix(".json").read_text())
        index = cls(dim=int(meta["dim"]), mesh=mesh, dtype=dtype)
        embeddings = np.load(path.with_suffix(".npz"))["embeddings"]
        docs = [Document.from_dict(d) for d in meta["documents"]]
        if len(docs):
            index.add(docs, embeddings)
        return index


# --------------------------------------------------------------------------
# compiled search kernels, cached per (mesh, dtype, k_local)

_TOPK_CACHE: dict = {}


def _topk_fn(mesh, dtype: str, k_local: int, k_out: int):
    key = (id(mesh) if mesh is not None else None, dtype, k_local, k_out)
    fn = _TOPK_CACHE.get(key)
    if fn is None:
        fn = _build_topk(mesh, dtype, k_local, k_out)
        _TOPK_CACHE[key] = fn
    return fn


def _build_topk(mesh, dtype: str, k_local: int, k_out: int):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def local_scores(corpus, valid, q):
        s = jnp.einsum("qd,nd->qn", q.astype(dt), corpus).astype(jnp.float32)
        return jnp.where(valid[None, :], s, -jnp.inf)

    if mesh is None:

        @jax.jit
        def single(corpus, valid, q):
            s = local_scores(corpus, valid, q)
            return jax.lax.top_k(s, k_out)

        return single

    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)

    def shard_fn(corpus, valid, q):
        # corpus/valid hold this device's rows; q replicated
        s = local_scores(corpus, valid, q)  # [Q, n_local]
        loc_s, loc_i = jax.lax.top_k(s, k_local)  # [Q, k_local]
        # local row index -> global row index
        first = jax.lax.axis_index(axes[0])
        idx = first
        for a in axes[1:]:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        n_local = corpus.shape[0]
        glob_i = loc_i + idx * n_local
        # gather candidates from every shard, then merge
        all_s = jax.lax.all_gather(loc_s, axes, axis=0, tiled=False)  # [S, Q, k]
        all_i = jax.lax.all_gather(glob_i, axes, axis=0, tiled=False)
        shards = all_s.shape[0]
        cat_s = jnp.moveaxis(all_s, 0, 1).reshape(-1, shards * k_local)
        cat_i = jnp.moveaxis(all_i, 0, 1).reshape(-1, shards * k_local)
        best_s, pos = jax.lax.top_k(cat_s, k_out)
        best_i = jnp.take_along_axis(cat_i, pos, axis=1)
        return best_s, best_i

    # check_vma off: all_gather'd outputs are replicated by construction
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)
