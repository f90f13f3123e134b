"""Native (C++) BM25 core vs the numpy reference implementation.

Correctness bar: identical scores and rankings on the same CSR index — the
native core is a hot-loop replacement, not a different algorithm. The build
is exercised for real here (g++ is part of the image); if it ever becomes
unavailable the factory must degrade to numpy, which is also tested.
"""

import numpy as np
import pytest

from sentio_tpu.models.document import Document
from sentio_tpu.ops.bm25 import (
    BM25Index,
    BM25Params,
    NativeBM25Index,
    make_bm25_index,
)


def corpus(n=100):
    rng = np.random.default_rng(7)
    vocab = ["tpu", "mxu", "jax", "xla", "pallas", "mesh", "hbm", "ici",
             "systolic", "matmul", "shard", "compile", "kernel", "batch"]
    docs = []
    for i in range(n):
        words = rng.choice(vocab, size=rng.integers(5, 30))
        docs.append(Document(text=" ".join(words), id=f"d{i}", metadata={"i": i}))
    return docs


@pytest.fixture(scope="module")
def built():
    docs = corpus()
    ref = BM25Index(params=BM25Params(k1=0.9, b=0.4)).build(docs)
    nat = NativeBM25Index(params=BM25Params(k1=0.9, b=0.4)).build(docs)
    assert nat._get_box() is not None, "C++ core must build in this image (g++ present)"
    return ref, nat


QUERIES = ["tpu mxu matmul", "jax jax jax compile", "hbm bandwidth", "", "systolic shard kernel batch"]


class TestParity:
    def test_dense_scores_match(self, built):
        ref, nat = built
        for q in QUERIES:
            np.testing.assert_allclose(nat.scores(q), ref.scores(q), rtol=1e-5, atol=1e-6)

    def test_topk_matches(self, built):
        ref, nat = built
        for q in QUERIES:
            r = ref.search(q, top_k=10)
            n = nat.search(q, top_k=10)
            assert [i for i, _ in n] == [i for i, _ in r]
            np.testing.assert_allclose([s for _, s in n], [s for _, s in r], rtol=1e-5)

    def test_repeated_query_terms_accumulate(self, built):
        ref, nat = built
        single = nat.scores("tpu")
        double = nat.scores("tpu tpu")
        np.testing.assert_allclose(double, 2.0 * single, rtol=1e-5)
        np.testing.assert_allclose(double, ref.scores("tpu tpu"), rtol=1e-5)

    def test_scratch_clean_between_queries(self, built):
        """Back-to-back different queries must not leak accumulator state."""
        _, nat = built
        a1 = nat.scores("tpu mxu")
        nat.scores("jax xla pallas")
        a2 = nat.scores("tpu mxu")
        np.testing.assert_array_equal(a1, a2)

    def test_rebuild_detaches_handle(self, built):
        _, nat = built
        nat.build(corpus(20))
        assert nat.size == 20
        assert len(nat.scores("tpu")) == 20
        nat.build(corpus(100))  # restore module fixture state


class TestFactory:
    def test_auto_prefers_native(self):
        idx = make_bm25_index(backend="auto")
        assert isinstance(idx, NativeBM25Index)

    def test_numpy_forced(self):
        idx = make_bm25_index(backend="numpy")
        assert type(idx) is BM25Index

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError):
            make_bm25_index(backend="lucene")

    def test_retrieve_contract_through_native(self):
        docs = corpus(30)
        idx = make_bm25_index(backend="native").build(docs)
        out = idx.retrieve("tpu mxu", top_k=5)
        assert len(out) <= 5
        for d in out:
            assert d.metadata["retriever"] == "bm25"
            assert d.metadata["score"] > 0

    def test_concurrent_queries_and_rebuild(self):
        """Thread-pool retrievers + mid-flight /embed rebuilds must not race
        the native scratch or use a destroyed handle."""
        import threading

        idx = NativeBM25Index().build(corpus(200))
        expected = {q: idx.search(q, top_k=5) for q in QUERIES if q}
        errors = []

        def query_loop():
            try:
                for _ in range(50):
                    for q, want in expected.items():
                        got = idx.search(q, top_k=5)
                        # only compare when no rebuild intervened (size match)
                        if idx.size == 200 and got != want:
                            errors.append((q, got, want))
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        def rebuild_loop():
            try:
                for _ in range(10):
                    idx.build(corpus(200))
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=query_loop) for _ in range(4)]
        threads.append(threading.Thread(target=rebuild_loop))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]

    def test_persistence_roundtrip_native(self, tmp_path):
        docs = corpus(40)
        idx = NativeBM25Index(params=BM25Params(k1=1.2, b=0.6)).build(docs)
        idx.save(tmp_path / "bm25")
        loaded = NativeBM25Index.load(tmp_path / "bm25")
        assert isinstance(loaded, NativeBM25Index)
        for q in QUERIES:
            np.testing.assert_allclose(loaded.scores(q), idx.scores(q), rtol=1e-5)


class TestForeignBinary:
    def test_foreign_binary_is_rebuilt_and_backend_reported(
            self, tmp_path, monkeypatch):
        """The shared object is git-ignored and built ``-march=native``: one
        that came along with a copied working tree (another machine's, or
        older than the source) must never be loaded. Binaries are named by
        a key of (source, flags, host CPU); anything else in the directory
        is ignored and the library is built from ``bm25.cpp`` here. The
        index says which core serves."""
        import shutil

        from sentio_tpu import native

        shutil.copy(native._SRC_DIR / "bm25.cpp", tmp_path / "bm25.cpp")
        # the legacy un-keyed name, and a keyed one from "another host" —
        # both newer than the source, neither loadable
        foreign = [tmp_path / "libbm25.so",
                   tmp_path / "libbm25.0123456789abcdef.so"]
        for path in foreign:
            path.write_bytes(b"not an ELF file")
        monkeypatch.setattr(native, "_SRC_DIR", tmp_path)
        monkeypatch.setattr(native, "_CACHE", {})

        lib = native.load_bm25()
        assert lib is not None, "rebuild from source must succeed (g++ present)"
        assert lib.sbm25_version() >= 1
        built_here = tmp_path / f"libbm25.{native._build_key(tmp_path / 'bm25.cpp')}.so"
        assert built_here.exists()
        assert all(p.read_bytes() == b"not an ELF file" for p in foreign)

        # another CPU (or other flags) is another key: never this binary
        monkeypatch.setattr(native, "_host_cpu", lambda: "another-machine")
        assert native._build_key(tmp_path / "bm25.cpp") not in built_here.name

        assert make_bm25_index(backend="auto").backend == "native"
        assert make_bm25_index(backend="numpy").backend == "numpy"


class TestEmptyIndex:
    def test_empty_native_index_search_does_not_deadlock(self):
        """Regression: search on an empty native index falls back to the
        numpy base implementation, whose scores() re-enters the overridden
        native scores(). The original design held a non-reentrant instance
        lock across the fallback and self-deadlocked (observed as /chat
        hanging on a fresh server with no documents ingested); scoring is
        now lock-free so the re-entry is harmless by construction."""
        nat = NativeBM25Index().build([])
        assert nat.search("anything", top_k=5) == []
        assert nat.scores("anything").shape == (0,)
        assert nat.retrieve("anything") == []


class TestLockFreeScoring:
    def test_many_threads_score_concurrently(self):
        """Queries must not serialize on an instance lock: N threads scoring
        the same index finish with correct, identical-to-sequential results
        (lifecycle lock covers only handle create/retire)."""
        import threading

        docs = corpus(300)
        nat = NativeBM25Index().build(docs)
        assert nat._get_box() is not None
        expected = {q: nat.search(q, top_k=7) for q in ("tpu mxu", "jax xla", "hbm ici")}
        errors = []

        def worker(q):
            for _ in range(30):
                if nat.search(q, top_k=7) != expected[q]:
                    errors.append(q)
                    return

        threads = [threading.Thread(target=worker, args=(q,)) for q in expected for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors

    def test_rebuild_while_scoring_is_safe(self):
        """retire() defers destroy until in-flight searches release."""
        import threading

        nat = NativeBM25Index().build(corpus(200))
        stop = threading.Event()
        errors = []

        def scorer():
            while not stop.is_set():
                try:
                    nat.search("tpu jax kernel", top_k=5)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return

        threads = [threading.Thread(target=scorer) for _ in range(4)]
        for t in threads:
            t.start()
        for n in (50, 150, 250, 100):
            nat.build(corpus(n))
        stop.set()
        for t in threads:
            t.join()
        assert not errors


class TestTieBound:
    def test_massive_tie_set_returns_smallest_ids(self):
        """k-th-score ties across a huge uniform corpus must not lexsort the
        whole match set; winners are the smallest doc ids, deterministically."""
        docs = [Document(text="boilerplate token", id=f"d{i}", metadata={}) for i in range(5000)]
        ref = BM25Index().build(docs)
        out = ref.search("boilerplate", top_k=10)
        assert [i for i, _ in out] == list(range(10))
        nat = NativeBM25Index().build(docs)
        assert nat.search("boilerplate", top_k=10) == out


class TestRebuildConsistency:
    def test_inflight_query_uses_handle_snapshot_after_shrink(self):
        """A query holding the old handle mid-rebuild must size buffers by
        the OLD corpus (the C++ core writes old-n_docs floats — live size
        would overflow after a shrink) and resolve indices against the OLD
        document list."""
        nat = NativeBM25Index().build(corpus(250))
        box = nat._get_box()
        assert box is not None and box.acquire()
        try:
            nat.build(corpus(40))  # shrink under the in-flight query
            assert box.n_docs == 250
            hits = nat._native_search(box, "tpu jax kernel", top_k=5)
            for di, _ in hits:
                assert 0 <= di < 250
                assert box.documents[di].id.startswith("d")
        finally:
            box.release()
        # post-rebuild queries see the new corpus
        assert all(0 <= di < 40 for di, _ in nat.search("tpu jax kernel", top_k=5))

    def test_retrieve_documents_match_scores_under_churn(self):
        """Stress: concurrent retrieves during shrinking/growing rebuilds
        return documents whose metadata is internally consistent."""
        import threading

        nat = NativeBM25Index().build(corpus(300))
        stop = threading.Event()
        errors = []

        def worker():
            while not stop.is_set():
                try:
                    for doc in nat.retrieve("tpu jax kernel shard", top_k=5):
                        if not doc.id.startswith("d"):
                            errors.append(f"bad id {doc.id}")
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                    return

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for n in (30, 280, 10, 300, 50):
            nat.build(corpus(n))
        stop.set()
        for t in threads:
            t.join()
        assert not errors


class TestAddition:
    @pytest.fixture()
    def toolchain(self):
        from sentio_tpu import native

        if native.load_bm25() is None:
            pytest.skip("no toolchain: the C++ core did not build")

    def test_search_after_add_equals_numpy_on_the_grown_index(self, toolchain):
        docs = corpus(120)
        params = BM25Params(k1=0.9, b=0.4)
        nat = NativeBM25Index(params=params).build(docs[:50])
        ref = BM25Index(params=params).build(docs)
        assert nat._get_box() is not None
        for start in range(50, 120, 35):
            nat.add(docs[start:start + 35])
            assert nat._box is None  # retired by the add; made again by the next query
            for q in QUERIES:
                # the numpy search over the same grown arrays (a pinned snapshot)
                assert nat.search(q, top_k=10) == BM25Index.search(nat, q, 10, nat._epoch)
            assert nat._get_box().n_docs == nat.size
        for name in ("term_offsets", "post_docs", "post_tfs", "idf", "_norm"):
            np.testing.assert_array_equal(getattr(nat, name), getattr(ref, name))
        for q in QUERIES:
            n, r = nat.search(q, top_k=10), ref.search(q, top_k=10)
            assert [i for i, _ in n] == [i for i, _ in r]
            np.testing.assert_allclose([s for _, s in n], [s for _, s in r], rtol=1e-5)

    def test_add_retires_the_handle_and_the_last_reader_frees_it(self, toolchain):
        nat = NativeBM25Index().build(corpus(60))
        box = nat._get_box()
        assert box is not None and box.acquire()
        try:
            nat.add(corpus(90)[60:])  # grow under the in-flight query
            assert box._dead and box._pinned and box.n_docs == 60
            assert all(0 <= di < 60 for di, _ in nat._native_search(box, "tpu jax kernel", 5))
        finally:
            box.release()
        assert box._pinned == ()  # freed by its last reader: buffers unpinned
        new = nat._get_box()
        assert new is not box and new.n_docs == 90 and new.documents is nat._documents
        assert nat.add([]) is nat and nat._box is new  # nothing added: the handle stays
