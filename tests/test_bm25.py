import numpy as np
import pytest

from sentio_tpu.models.document import Document
from sentio_tpu.ops.bm25 import BM25Index, BM25Params, default_tokenizer


def test_tokenizer_lowercases_and_splits():
    assert default_tokenizer("Hello, World! 42") == ["hello", "world", "42"]


def test_exact_term_match_ranks_first(docs):
    index = BM25Index().build(docs)
    results = index.retrieve("systolic array matrix", top_k=3)
    assert results
    assert results[0].id == "d2"
    assert results[0].metadata["score"] > 0


def test_scores_match_naive_okapi(docs):
    """Vectorized CSR scoring must equal a straightforward per-doc loop."""
    params = BM25Params(k1=1.2, b=0.6)
    index = BM25Index(params=params).build(docs)
    query = "quick fox dog"
    fast = index.scores(query)

    # naive implementation
    tokenized = [default_tokenizer(d.content) for d in docs]
    n = len(docs)
    avgdl = sum(len(t) for t in tokenized) / n
    naive = np.zeros(n)
    for tok in default_tokenizer(query):
        df = sum(1 for t in tokenized if tok in t)
        if df == 0:
            continue
        idf = max(np.log(1 + (n - df + 0.5) / (df + 0.5)), 0.0)
        for di, toks in enumerate(tokenized):
            tf = toks.count(tok)
            if tf == 0:
                continue
            denom = tf + params.k1 * (1 - params.b + params.b * len(toks) / avgdl)
            naive[di] += idf * tf * (params.k1 + 1) / denom
    np.testing.assert_allclose(fast, naive, rtol=1e-5)


def test_unknown_terms_score_zero(docs):
    index = BM25Index().build(docs)
    assert index.search("zzzxqwv nonexistent", top_k=5) == []


def test_repeated_query_terms_accumulate(docs):
    index = BM25Index().build(docs)
    single = index.scores("fox")
    double = index.scores("fox fox")
    np.testing.assert_allclose(double, single * 2, rtol=1e-5)


def test_bm25_plus_delta_boosts_matches(docs):
    okapi = BM25Index(BM25Params()).build(docs)
    plus = BM25Index(BM25Params(variant="plus")).build(docs)
    q = "fox"
    s_ok, s_plus = okapi.scores(q), plus.scores(q)
    matched = s_ok > 0
    assert (s_plus[matched] > s_ok[matched]).all()
    assert (s_plus[~matched] == 0).all()


def test_save_load_roundtrip(tmp_path, docs):
    index = BM25Index().build(docs)
    index.save(tmp_path / "bm25")
    loaded = BM25Index.load(tmp_path / "bm25")
    q = "retrieval language models"
    np.testing.assert_allclose(loaded.scores(q), index.scores(q), rtol=1e-6)
    orig = [(d.id, d.metadata["score"]) for d in index.retrieve(q, 5)]
    new = [(d.id, d.metadata["score"]) for d in loaded.retrieve(q, 5)]
    assert orig == new


def test_empty_corpus():
    index = BM25Index().build([])
    assert index.search("anything") == []
    assert index.scores("anything").shape == (0,)


def test_load_with_custom_tokenizer_guard(tmp_path, docs):
    def shouty(text):
        return text.upper().split()

    index = BM25Index(tokenizer=shouty).build(docs)
    index.save(tmp_path / "custom")
    with pytest.raises(ValueError, match="custom tokenizer"):
        BM25Index.load(tmp_path / "custom")
    loaded = BM25Index.load(tmp_path / "custom", tokenizer=shouty)
    np.testing.assert_allclose(loaded.scores("quick FOX"), index.scores("quick FOX"))


# ----------------------------------------------------------------- additions


def _grown_corpus(n=40):
    """Seeded documents of 3–20 words over a small vocabulary (terms recur
    across documents and inside one), ids in order."""
    rng = np.random.default_rng(11)
    words = [f"w{i}" for i in range(30)]
    return [Document(text=" ".join(rng.choice(words, size=rng.integers(3, 20))), id=f"g{i}")
            for i in range(n)]


def _reference_csr(documents, params):
    """The index as the build before ``add`` made it: a loop over every token
    of every document, then over every term — independent of ``_grow``."""
    vocab, postings = {}, {}
    doc_lens = np.zeros(len(documents), np.float32)
    for di, doc in enumerate(documents):
        tokens = default_tokenizer(doc.content)
        doc_lens[di] = len(tokens)
        for tok in tokens:
            tid = vocab.setdefault(tok, len(vocab))
            postings.setdefault(tid, {})
            postings[tid][di] = postings[tid].get(di, 0) + 1
    lengths = np.array([len(postings[t]) for t in range(len(vocab))], np.int64)
    post_docs = np.array([d for t in range(len(vocab)) for d in sorted(postings[t])], np.int32)
    post_tfs = np.array([postings[t][d] for t in range(len(vocab)) for d in sorted(postings[t])],
                        np.float32)
    n = len(documents)
    df = lengths.astype(np.float64)
    idf = np.maximum(np.log(1.0 + (n - df + 0.5) / (df + 0.5)), 0.0).astype(np.float32)
    avgdl = float(doc_lens.mean()) if n else 0.0
    norm = (params.k1 * (1.0 - params.b + params.b * doc_lens / avgdl)).astype(np.float32)
    return {"vocab": vocab, "doc_ids": [d.id for d in documents], "avgdl": avgdl,
            "term_offsets": np.concatenate([[0], np.cumsum(lengths)]), "post_docs": post_docs,
            "post_tfs": post_tfs, "idf": idf, "doc_lens": doc_lens, "_norm": norm}


def _in_steps(index, documents, step):
    for i in range(0, len(documents), step):
        index.add(documents[i:i + step])
    return index


def _one_at_a_time(make, documents, tmp_path):
    return _in_steps(make(), documents, 1)


def _batches_of_seven(make, documents, tmp_path):
    return _in_steps(make(), documents, 7)


def _onto_a_build(make, documents, tmp_path):
    return _in_steps(make().build(documents[:13]), documents[13:], 5)


def _onto_a_loaded_index(make, documents, tmp_path):
    make().build(documents[:13]).save(tmp_path / "held")
    return _in_steps(BM25Index.load(tmp_path / "held"), documents[13:], 5)


def _with_empty_additions_between(make, documents, tmp_path):
    index = make().add([])
    for i in range(0, len(documents), 9):
        before = index._epoch
        assert index.add([]) is index and index._epoch is before  # nothing published
        index.add(documents[i:i + 9])
    return index


def _after_a_build_of_other_documents(make, documents, tmp_path):
    # the vocabulary outlives a build: terms no document held have empty slices
    index = make().build([Document(text="zebra quagga okapi", id="other")])
    return _in_steps(index.build(documents[:4]), documents[4:], 3)


ADD_WAYS = [_one_at_a_time, _batches_of_seven, _onto_a_build, _onto_a_loaded_index,
            _with_empty_additions_between, _after_a_build_of_other_documents]
ADD_CORPORA = {
    "seeded": _grown_corpus,
    # the later documents bring no term the first did not hold
    "no_new_term": lambda: [Document(text=t, id=f"n{i}") for i, t in enumerate(
        ["alpha beta gamma delta", "beta alpha", "gamma gamma delta", "delta alpha beta gamma"] * 5)],
    # a term repeated inside one document, and a document of one term alone
    "repeated_term": lambda: [Document(text=t, id=f"r{i}") for i, t in enumerate(
        ["echo echo echo echo", "echo fox", "fox fox echo fox echo", "golf", "golf golf echo"] * 4)],
}
ADD_CASES = ([(way, "seeded") for way in ADD_WAYS]
             + [(_one_at_a_time, "no_new_term"), (_onto_a_build, "no_new_term"),
                (_one_at_a_time, "repeated_term"), (_batches_of_seven, "repeated_term")])


@pytest.mark.parametrize("params", [BM25Params(k1=1.2, b=0.6), BM25Params(variant="plus", delta=0.5)],
                         ids=["okapi", "plus"])
@pytest.mark.parametrize("way,corpus", ADD_CASES,
                         ids=[f"{way.__name__.strip('_')}-{corpus}" for way, corpus in ADD_CASES])
def test_added_in_steps_is_the_index_built_of_all(way, corpus, params, tmp_path):
    documents = ADD_CORPORA[corpus]()

    def make():
        return BM25Index(params=BM25Params(**vars(params)))

    grown, built = way(make, documents, tmp_path), make().build(documents)
    want = _reference_csr(documents, params)
    for index in (grown, built):
        # a build keeps the vocabulary of earlier builds: the documents' own
        # terms take the same ids in the same order after them
        own = [t for t in index.vocab if t in want["vocab"]]
        assert own == list(want["vocab"])
        first = len(index.vocab) - len(own)
        assert index.doc_ids == want["doc_ids"] and index.avgdl == want["avgdl"]
        assert [d.id for d in index._epoch.documents] == want["doc_ids"]
        np.testing.assert_array_equal(index.term_offsets[:first], 0)
        for name in ("post_docs", "post_tfs", "doc_lens", "_norm"):
            got = getattr(index, name)
            assert got.dtype == want[name].dtype
            np.testing.assert_array_equal(got, want[name], err_msg=name)
        assert index.term_offsets.dtype == np.int64 and index.idf.dtype == np.float32
        np.testing.assert_array_equal(index.term_offsets[first:], want["term_offsets"])
        np.testing.assert_array_equal(index.idf[first:], want["idf"])
        assert index._epoch.post_docs is index.post_docs and index._epoch.norm is index._norm
    for query in ("w3 w7 w7 w21", "alpha delta", "echo golf fox", "absent"):
        np.testing.assert_array_equal(grown.scores(query), built.scores(query))
        assert grown.search(query, top_k=5) == built.search(query, top_k=5)


def test_a_reader_of_the_old_snapshot_keeps_its_scores_after_an_add(docs):
    index = BM25Index().build(docs[:5])
    old = index._epoch
    arrays = [a.copy() for a in old[:5]]
    before, hits = index.scores("fox dog search", _e=old), index.search("fox dog search", 3, _e=old)
    index.add(docs[5:])
    assert index._epoch is not old and len(index._epoch.doc_ids) == 8
    assert len(old.doc_ids) == len(old.documents) == 5
    for was, now in zip(arrays, old[:5]):
        np.testing.assert_array_equal(was, now)
    np.testing.assert_array_equal(index.scores("fox dog search", _e=old), before)
    assert index.search("fox dog search", 3, _e=old) == hits
    assert index.scores("fox dog search").shape == (8,)
    assert not np.array_equal(index.scores("fox dog search")[:5], before)  # idf moved
