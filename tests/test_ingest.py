"""Ingestion pipeline tests: loaders, directory walk, chunk→embed→index.

Mirrors the reference's ingest suite (src/tests/ingest/
test_document_ingestor_comprehensive.py there) with the hash-embedder fake
backend (SURVEY.md §4) — full pipeline, no device model needed.
"""

import itertools
import json
import zipfile

import numpy as np
import pytest

from sentio_tpu.config import EmbedderConfig, Settings
from sentio_tpu.infra import tracing
from sentio_tpu.infra.flight import FlightRecorder, set_flight_recorder
from sentio_tpu.infra.metrics import MetricsCollector, set_metrics
from sentio_tpu.infra.phases import BM25_UPDATE_KINDS
from sentio_tpu.models.document import Document
from sentio_tpu.ops.bm25 import BM25Index, default_tokenizer
from sentio_tpu.ops.dense_index import TpuDenseIndex
from sentio_tpu.ops.embedder import HashEmbedder
from sentio_tpu.ops.ingest import DocumentIngestor, IngestError, ingest_directory


@pytest.fixture()
def ingestor(settings):
    settings.embedder = EmbedderConfig(provider="hash", dim=64)
    embedder = HashEmbedder(settings.embedder)
    return DocumentIngestor(
        embedder=embedder,
        dense_index=TpuDenseIndex(dim=64),
        sparse_index=BM25Index(),
        settings=settings,
    )


class TestLoaders:
    def test_txt_and_md(self, ingestor, tmp_path):
        (tmp_path / "a.txt").write_text("plain text body")
        (tmp_path / "b.md").write_text("# Title\n\nmarkdown body")
        docs = ingestor.load_directory(tmp_path)
        assert {d.metadata["format"] for d in docs} == {"txt", "md"}
        assert any("markdown body" in d.text for d in docs)

    def test_html_strips_tags_and_scripts(self, ingestor, tmp_path):
        (tmp_path / "page.html").write_text(
            "<html><head><script>var x=1;</script><style>.c{}</style></head>"
            "<body><h1>Heading</h1><p>visible text</p></body></html>"
        )
        [doc] = ingestor.load_file(tmp_path / "page.html")
        assert "visible text" in doc.text and "Heading" in doc.text
        assert "var x" not in doc.text and ".c{}" not in doc.text

    def test_json_extracts_string_leaves(self, ingestor, tmp_path):
        (tmp_path / "d.json").write_text(json.dumps(
            {"title": "doc title", "nested": {"body": ["part one", "part two"]}, "n": 7}
        ))
        [doc] = ingestor.load_file(tmp_path / "d.json")
        assert "doc title" in doc.text and "part two" in doc.text and "7" not in doc.text

    def test_jsonl(self, ingestor, tmp_path):
        (tmp_path / "d.jsonl").write_text('{"text": "line one"}\n{"text": "line two"}\n')
        [doc] = ingestor.load_file(tmp_path / "d.jsonl")
        assert "line one" in doc.text and "line two" in doc.text

    def test_yaml(self, ingestor, tmp_path):
        (tmp_path / "c.yaml").write_text("title: yaml title\nitems:\n  - alpha\n  - beta\n")
        [doc] = ingestor.load_file(tmp_path / "c.yaml")
        assert "yaml title" in doc.text and "beta" in doc.text

    def test_csv_tsv(self, ingestor, tmp_path):
        (tmp_path / "t.csv").write_text("name,role\nada,engineer\n")
        [doc] = ingestor.load_file(tmp_path / "t.csv")
        assert "ada engineer" in doc.text

    def test_docx_via_zipfile(self, ingestor, tmp_path):
        path = tmp_path / "w.docx"
        xml = (
            '<?xml version="1.0"?><w:document><w:body>'
            "<w:p><w:r><w:t>first paragraph</w:t></w:r></w:p>"
            "<w:p><w:r><w:t>second</w:t></w:r><w:r><w:t> half</w:t></w:r></w:p>"
            "</w:body></w:document>"
        )
        with zipfile.ZipFile(path, "w") as zf:
            zf.writestr("word/document.xml", xml)
        [doc] = ingestor.load_file(path)
        assert doc.text == "first paragraph\nsecond half"

    def test_bad_docx_raises(self, ingestor, tmp_path):
        path = tmp_path / "bad.docx"
        path.write_bytes(b"not a zip")
        with pytest.raises(IngestError):
            ingestor.load_file(path)

    def test_pdf_gated_with_clear_error(self, ingestor, tmp_path):
        path = tmp_path / "x.pdf"
        path.write_bytes(b"%PDF-1.4")
        with pytest.raises(IngestError, match="PyPDF2"):
            ingestor.load_file(path)

    def test_unknown_suffix_skipped_in_directory(self, ingestor, tmp_path):
        (tmp_path / "keep.txt").write_text("keep me")
        (tmp_path / "skip.bin").write_bytes(b"\x00\x01")
        docs = ingestor.load_directory(tmp_path)
        assert len(docs) == 1
        assert ingestor.stats.files_skipped == 1

    def test_recursive_walk(self, ingestor, tmp_path):
        sub = tmp_path / "nested" / "deep"
        sub.mkdir(parents=True)
        (sub / "leaf.md").write_text("deep leaf")
        assert len(ingestor.load_directory(tmp_path)) == 1
        assert len(ingestor.load_directory(tmp_path, recursive=False)) == 0


class TestIngestPipeline:
    def test_chunks_embedded_and_indexed(self, ingestor):
        text = "sentence about tpus. " * 200  # forces multiple chunks
        stats = ingestor.ingest_documents([Document(text=text, metadata={"source": "mem"})])
        assert stats.chunks_created > 1
        assert stats.chunks_stored == stats.chunks_created
        assert ingestor.dense_index.size == stats.chunks_stored
        # sparse index rebuilt over the same corpus
        assert ingestor._sparse_index.size == stats.chunks_stored

    def test_single_document_path(self, ingestor):
        stats = ingestor.ingest_document("short body", {"source": "api"})
        assert stats.chunks_stored == 1
        [doc] = ingestor.dense_index.documents()
        assert doc.metadata["source"] == "api"
        assert doc.metadata["parent_id"]

    def test_empty_chunks_dropped(self, ingestor):
        stats = ingestor.ingest_documents([Document(text="   \n  ")])
        assert stats.chunks_stored == 0

    def test_retrieval_after_ingest(self, ingestor):
        ingestor.ingest_documents([
            Document(text="jax compiles to xla for tpus", id="d1"),
            Document(text="bm25 ranks by term frequency", id="d2"),
        ])
        hits = ingestor._sparse_index.retrieve("term frequency ranking bm25", top_k=1)
        assert hits and hits[0].metadata["parent_id"] == "d2"

    def test_clear(self, ingestor):
        ingestor.ingest_document("whatever", {})
        removed = ingestor.clear()
        assert removed == 1
        assert ingestor.dense_index.size == 0
        assert ingestor._sparse_index.size == 0

    def test_ingest_directory_helper(self, settings, tmp_path):
        settings.embedder = EmbedderConfig(provider="hash", dim=32)
        (tmp_path / "doc.txt").write_text("directory helper body")
        stats = ingest_directory(tmp_path, settings=settings)
        assert stats.documents_loaded == 1 and stats.chunks_stored >= 1


class TestSparseAddition:
    """The sparse leg of an ingest call: the call's chunks ADDED where the
    dense index appended them, the store's documents built anew otherwise."""

    @pytest.fixture()
    def metrics(self):
        m = MetricsCollector()
        set_metrics(m)
        yield m
        set_metrics(None)

    @pytest.fixture()
    def traced(self, ingestor):
        """``traced(docs)`` ingests under a flight record of its own and
        returns the call's stats and its ``ingest.sparse_add`` span."""
        rec = FlightRecorder()
        set_flight_recorder(rec)
        seq = itertools.count()

        def ingest(docs):
            rid = f"upload-{next(seq)}"
            rec.start_request(rid, endpoint="/upload")
            with tracing.span("ingest", request_id=rid):
                stats = ingestor.ingest_documents(docs)
            spans = [s for s in rec.get(rid)["spans"] if s["name"] == "ingest.sparse_add"]
            rec.finish_request(rid)
            return stats, spans

        yield ingest
        set_flight_recorder(None)

    @staticmethod
    def _updates(metrics):
        counters = metrics.export_json()["counters"]
        return {kind: counters.get(f"bm25_updates('{kind}',)", 0) for kind in BM25_UPDATE_KINDS}

    @staticmethod
    def _assert_is_a_fresh_build(ingestor):
        """What the index holds is what building it now from the store's
        documents leaves (the vocabulary outlives a build, so the SAME index
        is built: term ids are its own)."""
        sparse = ingestor._sparse_index
        names = ("term_offsets", "post_docs", "post_tfs", "idf", "doc_lens", "_norm")
        held = {name: getattr(sparse, name) for name in names}
        ids, vocab, avgdl = sparse.doc_ids, dict(sparse.vocab), sparse.avgdl
        sparse.build(ingestor.dense_index.documents())
        assert (sparse.doc_ids, sparse.vocab, sparse.avgdl) == (ids, vocab, avgdl)
        for name in names:
            assert getattr(sparse, name) is not held[name]
            np.testing.assert_array_equal(held[name], getattr(sparse, name), err_msg=name)

    def test_files_ingested_one_a_call_are_added(self, ingestor, metrics):
        files = [f"file {i} holds key{i} and key{i % 3} beside common words" for i in range(9)]
        for i, text in enumerate(files):
            call = ingestor.ingest_documents([Document(text=text, id=f"f{i}")])
            assert call.bm25_updates == {"add": 1, "build": 0}
        assert self._updates(metrics) == {"add": 9, "build": 0}
        assert ingestor.stage_summary()["bm25_updates"] == {"add": 9, "build": 0}
        assert ingestor._sparse_index.size == ingestor.dense_index.size == 9
        self._assert_is_a_fresh_build(ingestor)
        hits = ingestor._sparse_index.retrieve("key7", top_k=1)  # held when its call returned
        assert hits and hits[0].metadata["parent_id"] == "f7"

    def test_an_id_written_again_builds_and_is_held_once(self, ingestor, metrics):
        chunk = lambda text: Document(text=text, id="same")  # noqa: E731
        ingestor.chunker.split = lambda docs: list(docs)  # a chunk keeps its document's id
        ingestor.ingest_documents([Document(text="the other file", id="other")])
        ingestor.ingest_documents([chunk("first wording about walruses")])
        assert self._updates(metrics) == {"add": 2, "build": 0}
        call = ingestor.ingest_documents([chunk("second wording about narwhals")])
        assert call.bm25_updates == {"add": 0, "build": 1}
        assert self._updates(metrics) == {"add": 2, "build": 1}
        sparse = ingestor._sparse_index
        assert sorted(sparse.doc_ids) == ["other", "same"] and ingestor.dense_index.size == 2
        assert sparse.search("walruses") == [] and len(sparse.search("narwhals")) == 1
        self._assert_is_a_fresh_build(ingestor)
        # an id twice in ONE call: the dense add keeps the last, so does the build
        call = ingestor.ingest_documents([Document(text="twin one", id="t"), Document(text="twin two", id="t")])
        assert call.bm25_updates == {"add": 0, "build": 1} and sparse.size == 3
        self._assert_is_a_fresh_build(ingestor)

    def test_a_delete_the_sparse_index_missed_builds(self, ingestor, metrics):
        ingestor.ingest_documents([Document(text="kept file", id="a")])
        ingestor.ingest_documents([Document(text="dropped file", id="b")])
        [dropped] = [d.id for d in ingestor.dense_index.documents() if "dropped" in d.text]
        ingestor.dense_index.delete([dropped])
        call = ingestor.ingest_documents([Document(text="late file", id="c")])
        assert call.bm25_updates == {"add": 0, "build": 1}
        assert ingestor._sparse_index.size == 2 and ingestor._sparse_index.search("dropped") == []
        self._assert_is_a_fresh_build(ingestor)

    def test_clear_then_an_ingest(self, ingestor, metrics):
        ingestor.ingest_documents([Document(text="before the clearing", id="x")])
        assert ingestor.clear() == 1 and ingestor._sparse_index.size == 0
        call = ingestor.ingest_documents([Document(text="after the clearing", id="x")])
        assert call.bm25_updates == {"add": 1, "build": 0}  # the id is no longer held
        assert ingestor._sparse_index.search("before") == []
        assert len(ingestor._sparse_index.search("after")) == 1
        self._assert_is_a_fresh_build(ingestor)

    def test_the_sparse_span_says_its_path_and_its_tokens(self, ingestor, traced):
        ingestor.chunker.split = lambda docs: list(docs)
        _, [first] = traced([Document(text="one two three", id="a")])
        assert first["fields"] == {"chunks": 1, "path": "add", "tokens": 3, "index_size": 1}
        _, [second] = traced([Document(text="four five", id="b"), Document(text="six", id="c")])
        assert second["fields"] == {"chunks": 2, "path": "add", "tokens": 3, "index_size": 3}
        _, [again] = traced([Document(text="one two three four", id="a")])  # all three, anew
        assert again["fields"] == {"chunks": 1, "path": "build", "tokens": 7, "index_size": 3}

    def test_two_hundred_ingests_tokenise_two_hundred_documents(self, ingestor, traced):
        """The scaling guard, without a clock: the tokens tokenised are the
        corpus's (a build a call tokenises about a hundred times as many)."""
        rng = np.random.default_rng(5)
        texts = [" ".join(f"t{w}" for w in rng.integers(0, 400, size=rng.integers(8, 30)))
                 for _ in range(200)]
        tokens = 0
        for i, text in enumerate(texts):
            stats, [span] = traced([Document(text=text, id=f"doc{i}")])
            assert span["fields"]["path"] == "add" and span["fields"]["index_size"] == i + 1
            assert stats.chunks_stored == 1
            tokens += span["fields"]["tokens"]
        assert tokens == sum(len(default_tokenizer(t)) for t in texts)
        assert tokens == ingestor._sparse_index.tokenised
        self._assert_is_a_fresh_build(ingestor)


class TestPersistence:
    def test_per_call_stats_carry_loader_errors(self, ingestor, tmp_path):
        (tmp_path / "good.txt").write_text("fine body")
        (tmp_path / "bad.docx").write_bytes(b"not a zip")
        stats = ingestor.ingest_path(tmp_path)
        assert stats.chunks_stored >= 1
        assert stats.files_skipped == 1
        assert any("bad.docx" in e for e in stats.errors)

    def test_saved_index_rehydrates_container(self, settings, tmp_path):
        from sentio_tpu.serve.dependencies import DependencyContainer

        settings.embedder = EmbedderConfig(provider="hash", dim=32)
        ingestor = DocumentIngestor(
            embedder=HashEmbedder(settings.embedder),
            dense_index=TpuDenseIndex(dim=32),
            settings=settings,
        )
        ingestor.ingest_document("persisted corpus entry about rings", {"source": "s"})
        path = tmp_path / "idx"
        ingestor.dense_index.save(path)

        settings.retrieval.index_path = str(path)
        container = DependencyContainer(settings=settings)
        assert container.dense_index.size == 1
        # BM25 rehydrated from the loaded documents
        assert container.sparse_index.size == 1
        hits = container.sparse_index.retrieve("rings", top_k=1)
        assert hits and "rings" in hits[0].text
