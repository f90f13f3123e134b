"""Document ingestion: load → chunk → embed → index.

Parity with /root/reference/src/core/ingest/ingest.py:20-529 (multi-format
readers :172-223, recursive directory loader :225-289, batched embedding
keyed by chunk id :291-334, store upsert :336-392, single-doc path for
``/embed`` :460-488, stats :62-67) — rebuilt around in-process TPU compute:
the embed step batches whole chunk lists through the bi-encoder in one
device dispatch per ``batch_size`` (the reference pays one HTTPS round trip
per ≤100-chunk batch), and "the store" is the in-HBM :class:`TpuDenseIndex`
plus the host-side BM25 postings — there is no external vector database in
the hot path.

Format support: txt/md/rst (raw), json/jsonl (text-field extraction),
yaml, html/htm (stdlib tag stripping), csv/tsv, docx (stdlib zipfile +
XML — no python-docx needed), pdf (gated: needs an extractor lib the base
image doesn't ship; a clear error tells the operator).
"""

from __future__ import annotations

import csv
import io
import json
import logging
import re
import threading
import time
import zipfile
from dataclasses import dataclass, field
from html.parser import HTMLParser
from pathlib import Path
from typing import Iterable, Optional, Sequence

import numpy as np

from sentio_tpu.config import Settings, get_settings
from sentio_tpu.infra import tracing
from sentio_tpu.infra.metrics import get_metrics
from sentio_tpu.infra.phases import BM25_UPDATE_KINDS, INGEST_STAGES
from sentio_tpu.models.document import Document

logger = logging.getLogger(__name__)

__all__ = [
    "IngestError",
    "IngestStats",
    "DocumentIngestor",
    "ingest_directory",
    "SUPPORTED_SUFFIXES",
]


class IngestError(Exception):
    pass


SUPPORTED_SUFFIXES = (
    ".txt", ".md", ".rst", ".json", ".jsonl", ".yaml", ".yml",
    ".html", ".htm", ".csv", ".tsv", ".docx", ".pdf",
)


class _TextExtractor(HTMLParser):
    """Collects visible text, skipping script/style (reference ingests HTML
    via its loader at ingest.py:196-204 there)."""

    _SKIP = {"script", "style", "noscript"}

    def __init__(self) -> None:
        super().__init__()
        self.parts: list[str] = []
        self._skip_depth = 0

    def handle_starttag(self, tag, attrs):
        if tag in self._SKIP:
            self._skip_depth += 1

    def handle_endtag(self, tag):
        if tag in self._SKIP and self._skip_depth:
            self._skip_depth -= 1

    def handle_data(self, data):
        if not self._skip_depth and data.strip():
            self.parts.append(data.strip())


def _read_html(raw: str) -> str:
    parser = _TextExtractor()
    parser.feed(raw)
    return "\n".join(parser.parts)


def _read_json(raw: str) -> str:
    """Flatten all string leaves — same spirit as the reference's JSON loader
    (ingest.py:186-195 there), which joins textual fields."""

    def walk(node) -> Iterable[str]:
        if isinstance(node, str):
            if node.strip():
                yield node.strip()
        elif isinstance(node, dict):
            for v in node.values():
                yield from walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                yield from walk(v)

    return "\n".join(walk(json.loads(raw)))


def _read_jsonl(raw: str) -> str:
    parts = []
    for line in raw.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            parts.append(_read_json(line))
        except json.JSONDecodeError:
            parts.append(line)
    return "\n".join(parts)


def _read_yaml(raw: str) -> str:
    try:
        import yaml

        docs = list(yaml.safe_load_all(raw))
    except Exception:  # noqa: BLE001 — yaml missing or invalid: treat as plain text
        return raw

    def walk(node) -> Iterable[str]:
        if isinstance(node, str):
            if node.strip():
                yield node.strip()
        elif isinstance(node, dict):
            for k, v in node.items():
                yield from walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                yield from walk(v)

    return "\n".join(p for d in docs for p in walk(d))


def _read_csv(raw: str, delimiter: str = ",") -> str:
    rows = csv.reader(io.StringIO(raw), delimiter=delimiter)
    return "\n".join(" ".join(cell for cell in row if cell.strip()) for row in rows)


_DOCX_TAG = re.compile(r"<[^>]+>")


def _read_docx(path: Path) -> str:
    """DOCX is a zip of XML; paragraph text lives in ``word/document.xml``
    under ``<w:t>`` runs. Stdlib-only replacement for the reference's
    python-docx loader (ingest.py:205-214 there)."""
    try:
        with zipfile.ZipFile(path) as zf:
            xml = zf.read("word/document.xml").decode("utf-8", errors="replace")
    except (zipfile.BadZipFile, KeyError) as exc:
        raise IngestError(f"not a valid docx file: {path}") from exc
    paragraphs = []
    for para in re.split(r"</w:p>", xml):
        runs = re.findall(r"<w:t[^>]*>(.*?)</w:t>", para, flags=re.S)
        text = _DOCX_TAG.sub("", "".join(runs)).strip()
        if text:
            paragraphs.append(text)
    return "\n".join(paragraphs)


def _read_pdf(path: Path) -> str:
    try:
        import PyPDF2  # noqa: F401 — gated: not in the base image
    except ImportError as exc:
        raise IngestError(
            f"PDF ingestion for {path.name} needs PyPDF2 (not installed in "
            "this image); convert to text/markdown first"
        ) from exc
    reader = PyPDF2.PdfReader(str(path))
    return "\n".join(page.extract_text() or "" for page in reader.pages)


@dataclass
class IngestStats:
    """Mirrors the reference's stats dict (ingest.py:62-67 there)."""

    documents_loaded: int = 0
    chunks_created: int = 0
    chunks_embedded: int = 0
    chunks_stored: int = 0
    files_skipped: int = 0
    errors: list[str] = field(default_factory=list)
    elapsed_s: float = 0.0
    # seconds by stage (``INGEST_STAGES``); no part of ``to_dict``, the
    # response's shape: ``/info`` and the upload's flight record read it
    stage_s: dict = field(default_factory=lambda: dict.fromkeys(INGEST_STAGES, 0.0))
    # sparse stages by what they did: added the call's chunks, or built anew
    bm25_updates: dict = field(default_factory=lambda: dict.fromkeys(BM25_UPDATE_KINDS, 0))
    calls: int = 0

    def to_dict(self) -> dict:
        return {
            "documents_loaded": self.documents_loaded,
            "chunks_created": self.chunks_created,
            "chunks_embedded": self.chunks_embedded,
            "chunks_stored": self.chunks_stored,
            "files_skipped": self.files_skipped,
            "errors": self.errors,
            "elapsed_s": round(self.elapsed_s, 3),
        }


class _Stage(tracing.span):
    """One stage of one ingest call: an ``ingest.<stage>`` span (on the
    upload request's flight record where the call runs for one), its seconds
    on the call's stats and in ``sentio_tpu_ingest_stage_seconds_total``."""

    __slots__ = ("call", "stage")

    def __init__(self, call: IngestStats, stage: str, **fields) -> None:
        super().__init__(f"ingest.{stage}", **fields)
        self.call, self.stage = call, stage

    def __exit__(self, *exc) -> bool:
        seconds = time.perf_counter() - self._t0
        self.call.stage_s[self.stage] += seconds
        get_metrics().record_ingest_stage(self.stage, seconds)
        return super().__exit__(*exc)


class DocumentIngestor:
    """load → chunk → embed (batched device dispatch) → index.

    Components are injected so the serving container shares one embedder and
    one index across ingest + retrieval (the reference's shared-component
    init, ingest.py:125-170 there). ``sparse_index`` takes each call's
    chunks as an ADDITION where the dense index appended them, and is built
    anew from the store where it did otherwise (``_update_sparse``): an
    ingest tokenises what it adds, not the corpus held.
    """

    def __init__(
        self,
        chunker=None,
        embedder=None,
        dense_index=None,
        sparse_index=None,
        settings: Optional[Settings] = None,
    ) -> None:
        self.settings = settings or get_settings()
        self._chunker = chunker
        self._embedder = embedder
        self._dense_index = dense_index
        self._sparse_index = sparse_index
        self.stats = IngestStats()  # lifetime totals; per-call stats are returned
        # index mutation (dense add + sparse add) is multi-step and not
        # atomic — concurrent /embed requests serialize here
        self._write_lock = threading.Lock()

    # ------------------------------------------------------------ components

    @property
    def chunker(self):
        if self._chunker is None:
            from sentio_tpu.ops.chunking import TextChunker

            self._chunker = TextChunker(config=self.settings.chunking)
        return self._chunker

    @property
    def embedder(self):
        if self._embedder is None:
            from sentio_tpu.ops.embedder import get_embedder

            self._embedder = get_embedder(self.settings.embedder)
        return self._embedder

    @property
    def dense_index(self):
        if self._dense_index is None:
            # through the registry so INDEX_BACKEND=qdrant ingests into the
            # same external store the serving pods retrieve from — a local
            # default here would silently ingest into a process-private index
            from sentio_tpu.ops.vector_store import get_vector_store

            self._dense_index = get_vector_store(
                self.settings.retrieval.index_backend,
                dim=self.embedder.dimension,
                settings=self.settings,
            )
        return self._dense_index

    # ----------------------------------------------------------------- load

    def load_file(self, path: str | Path) -> list[Document]:
        """One file → one Document (pre-chunking), with source metadata."""
        path = Path(path)
        if not path.is_file():
            raise IngestError(f"not a file: {path}")
        suffix = path.suffix.lower()
        if suffix == ".docx":
            text = _read_docx(path)
        elif suffix == ".pdf":
            text = _read_pdf(path)
        else:
            raw = path.read_text(encoding="utf-8", errors="replace")
            if suffix in (".html", ".htm"):
                text = _read_html(raw)
            elif suffix == ".json":
                try:
                    text = _read_json(raw)
                except json.JSONDecodeError:
                    text = raw
            elif suffix == ".jsonl":
                text = _read_jsonl(raw)
            elif suffix in (".yaml", ".yml"):
                text = _read_yaml(raw)
            elif suffix == ".csv":
                text = _read_csv(raw)
            elif suffix == ".tsv":
                text = _read_csv(raw, delimiter="\t")
            else:  # txt/md/rst and any other text-like file
                text = raw
        text = text.strip()
        if not text:
            return []
        return [
            Document(
                text=text,
                metadata={"source": str(path), "filename": path.name, "format": suffix.lstrip(".")},
            )
        ]

    def load_directory(
        self, path: str | Path, recursive: bool = True, suffixes: Optional[Sequence[str]] = None
    ) -> list[Document]:
        """Glob loader (reference: recursive ``**/*`` walk, ingest.py:225-289
        there). Unsupported/failed files are counted, not fatal."""
        path = Path(path)
        if not path.is_dir():
            raise IngestError(f"not a directory: {path}")
        allowed = tuple(suffixes) if suffixes else SUPPORTED_SUFFIXES
        pattern = "**/*" if recursive else "*"
        docs: list[Document] = []
        for file in sorted(path.glob(pattern)):
            if not file.is_file():
                continue
            if file.suffix.lower() not in allowed:
                self.stats.files_skipped += 1
                continue
            try:
                docs.extend(self.load_file(file))
            except (IngestError, OSError) as exc:
                logger.warning("skipping %s: %s", file, exc)
                self.stats.errors.append(f"{file.name}: {exc}")
                self.stats.files_skipped += 1
        return docs

    # ---------------------------------------------------------------- ingest

    def ingest_documents(self, documents: Sequence[Document]) -> IngestStats:
        """Chunk, embed (device-batched), and index a document list. Empty
        chunks are dropped before embedding (reference: ingest.py:291-334).
        Returns THIS call's stats; lifetime totals accumulate on ``.stats``."""
        t0 = time.perf_counter()
        call = IngestStats(documents_loaded=len(documents), calls=1)

        with _Stage(call, "chunk", docs=len(documents)) as stage:
            chunks = self.chunker.split(list(documents))
            chunks = [c for c in chunks if c.text.strip()]
            stage.fields["chunks"] = call.chunks_created = len(chunks)
        if chunks:
            with _Stage(call, "embed", chunks=len(chunks)):
                vecs = self.embedder.embed_many([c.text for c in chunks])
                vecs = np.asarray(vecs, np.float32)
            call.chunks_embedded = len(chunks)

            with self._write_lock:
                with _Stage(call, "dense_add", chunks=len(chunks)) as stage:
                    self.dense_index.add(chunks, vecs)
                    stage.fields["index_size"] = self.dense_index.size
                if self._sparse_index is not None:
                    with _Stage(call, "sparse_add", chunks=len(chunks)) as stage:
                        stage.fields.update(self._update_sparse(call, chunks))
            call.chunks_stored = len(chunks)
        call.elapsed_s = time.perf_counter() - t0
        self._accumulate(call)
        return call

    def _update_sparse(self, call: IngestStats, chunks: Sequence[Document]) -> dict:
        """The sparse leg of one call, after ``dense_index.add(chunks)``; the
        ``sparse_add`` span's fields. The dense add is last-write-wins, so
        the chunks were APPENDED only where none of their ids was held and
        the store now counts the sparse index's documents plus these: then
        they are added. Anything else the store reports (an id written again,
        twice in one call, a delete since) builds from its documents."""
        sparse = self._sparse_index
        tokens0 = sparse.tokenised
        appended = (not sparse.holds_any(c.id for c in chunks)
                    and self.dense_index.size == sparse.size + len(chunks))
        path = "add" if appended else "build"
        if appended:
            sparse.add(chunks)
        else:
            sparse.build(self.dense_index.documents())
        call.bm25_updates[path] += 1
        get_metrics().record_bm25_update(path)
        return {"path": path, "tokens": sparse.tokenised - tokens0, "index_size": sparse.size}

    def _accumulate(self, call: IngestStats) -> None:
        s = self.stats
        s.documents_loaded += call.documents_loaded
        s.chunks_created += call.chunks_created
        s.chunks_embedded += call.chunks_embedded
        s.chunks_stored += call.chunks_stored
        s.elapsed_s += call.elapsed_s
        s.calls += call.calls
        for stage, seconds in call.stage_s.items():
            s.stage_s[stage] += seconds
        for kind, n in call.bm25_updates.items():
            s.bm25_updates[kind] += n

    def stage_summary(self) -> dict:
        """Lifetime seconds by stage, as ``/info``'s ``startup.ingest`` gives
        them: an upload's stages are the third of a warm set-up no one saw."""
        s = self.stats
        return {
            "seconds_total": round(sum(s.stage_s.values()), 6),
            "stages": {stage: round(seconds, 6) for stage, seconds in s.stage_s.items()},
            "bm25_updates": dict(s.bm25_updates),
            "calls": s.calls, "docs": s.documents_loaded, "chunks": s.chunks_stored,
            "index_size": self.dense_index.size if self._dense_index is not None else 0,
        }

    def ingest_document(self, text: str, metadata: Optional[dict] = None) -> IngestStats:
        """Single in-memory document — the ``POST /embed`` path (reference:
        ingest.py:460-488 there)."""
        doc = Document(text=text, metadata=dict(metadata or {}))
        return self.ingest_documents([doc])

    def ingest_path(self, path: str | Path, recursive: bool = True) -> IngestStats:
        path = Path(path)
        # loader failures land on the lifetime stats; snapshot around the load
        # so THIS call's stats carry its own errors/skips (CLI exit code and
        # /embed responses depend on per-call accuracy)
        err0, skip0 = len(self.stats.errors), self.stats.files_skipped
        docs = self.load_directory(path, recursive=recursive) if path.is_dir() else self.load_file(path)
        call = self.ingest_documents(docs)
        call.errors = self.stats.errors[err0:]
        call.files_skipped = self.stats.files_skipped - skip0
        return call

    def clear(self) -> int:
        """Drop everything from both indexes; returns prior doc count."""
        with self._write_lock:
            n = self.dense_index.size
            self.dense_index.clear()
            if self._sparse_index is not None:
                self._sparse_index.build([])
        return n


def ingest_directory(
    path: str | Path,
    settings: Optional[Settings] = None,
    ingestor: Optional[DocumentIngestor] = None,
    recursive: bool = True,
) -> IngestStats:
    """Convenience used by the CLI (reference: ingest.py:491-529 there)."""
    ingestor = ingestor or DocumentIngestor(settings=settings)
    return ingestor.ingest_path(path, recursive=recursive)
