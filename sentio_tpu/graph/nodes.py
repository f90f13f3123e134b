"""Graph node factories: retrieve → rerank → select → generate → verify.

Parity with /root/reference/src/core/graph/nodes.py:37-478: per-request
``user_top_k`` override, content-normalization via ``Document.content``,
the selector's sort/dedup/token-budget pass (≈4 chars/token heuristic,
nodes.py:276-338 there), the generator's mode/temperature metadata, and the
verifier rewriting the answer on a ``fail`` verdict (:471-472). Every node
returns a *partial* state update and records soft errors in metadata instead
of raising — the executor's soft-fail plus these per-node catches reproduce
the reference's "every stage degrades, nothing 500s" ladder.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Any, Optional

from sentio_tpu.config import Settings, get_settings
from sentio_tpu.graph.state import (
    RAGState,
    best_documents,
    deadline_remaining_s,
    deadline_ts,
)
from sentio_tpu.infra.tracing import span
from sentio_tpu.models.document import Document

logger = logging.getLogger(__name__)


def _user_top_k(state: RAGState, default: int, cap: int = 50) -> int:
    raw = state.get("metadata", {}).get("user_top_k")
    if raw is None:
        return default
    try:
        return max(1, min(int(raw), cap))
    except (TypeError, ValueError):
        return default


def create_retriever_node(retriever, settings: Optional[Settings] = None):
    settings = settings or get_settings()

    async def retrieve_node(state: RAGState) -> dict[str, Any]:
        top_k = _user_top_k(state, settings.retrieval.top_k)
        t0 = time.perf_counter()
        try:
            docs = await retriever.aretrieve(state["query"], top_k=top_k)
        except Exception as exc:  # noqa: BLE001
            logger.exception("retrieval failed")
            return {"retrieved_documents": [], "metadata": {"retrieval_error": str(exc)}}
        return {
            "retrieved_documents": docs,
            "metadata": {
                "num_retrieved": len(docs),
                "retrieval_ms": round((time.perf_counter() - t0) * 1000, 2),
                "retriever": getattr(retriever, "name", "unknown"),
            },
        }

    return retrieve_node


def create_reranker_node(reranker, settings: Optional[Settings] = None):
    settings = settings or get_settings()

    async def rerank_node(state: RAGState) -> dict[str, Any]:
        docs = state.get("retrieved_documents") or []
        if not docs:
            return {"reranked_documents": [], "metadata": {"num_reranked": 0}}
        top_k = _user_top_k(state, settings.rerank.top_k)
        t0 = time.perf_counter()
        result = await reranker.arerank(state["query"], docs, top_k=top_k)
        return {
            "reranked_documents": result.documents,
            "metadata": {
                "num_reranked": len(result.documents),
                "rerank_ms": round((time.perf_counter() - t0) * 1000, 2),
                "reranker": result.model,
                "rerank_fallback": result.fallback_used,
            },
        }

    return rerank_node


CHARS_PER_TOKEN = 4  # the selector's ≈4-chars/token budget heuristic


def select_documents(
    docs: list, budget_tokens: int
) -> tuple[list[Document], int]:
    """Sort by best score, dedup by id, enforce the ≈4-chars/token context
    budget (reference nodes.py:276-338). Shared by the graph's select node
    and the SSE streaming path so the two can never drift."""
    with span("select", candidates=len(docs)):
        docs = sorted(docs, key=lambda d: d.score(), reverse=True)
        seen: set[str] = set()
        budget_chars = budget_tokens * CHARS_PER_TOKEN
        used = 0
        selected: list[Document] = []
        for doc in docs:
            if doc.id in seen:
                continue
            seen.add(doc.id)
            text = doc.content
            if not text.strip():
                continue
            cost = len(text)
            if used + cost > budget_chars and selected:
                continue  # keep scanning: a shorter doc may still fit
            selected.append(doc)
            used += cost
            if used >= budget_chars:
                break
    return selected, used


def create_document_selector_node(settings: Optional[Settings] = None):
    settings = settings or get_settings()
    budget_tokens = settings.generator.context_token_budget

    def select_node(state: RAGState) -> dict[str, Any]:
        docs = state.get("reranked_documents") or state.get("retrieved_documents") or []
        selected, used = select_documents(docs, budget_tokens)
        return {
            "selected_documents": selected,
            "metadata": {
                "num_selected": len(selected),
                "context_chars": used,
                "context_budget_chars": budget_tokens * CHARS_PER_TOKEN,
            },
        }

    return select_node


def create_generator_node(generator, settings: Optional[Settings] = None):
    settings = settings or get_settings()

    async def generate_node(state: RAGState) -> dict[str, Any]:
        docs = best_documents(state)
        meta = state.get("metadata", {})
        mode = meta.get("mode") or settings.generator.mode
        temperature = meta.get("temperature")
        # flight-recorder trace context: ties this generation's engine
        # tickets/ticks to the serving layer's request id
        request_id = meta.get("query_id")
        # caller deadline: rides metadata from the HTTP layer down into the
        # decode service's ticket, so an expired caller's decode is cancelled
        deadline = deadline_ts(state)
        # WFQ tenant key + priority tier (multi-replica tier): the decode
        # admission is charged against this tenant's fair-share quota
        tenant = meta.get("tenant")
        priority = meta.get("priority")
        # logprob accumulators from the paged decode (runtime/paged.py):
        # filled in place by the provider when the serving path carries
        # them; the confidence gate scores them after this node
        gen_stats: dict[str, Any] = {}
        t0 = time.perf_counter()
        try:
            # device generation is the longest stage — keep it off the event
            # loop so concurrent requests, streams, and health checks proceed
            # (to_thread carries the node's span context to the admission)
            answer = await asyncio.to_thread(
                lambda: generator.generate(
                    state["query"], docs, mode=mode,
                    temperature=temperature if temperature is None else float(temperature),
                    request_id=str(request_id) if request_id else None,
                    deadline_ts=deadline,
                    tenant=str(tenant) if tenant else None,
                    priority=str(priority) if priority else None,
                    stats=gen_stats,
                ),
            )
        except Exception as exc:  # noqa: BLE001
            if getattr(exc, "soft_fail_exempt", False):
                raise  # shed/deadline errors surface as 429/503/504, not prose
            logger.exception("generation failed")
            return {"response": "", "metadata": {"generation_error": str(exc)}}
        update_meta: dict[str, Any] = {
            "generation_ms": round((time.perf_counter() - t0) * 1000, 2),
            "generation_mode": mode,
            "generator": getattr(generator.provider, "name", "unknown"),
        }
        if gen_stats.get("logprob_count"):
            update_meta["logprob_mean"] = round(gen_stats["logprob_mean"], 4)
            update_meta["logprob_min"] = round(gen_stats["logprob_min"], 4)
            update_meta["logprob_count"] = gen_stats["logprob_count"]
        if gen_stats.get("replica_id") is not None:
            # which serving replica decoded the answer: downstream node
            # spans (verify) and traces carry it as a correlation key
            update_meta["replica_id"] = gen_stats["replica_id"]
        return {"response": answer, "metadata": update_meta}

    return generate_node


def _record_verify(request_id: Optional[str], mode: str, outcome: str,
                   confidence: Optional[float] = None,
                   verdict_ms: Optional[float] = None,
                   skipped: Optional[str] = None) -> None:
    """One per-request verify record, published to BOTH evidence surfaces:
    the ``sentio_tpu_verify_total{mode,outcome}`` counter + confidence
    histogram in /metrics, and the request's flight record (``verify``
    section — what ``sentio trace`` and ``/debug/flight/{id}`` print).
    Best-effort: telemetry must never fail a verdict."""
    try:
        from sentio_tpu.infra.flight import get_flight_recorder
        from sentio_tpu.infra.metrics import get_metrics

        get_metrics().record_verify(mode, outcome, confidence=confidence)
        if request_id:
            fields: dict[str, Any] = {"mode": mode, "outcome": outcome}
            if confidence is not None:
                fields["confidence"] = round(float(confidence), 4)
            if verdict_ms is not None:
                fields["verdict_ms"] = round(float(verdict_ms), 2)
            if skipped is not None:
                fields["skipped"] = skipped
            get_flight_recorder().note_verify(str(request_id), **fields)
    except Exception:  # noqa: BLE001
        logger.debug("verify telemetry failed", exc_info=True)


def confidence_skip_evaluation(confidence: float) -> dict[str, Any]:
    """THE typed ``skipped_confident`` verdict shape — shared by the graph
    gate node and the SSE streaming handler so the two surfaces can never
    drift."""
    return {
        "verdict": "skipped_confident",
        "citations_ok": True,
        "confidence": round(float(confidence), 4),
        "notes": [],
    }


def create_confidence_gate_node(settings: Optional[Settings] = None):
    """The ``verify_gate`` node (VERIFY_MODE=gated): scores the generation's
    logprob accumulators + retrieval fusion margins (ops/confidence.py) and,
    at or above ``verify_confidence_threshold``, short-circuits verification
    with a typed ``skipped_confident`` verdict — zero verify-decode
    admissions, the whole audit round-trip saved. Below threshold (or with
    no logprob signal at all) it stamps the score and routes on to the
    detached verify node."""
    settings = settings or get_settings()
    threshold = settings.generator.verify_confidence_threshold

    def gate_node(state: RAGState) -> dict[str, Any]:
        from sentio_tpu.ops.confidence import confidence_score

        meta = state.get("metadata", {})
        request_id = meta.get("query_id")
        answer = state.get("response", "")
        if not answer:
            # nothing to audit; the verify node's empty-answer warn applies
            return {"metadata": {"verify_confidence": None}}
        conf = confidence_score(
            meta.get("logprob_mean"), meta.get("logprob_min"),
            best_documents(state),
        )
        if conf is not None and conf >= threshold:
            _record_verify(request_id, "gated", "skipped_confident",
                           confidence=conf, skipped="confident")
            return {
                "evaluation": confidence_skip_evaluation(conf),
                "metadata": {
                    "verify_confidence": round(conf, 4),
                    "verify_skipped": "confident",
                },
            }
        return {"metadata": {
            "verify_confidence": None if conf is None else round(conf, 4),
        }}

    return gate_node


def confidence_gate_router(state: RAGState) -> str:
    """Conditional edge after ``verify_gate``: confident answers end the
    graph (no verify at all); everything else proceeds to ``verify``."""
    from sentio_tpu.graph.executor import END

    if state.get("metadata", {}).get("verify_skipped") == "confident":
        return END
    return "verify"


def create_verifier_node(verifier, settings: Optional[Settings] = None,
                         mode: str = "sync"):
    settings = settings or get_settings()

    async def verify_node(state: RAGState) -> dict[str, Any]:
        answer = state.get("response", "")
        if not answer:
            # recorded like every other terminal outcome: in async/gated
            # mode the caller holds verify_pending and polls the flight
            # record — an unrecorded return would leave it pending forever
            _record_verify(state.get("metadata", {}).get("query_id"),
                           mode, "skipped_empty", skipped="empty")
            return {"evaluation": {"verdict": "warn", "notes": ["empty answer"]}}
        # verification is an optional quality stage: with the caller's
        # deadline already spent, running it would burn decode ticks on an
        # answer nobody may read in time — return the unverified answer
        remaining = deadline_remaining_s(state)
        if remaining is not None and remaining <= 0:
            _record_verify(state.get("metadata", {}).get("query_id"),
                           mode, "skipped_deadline", skipped="deadline")
            return {
                "evaluation": {
                    "verdict": "skip",
                    "notes": ["deadline expired; verification skipped"],
                },
                "metadata": {"verify_skipped": "deadline"},
            }
        docs = best_documents(state)
        # same trace id as the generate node: the verify admission lands on
        # the same flight record, where its prefix_hit_tokens show the
        # generate prompt head being reused from the radix cache
        meta = state.get("metadata", {})
        request_id = meta.get("query_id")
        # the remaining deadline bounds the audit decode too — without it
        # the pump's expiry sweep could never cancel an expired caller's
        # verify slot (verifier soft-fails internally, so an expiry here
        # degrades to a 'warn' verdict rather than failing the answer)
        deadline = deadline_ts(state)
        # WFQ tenant + priority: the verify admission is charged to the
        # REQUESTING tenant, exactly like the generate admission — verify
        # traffic riding the shared tenant would let one tenant's verify
        # load starve every other tenant's quota for free
        tenant = meta.get("tenant")
        priority = meta.get("priority")
        t0 = time.perf_counter()
        result = await asyncio.to_thread(
            lambda: verifier.verify(
                state["query"], answer, docs,
                request_id=str(request_id) if request_id else None,
                deadline_ts=deadline,
                tenant=str(tenant) if tenant else None,
                priority=str(priority) if priority else None,
            ),
        )
        verdict_ms = round((time.perf_counter() - t0) * 1000, 2)
        _record_verify(
            str(request_id) if request_id else None, mode, result.verdict,
            confidence=meta.get("verify_confidence"), verdict_ms=verdict_ms,
        )
        update: dict[str, Any] = {
            "evaluation": result.to_dict(),
            "metadata": {
                "verify_ms": verdict_ms,
                "verdict": result.verdict,
            },
        }
        if result.verdict == "fail" and result.revised_answer:
            # sync mode only in practice: a detached verify's update is
            # discarded by the executor — the answer already shipped, so a
            # late rewrite has nowhere to go (the verdict still lands on
            # the flight record for the caller to fetch)
            update["response"] = result.revised_answer
            update["metadata"]["answer_revised"] = True
        return update

    return verify_node
