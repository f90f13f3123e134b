"""Request handlers: chat (with the degradation ladder) and health.

Parity with /root/reference/src/api/handlers/chat.py:25-274 and
health.py:20-344: the chat handler builds pipeline state with per-request
``user_top_k``/temperature metadata, invokes the graph, serializes cited
sources, and on ANY failure walks the 3-tier ladder — cached response →
template fallback → apology — so the endpoint never 500s on pipeline
errors. The health handler runs component probes concurrently with an
overall timeout and caches results for 10 s. TPU additions: device health
(mesh, HBM headroom) rides the detailed report.
"""

from __future__ import annotations

import asyncio
import logging
import time
import uuid
from typing import Any, Optional

from sentio_tpu.graph.state import create_initial_state
from sentio_tpu.infra import startup, tracing

logger = logging.getLogger(__name__)

__all__ = ["ChatHandler", "HealthHandler"]


class ChatHandler:
    """Graph-invoking chat processor with soft-fail semantics."""

    def __init__(self, container) -> None:
        self.container = container
        self.settings = container.settings
        self._fallback = None

    @property
    def fallback(self):
        if self._fallback is None:
            from sentio_tpu.infra.resilience import FallbackResponseCache, LLMFallback

            self._fallback = (FallbackResponseCache(), LLMFallback())
        return self._fallback

    # ----------------------------------------------------------------- sync

    @staticmethod
    def _open_record(request_id: Optional[str], t_received: Optional[float],
                     deadline_ts: Optional[float], **fields: Any) -> float:
        """Open the request's flight record on the thread that runs its
        pipeline and return the clock its latency counts from: the receipt
        the HTTP handler stamped, when it did. The wait for this thread is
        the request's ``pool_wait`` stage."""
        from sentio_tpu.infra.flight import get_flight_recorder

        t_thread = time.perf_counter()
        t0 = t_received if t_received is not None else t_thread
        if request_id:
            if deadline_ts is not None:
                fields["deadline_ms"] = round((deadline_ts - t0) * 1e3, 1)
            get_flight_recorder().start_request(request_id, t_received=t0, **fields)
            tracing.stamp("pool_wait", t0, t_thread, request_id)
        return t0

    def process_chat_request_sync(
        self,
        question: str,
        top_k: Optional[int] = None,
        temperature: Optional[float] = None,
        mode: str = "balanced",
        thread_id: Optional[str] = None,
        deadline_ts: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        t_received: Optional[float] = None,
    ) -> dict[str, Any]:
        query_id = thread_id or uuid.uuid4().hex[:12]
        metadata: dict[str, Any] = {"query_id": query_id, "mode": mode}
        if top_k is not None:
            metadata["user_top_k"] = top_k
        if temperature is not None:
            metadata["temperature"] = temperature
        if deadline_ts is not None:
            # absolute perf_counter deadline rides metadata into the graph's
            # generate node and down into the decode-service ticket
            metadata["deadline_ts"] = deadline_ts
        if tenant is not None:
            # WFQ key: rides metadata into the generate node, whose decode
            # admission is charged to this tenant's fair-share quota
            metadata["tenant"] = tenant
        if priority is not None:
            metadata["priority"] = priority
        # flight record opens HERE — the query_id in metadata is the trace
        # context every downstream layer (graph executor, generator provider,
        # decode-engine pump) attaches its telemetry to
        from sentio_tpu.infra.flight import get_flight_recorder

        recorder = get_flight_recorder()
        t0 = self._open_record(query_id, t_received, deadline_ts,
                               endpoint="/chat", mode=mode,
                               question_chars=len(question))

        cache = self.container.cache_manager
        try:
            state = self.container.graph.invoke(
                create_initial_state(question, metadata=metadata),
                config={"thread_id": query_id},
            )
            answer = state.get("response", "")
            if not answer:
                raise RuntimeError("pipeline produced an empty response")
            # deadline_ts is a process-local perf_counter value — meaningless
            # (and misleading) outside this server; never serialize it to
            # clients or persist it into the query cache
            meta_out = {k: v for k, v in state.get("metadata", {}).items()
                        if k != "deadline_ts"}
            result = {
                "answer": answer,
                "sources": self._serialize_sources(state),
                "metadata": {
                    **meta_out,
                    "query_id": query_id,
                    "latency_ms": round((time.perf_counter() - t0) * 1000.0, 1),
                    "degraded": False,
                },
            }
            if state.get("evaluation"):
                result["metadata"]["evaluation"] = state["evaluation"]
            # NB: with VERIFY_MODE=async (or gated, below threshold) the
            # executor-stamped metadata.verify_pending flag rides meta_out
            # into the LIVE response — the answer ships NOW and the verdict
            # is fetchable at /debug/flight/{query_id} once it lands. The
            # CACHED copy must drop it: a cache replay serves a different
            # query_id with no detached verify behind it, so a baked-in
            # pending flag would promise a verdict that can never arrive.
            cache.set_query_response(question, {
                **result,
                "metadata": {k: v for k, v in result["metadata"].items()
                             if k != "verify_pending"},
            })
            disk_cache, _ = self.fallback
            disk_cache.put(question, answer)
            recorder.finish_request(
                query_id, status="done",
                latency_ms=result["metadata"]["latency_ms"],
            )
            return result
        except Exception as exc:  # noqa: BLE001 — ladder, never a 500
            if getattr(exc, "soft_fail_exempt", False):
                # typed shed / deadline errors skip the ladder: the caller
                # gets an honest 429/503/504 + Retry-After (mapped by the
                # serve error middleware) instead of a degraded 200
                recorder.finish_request(
                    query_id, status="shed", error=str(exc),
                    latency_ms=round((time.perf_counter() - t0) * 1000.0, 1),
                )
                raise
            logger.warning("chat pipeline failed (%s); degrading", exc)
            recorder.finish_request(
                query_id, status="degraded", error=str(exc),
                latency_ms=round((time.perf_counter() - t0) * 1000.0, 1),
            )
            return self._degraded_response(question, query_id, str(exc), t0)

    def _degraded_response(
        self, question: str, query_id: str, error: str, t0: float
    ) -> dict[str, Any]:
        """cached → template → apology (reference chat.py:195-239 there)."""
        meta = {
            "query_id": query_id,
            "degraded": True,
            "error": error,
            "latency_ms": round((time.perf_counter() - t0) * 1000.0, 1),
        }
        cached = self.container.cache_manager.get_query_response(question)
        if cached and cached.get("answer"):
            return {**cached, "metadata": {**cached.get("metadata", {}), **meta, "tier": "query_cache"}}
        disk_cache, llm_fallback = self.fallback
        disk_hit = disk_cache.get(question)
        if disk_hit:
            return {"answer": disk_hit, "sources": [], "metadata": {**meta, "tier": "disk_cache"}}
        template = llm_fallback.no_llm(question)
        if template:
            return {"answer": template, "sources": [], "metadata": {**meta, "tier": "template"}}
        return {"answer": llm_fallback.apology(), "sources": [], "metadata": {**meta, "tier": "apology"}}

    @staticmethod
    def _serialize_sources(state: dict) -> list[dict[str, Any]]:
        """Cited sources from the best doc set (reference chat.py:158-166)."""
        from sentio_tpu.graph.state import best_documents

        out = []
        for doc in best_documents(state):
            out.append(
                {
                    "id": doc.id,
                    "text": doc.text[:500],
                    "score": doc.score(),
                    "metadata": {
                        k: v for k, v in doc.metadata.items()
                        if k in ("source", "filename", "score", "hybrid_score", "rerank_score")
                    },
                }
            )
        return out

    def stream_chat_sync(
        self,
        question: str,
        top_k: Optional[int] = None,
        temperature: Optional[float] = None,
        mode: str = "balanced",
        request_id: Optional[str] = None,
        deadline_ts: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        resumable: bool = True,
        t_received: Optional[float] = None,
    ):
        """Typed-event generator for SSE, with FULL graph-stage parity
        (reference factory.py:191-208 — streaming traverses the same graph):
        retrieve → rerank → select (dedup + token budget) → stream decode →
        verify. Yields ("sources", [...]) once, ("token", str) per increment,
        ("verdict", {...}) after the stream when the verifier is on, and
        ("usage", {prompt_tokens, answer_tokens}) last before the stream's end
        when the provider reports the engine's counts.
        Failures degrade to the ladder text instead of raw errors. The
        ``request_id`` opens a flight record whose stage timings mirror the
        stream's stages (streams bypass the graph executor, so the stages
        are timed here)."""
        from sentio_tpu.infra.flight import get_flight_recorder

        recorder = get_flight_recorder()
        t0 = self._open_record(request_id, t_received, deadline_ts,
                               endpoint="/chat?stream", mode=mode,
                               question_chars=len(question))
        timings: dict[str, float] = {}
        # set once the ANSWER's flight record has been finished (async/gated
        # close it at [DONE] time): the disconnect/degrade handlers below
        # must not re-finish it — that would clobber the answer-latency
        # 'done' record with an audit-inclusive 'disconnected'/'degraded'
        record_closed = False
        try:
            # the node spans the graph executor would write: the stages
            # inside (embed, sparse_fuse, rerank, select) find the request
            # through them
            t = time.perf_counter()
            with tracing.span("graph.retrieve", request_id=request_id):
                docs = self.container.retriever.retrieve(
                    question, top_k=top_k or self.settings.retrieval.top_k
                )
            timings["retrieve"] = round((time.perf_counter() - t) * 1e3, 3)
            reranker = self.container.reranker
            if reranker is not None and docs:
                t = time.perf_counter()
                with tracing.span("graph.rerank", request_id=request_id):
                    docs = reranker.rerank(
                        question, docs, top_k=self.settings.rerank.top_k
                    ).documents
                timings["rerank"] = round((time.perf_counter() - t) * 1e3, 3)
            from sentio_tpu.graph.nodes import select_documents

            with tracing.span("graph.select", request_id=request_id):
                selected, _used = select_documents(
                    list(docs), self.settings.generator.context_token_budget
                )
            yield ("sources", [
                {"id": d.id, "source": d.metadata.get("source", d.id),
                 "score": d.score()} for d in selected
            ])
            chunks: list[str] = []
            gen_stats: dict = {}
            t = time.perf_counter()
            for piece in self.container.generator.stream(
                question, selected, mode=mode, temperature=temperature,
                request_id=request_id, deadline_ts=deadline_ts,
                tenant=tenant, priority=priority, stats=gen_stats,
                resumable=resumable,
            ):
                chunks.append(piece)
                yield ("token", piece)
            timings["generate"] = round((time.perf_counter() - t) * 1e3, 3)
            # the engine's own counts, as the last data event before [DONE]:
            # a client need not re-tokenize the text to count what it got
            usage = None
            if gen_stats.get("tokens") is not None:
                usage = {"prompt_tokens": gen_stats.get("prompt_tokens"),
                         "answer_tokens": gen_stats["tokens"]}
            verifier = self.container.verifier
            answer = "".join(chunks)
            # same deadline discipline as the graph verify node: skip the
            # optional audit when the budget is spent, and bound its decode
            # with the caller's deadline so the pump can cancel it
            deadline_ok = (deadline_ts is None
                           or time.perf_counter() < deadline_ts)
            verify_mode = self.settings.generator.verify_mode
            if verifier is not None and answer and deadline_ok:
                from sentio_tpu.graph.nodes import _record_verify
                from sentio_tpu.ops.confidence import confidence_score

                conf = None
                confident = False
                if verify_mode == "gated":
                    conf = confidence_score(
                        gen_stats.get("logprob_mean"),
                        gen_stats.get("logprob_min"), selected,
                    )
                    threshold = (
                        self.settings.generator.verify_confidence_threshold
                    )
                    confident = conf is not None and conf >= threshold
                if confident:
                    # gate pays off: typed skipped verdict, zero audit
                    # decode — same verdict shape as the graph gate node
                    from sentio_tpu.graph.nodes import (
                        confidence_skip_evaluation,
                    )

                    _record_verify(request_id, "gated", "skipped_confident",
                                   confidence=conf, skipped="confident")
                    yield ("verdict", confidence_skip_evaluation(conf))
                elif verify_mode in ("async", "gated"):
                    # answer first: the client gets [DONE] NOW and the
                    # flight record closes at ANSWER latency; the audit
                    # decodes while the connection idles (keepalives keep
                    # it warm) and the verdict trails as a `verify` event
                    if usage:
                        yield ("usage", usage)
                    yield ("done", "")
                    if request_id:
                        recorder.add_node_timings(request_id, timings)
                        recorder.finish_request(
                            request_id, status="done",
                            latency_ms=round(
                                (time.perf_counter() - t0) * 1e3, 1),
                        )
                    record_closed = True
                    # past this point the answer is DELIVERED and its
                    # record closed: a trailing-audit failure must degrade
                    # to a warn verdict, never to the apology ladder (which
                    # would append prose after [DONE]) and never touch the
                    # finished record (the verifier itself soft-fails to
                    # warn; this guards the telemetry around it too)
                    try:
                        t = time.perf_counter()
                        result = verifier.verify(question, answer, selected,
                                                 request_id=request_id,
                                                 deadline_ts=deadline_ts)
                        verdict_ms = round((time.perf_counter() - t) * 1e3, 3)
                        if request_id:
                            recorder.add_node_timings(
                                request_id, {"verify": verdict_ms})
                        _record_verify(request_id, verify_mode,
                                       result.verdict, confidence=conf,
                                       verdict_ms=verdict_ms)
                        trailing = result.to_dict()
                    except Exception as exc:  # noqa: BLE001
                        logger.warning("trailing verify failed (%s)", exc)
                        trailing = {"verdict": "warn", "citations_ok": True,
                                    "notes": [f"verify failed: {exc}"]}
                    if conf is not None:
                        trailing["confidence"] = round(conf, 4)
                    yield ("verify", trailing)
                    return
                else:
                    t = time.perf_counter()
                    result = verifier.verify(question, answer, selected,
                                             request_id=request_id,
                                             deadline_ts=deadline_ts)
                    verdict_ms = round((time.perf_counter() - t) * 1e3, 3)
                    timings["verify"] = verdict_ms
                    _record_verify(request_id, "sync", result.verdict,
                                   verdict_ms=verdict_ms)
                    yield ("verdict", result.to_dict())
            if usage:
                yield ("usage", usage)
            if request_id:
                recorder.add_node_timings(request_id, timings)
                recorder.finish_request(
                    request_id, status="done",
                    latency_ms=round((time.perf_counter() - t0) * 1e3, 1),
                )
        except GeneratorExit:
            # client disconnected mid-stream and the SSE pump closed this
            # generator — close the flight record (it would otherwise sit
            # status='active' until LRU eviction, making disconnect-heavy
            # traffic look like a pile of stuck requests in /debug/flight).
            # A disconnect AFTER the answer finished (e.g. an async-mode
            # client that closes on [DONE] while the trailing verdict is
            # still decoding) keeps the 'done' record: the answer WAS
            # delivered at the recorded latency.
            if request_id and not record_closed:
                recorder.add_node_timings(request_id, timings)
                recorder.finish_request(
                    request_id, status="disconnected",
                    latency_ms=round((time.perf_counter() - t0) * 1e3, 1),
                )
            raise
        except Exception as exc:  # noqa: BLE001 — ladder, never a raw error
            if record_closed:
                # answer already delivered and its record closed: nothing
                # left to degrade — surface nothing after [DONE]
                logger.warning("post-answer stream stage failed (%s)", exc)
                return
            if getattr(exc, "soft_fail_exempt", False):
                # shed / expired mid-stream: the SSE status is already on
                # the wire, so no 429/503 — but appending an apology after
                # real tokens would corrupt the answer, and ending with a
                # bare [DONE] would be indistinguishable from a successful
                # empty answer. Emit a typed error event, then end.
                if request_id:
                    recorder.add_node_timings(request_id, timings)
                    recorder.finish_request(
                        request_id, status="shed", error=str(exc),
                        latency_ms=round((time.perf_counter() - t0) * 1e3, 1),
                    )
                code = getattr(exc, "code", None)
                yield ("error", {
                    "code": getattr(code, "value", "OVERLOADED"),
                    "message": str(exc),
                    "retryable": bool(getattr(exc, "retryable", True)),
                })
                return
            logger.warning("stream pipeline failed (%s); degrading", exc)
            if request_id:
                recorder.add_node_timings(request_id, timings)
                recorder.finish_request(
                    request_id, status="degraded", error=str(exc),
                    latency_ms=round((time.perf_counter() - t0) * 1e3, 1),
                )
            result = self._degraded_response(question, "stream", str(exc), time.perf_counter())
            yield ("token", result["answer"])

    # ---------------------------------------------------------------- async

    async def process_chat_request(self, **kwargs) -> dict[str, Any]:
        """The pipeline is synchronous device dispatch; keep the event loop
        free by running it on one of the server's request threads (as many
        as the generation service admits; the caller's span context travels
        with it, as under ``asyncio.to_thread``)."""
        return await self.container.request_threads.run(
            self.process_chat_request_sync, **kwargs)


class HealthHandler:
    """basic / detailed / ready / live with a 10 s result cache."""

    CACHE_TTL_S = 10.0
    PROBE_TIMEOUT_S = 30.0

    def __init__(self, container) -> None:
        self.container = container
        self._cached: Optional[dict[str, Any]] = None
        self._cached_at = 0.0
        self._lock = asyncio.Lock()

    def basic(self) -> dict[str, Any]:
        """Cheap liveness-with-capacity view: replica failure domains fold
        in here. ``degraded`` means ready at reduced capacity (1 ≤ serving
        replicas < N — k8s must KEEP routing to this pod while the
        supervisor rebuilds the dead replica in place); ``unhealthy`` only
        when zero replicas can serve, the one state where restarting the
        pod beats waiting."""
        out = {
            "status": "healthy",
            "service": "sentio-tpu",
            # since the PROCESS started: the ``startup`` record's clock
            "uptime_s": round(startup.uptime_s(), 1),
        }
        service = self.container.peek("generation_service")
        if service is not None and hasattr(service, "health_summary"):
            try:
                replicas = service.health_summary()
            except Exception:  # noqa: BLE001 — health must never 500
                logger.debug("replica health summary failed", exc_info=True)
            else:
                out["status"] = replicas["status"]
                out["replicas"] = {
                    k: replicas[k]
                    for k in ("healthy_replicas", "serving_replicas",
                              "total_replicas", "replicas")
                }
        return out

    def live(self) -> dict[str, Any]:
        return {"status": "alive"}

    def ready(self) -> dict[str, Any]:
        """Readiness = the container finished eager init (mesh + weights)."""
        ready = self.container._initialized
        return {"status": "ready" if ready else "initializing", "ready": ready}

    async def detailed(self) -> dict[str, Any]:
        async with self._lock:
            now = time.perf_counter()
            if self._cached is not None and now - self._cached_at < self.CACHE_TTL_S:
                return {**self._cached, "cached": True}
            try:
                components = await asyncio.wait_for(
                    asyncio.to_thread(self.container.check_dependency_health),
                    timeout=self.PROBE_TIMEOUT_S,
                )
            except asyncio.TimeoutError:
                components = {"error": {"healthy": False, "error": "health probe timeout"}}
            components["breakers"] = self._breaker_states()
            healthy = all(
                c.get("healthy", True) for c in components.values() if isinstance(c, dict)
            )
            report = {
                **self.basic(),
                "status": "healthy" if healthy else "degraded",
                "components": components,
                "cached": False,
            }
            self._cached, self._cached_at = report, now
            return report

    @staticmethod
    def _breaker_states() -> dict[str, Any]:
        try:
            from sentio_tpu.infra.resilience import registered_breakers

            return {name: b.health() for name, b in registered_breakers().items()}
        except ImportError:
            return {}
