"""HTTP serving surface on aiohttp.web.

Parity with /root/reference/src/api/app.py:250-665 — endpoints ``/chat``
(+SSE streaming), ``/embed``, ``/clear``, ``/health`` ×4, ``/info``,
``/metrics`` + ``/metrics/performance``; per-IP sliding-window rate limits
(10/min ``/embed``, 100/min default, :81-101 there), security-header
middleware (:272-281), central exception handlers (:284-297), lifespan
startup/shutdown (:206-246) — built on aiohttp instead of FastAPI (the only
async HTTP server in the base image), with the TPU inversion: startup eagerly
initializes mesh + weights + indexes via ``DependencyContainer.initialize_all``
so first-request latency is flat.

A minimal built-in chat page at ``/`` replaces the reference's separate
Streamlit app (src/ui/streamlit_app.py there) without adding a dependency.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import dataclasses
import itertools
import json
import logging
import os
import threading
import time
from pathlib import Path
from typing import Any, Optional

from aiohttp import web

from sentio_tpu.config import Settings, get_settings
from sentio_tpu.infra import startup, tracing
from sentio_tpu.infra.exceptions import ErrorHandler, RateLimitError, SentioError
from sentio_tpu.infra.metrics import get_metrics
from sentio_tpu.infra.phases import LANE_ADMISSION_KINDS
from sentio_tpu.infra.security import SECURITY_HEADERS, setup_log_sanitization
from sentio_tpu.runtime.weights import device_stats
from sentio_tpu.serve.dependencies import DependencyContainer, get_container, set_container
from sentio_tpu.serve.schemas import (
    MAX_DEADLINE_MS,
    SchemaError,
    parse_chat_request,
    parse_embed_request,
)

logger = logging.getLogger(__name__)

__all__ = ["create_app", "run_server"]

_UI_PAGE = """<!doctype html><html><head><meta charset="utf-8">
<title>sentio-tpu</title><style>
body{font-family:system-ui,sans-serif;max-width:780px;margin:2rem auto;padding:0 1rem}
#log{border:1px solid #ccc;border-radius:8px;padding:1rem;min-height:200px;white-space:pre-wrap}
textarea{width:100%;box-sizing:border-box}
.src{color:#666;font-size:.85em;margin-left:1em}
#health{float:right;font-size:.9em}
#dot{display:inline-block;width:.7em;height:.7em;border-radius:50%;background:#999}
#upl{color:#666;font-size:.85em}
</style></head><body>
<h2>sentio-tpu <span id="health"><span id="dot"></span> <span id="hstat">checking…</span></span></h2>
<p><input type="file" id="file" accept=".txt,.md,.rst,.json,.csv,.pdf,.docx,.html,.htm" multiple>
<button onclick="upload()">Ingest</button> <span id="upl"></span></p>
<div id="log"></div>
<p><textarea id="q" rows="3" placeholder="Ask a question..."></textarea>
<button onclick="send()">Send</button></p>
<script>
async function send(){
  const q=document.getElementById('q').value.trim(); if(!q)return;
  const log=document.getElementById('log');
  log.textContent+='\\n> '+q+'\\n';
  const r=await fetch('/chat',{method:'POST',headers:{'Content-Type':'application/json'},
    body:JSON.stringify({question:q})});
  const d=await r.json();
  log.textContent+=(d.answer||JSON.stringify(d))+'\\n';
  (d.sources||[]).forEach((s,i)=>{log.textContent+='  ['+(i+1)+'] '+(s.metadata.source||s.id)+'\\n'});
}
// client-side chunking + per-chunk /embed, like the reference UI's upload
function chunks(text,size=1500,overlap=200){
  const out=[]; for(let i=0;i<text.length;i+=size-overlap){out.push(text.slice(i,i+size));
    if(i+size>=text.length)break;} return out;
}
// binary formats go whole-file to /upload (server-side parse via the
// docx/pdf readers); text formats keep the chunked /embed flow
async function uploadBinary(f,st){
  for(let tries=0;tries<20;tries++){
    const fd=new FormData(); fd.append('file',f,f.name);
    const r=await fetch('/upload',{method:'POST',body:fd});
    if(r.status===429){
      const wait=parseInt(r.headers.get('Retry-After')||'6',10);
      st.textContent='rate limited; waiting '+wait+'s…';
      await new Promise(res=>setTimeout(res,wait*1000));
      continue;
    }
    let d=null; try{d=await r.json()}catch(e){}
    if(!d) return 'error: HTTP '+r.status;
    const info=(d.files&&d.files[0])||{};
    return info.error?('error: '+info.error):((info.chunks_embedded||0)+' chunks');
  }
  return 'error: rate limited too long';
}
async function upload(){
  const files=document.getElementById('file').files, st=document.getElementById('upl');
  if(!files.length){st.textContent='pick a file first';return}
  let done=0,total=0;
  for(const f of files){
    if(/\\.(pdf|docx|html|htm)$/i.test(f.name)){
      st.textContent='uploading '+f.name+'…';
      st.textContent=f.name+': '+await uploadBinary(f,st);
      continue;
    }
    const text=await f.text(); const parts=chunks(text); total+=parts.length;
    for(let i=0;i<parts.length;i++){
      // the server rate-limits /embed per IP: back off on 429 and retry
      // the SAME chunk instead of silently dropping the document tail
      for(let tries=0;tries<20;tries++){
        const r=await fetch('/embed',{method:'POST',headers:{'Content-Type':'application/json'},
          body:JSON.stringify({content:parts[i],metadata:{source:f.name,chunk:i}})});
        if(r.ok){done++;break}
        if(r.status===429){
          const wait=parseInt(r.headers.get('Retry-After')||'6',10);
          st.textContent='rate limited; waiting '+wait+'s ('+done+'/'+total+')…';
          await new Promise(res=>setTimeout(res,wait*1000));
          continue;
        }
        break; // non-retryable error: count as failed, move on
      }
      st.textContent='ingesting '+done+'/'+total+' chunks…';
    }
  }
  st.textContent='ingested '+done+'/'+total+' chunks';
}
// health badge, polled like the reference sidebar's backend check
async function health(){
  const dot=document.getElementById('dot'), hs=document.getElementById('hstat');
  try{
    const d=await (await fetch('/health')).json();
    dot.style.background=d.status==='healthy'?'#2a2':'#d92';
    hs.textContent=d.status+' · '+Math.round(d.uptime_s)+'s';
  }catch(e){dot.style.background='#d22';hs.textContent='unreachable'}
}
health(); setInterval(health, 15000);
</script></body></html>"""


def _client_ip(request: web.Request, trust_proxy: bool = False) -> str:
    """Socket peer address; X-Forwarded-For only when explicitly deployed
    behind a trusted proxy — otherwise any client could mint a fresh IP per
    request and walk straight past the per-IP rate limiter."""
    peer = request.transport.get_extra_info("peername") if request.transport else None
    ip = peer[0] if peer else "unknown"
    if trust_proxy:
        forwarded = request.headers.get("X-Forwarded-For", "").split(",")[0].strip()
        if forwarded:
            ip = forwarded
    return ip


async def _json_body(request: web.Request):
    """Malformed JSON is a client error (422 with a field list), not a 500."""
    if not request.can_read_body:
        return {}
    try:
        return await request.json()
    except (json.JSONDecodeError, UnicodeDecodeError) as exc:
        raise SchemaError([{"field": "body", "error": f"invalid JSON: {exc}"}]) from exc


@web.middleware
async def error_middleware(request: web.Request, handler):
    """Central exception → JSON error mapping (reference app.py:284-297)."""
    try:
        return await handler(request)
    except SchemaError as exc:
        return web.json_response({"error": "validation_error", "details": exc.errors}, status=422)
    except SentioError as exc:
        resp = web.json_response(exc.to_dict(), status=exc.status)
        # rate limits AND load sheds (ServiceOverloaded → 429/503) carry a
        # retry hint; one mapping so every shed response tells the caller
        # when coming back is worthwhile
        retry = exc.details.get("retry_after_s")
        if retry:
            resp.headers["Retry-After"] = str(max(int(retry), 1))
        return resp
    except web.HTTPException as exc:
        # an HTTPException IS a response — returning it (rather than
        # re-raising) lets the outer security-header middleware stamp it
        return exc
    except Exception as exc:  # noqa: BLE001
        status, body = ErrorHandler.handle(exc)
        return web.json_response(body, status=status)


@web.middleware
async def security_headers_middleware(request: web.Request, handler):
    response = await handler(request)
    for key, value in SECURITY_HEADERS.items():
        response.headers.setdefault(key, value)
    return response


def _make_observability_middleware(container: DependencyContainer):
    @web.middleware
    async def observability_middleware(request: web.Request, handler):
        """Rate limiting + request metrics (reference app.py:259-281).
        Error responses are synthesized in the OUTER error middleware, so
        metrics are recorded in a finally with the mapped status — error
        rates must be visible in /metrics, not just 2xx traffic."""
        path = request.path
        t0 = time.perf_counter()
        status = 500
        metrics = get_metrics()
        # queue-depth gauge: the k8s HPA scales TPU slices on this signal
        # (deploy/kubernetes/hpa.yaml) — probes/metrics scrapes excluded
        work = not path.startswith(("/health", "/metrics"))
        if work:
            metrics.adjust_inflight(+1)
        try:
            if work and path != "/":
                # uploads are ingest work — they share /embed's tight bucket
                endpoint = "/embed" if path in ("/embed", "/upload") else "*"
                ip = _client_ip(request, trust_proxy=container.settings.serve.trust_proxy_headers)
                container.rate_limiter.check(ip, endpoint)
            response = await handler(request)
            status = response.status
            return response
        except SchemaError:
            status = 422
            raise
        except (RateLimitError, SentioError) as exc:
            status = exc.status
            raise
        except web.HTTPException as exc:
            status = exc.status
            raise
        finally:
            if work:
                metrics.adjust_inflight(-1)
            metrics.record_request(path, status, time.perf_counter() - t0)

    return observability_middleware


def _make_auth_middleware(container: DependencyContainer):
    open_paths = ("/health", "/metrics", "/", "/auth/token")

    @web.middleware
    async def auth_middleware(request: web.Request, handler):
        auth = container.auth_manager
        if auth is None or request.path.startswith(open_paths[:2]) or request.path in open_paths:
            return await handler(request)
        header = request.headers.get("Authorization", "")
        api_key = request.headers.get("X-API-Key", "")
        try:
            if header.startswith("Bearer "):
                request["auth"] = auth.verify_token(header[7:])
            elif api_key:
                request["auth"] = auth.verify_api_key(api_key)
            else:
                raise web.HTTPUnauthorized(
                    text=json.dumps({"error": "missing credentials"}),
                    content_type="application/json",
                )
        except web.HTTPException:
            raise
        except Exception:  # noqa: BLE001 — invalid token/key
            raise web.HTTPUnauthorized(
                text=json.dumps({"error": "invalid credentials"}),
                content_type="application/json",
            )
        return await handler(request)

    return auth_middleware


# ---------------------------------------------------------------- endpoints


async def ui_page(request: web.Request) -> web.Response:
    # the inline chat page needs its own CSP (the global default-src 'none'
    # would block the inline script/style)
    return web.Response(
        text=_UI_PAGE,
        content_type="text/html",
        headers={
            "Content-Security-Policy":
                "default-src 'none'; script-src 'unsafe-inline'; "
                "style-src 'unsafe-inline'; connect-src 'self'"
        },
    )


def _resolve_deadline_ts(request: web.Request, req, serve_cfg, t_received: float) -> Optional[float]:
    """Absolute perf_counter deadline for this request, counted from its
    RECEIPT (the time its body took to arrive and parse is the caller's, and a
    record's ``deadline_ms`` is then the budget as given, whatever the host's
    speed): body ``deadline_ms``
    beats the ``X-Deadline-Ms`` header beats the serve default (0 = none).
    A malformed header is ignored rather than 422'd — proxies inject headers
    the caller never wrote."""
    deadline_ms = req.deadline_ms
    if deadline_ms is None:
        raw = request.headers.get("X-Deadline-Ms", "")
        if raw:
            try:
                value = float(raw)
                if 0 < value <= MAX_DEADLINE_MS:
                    deadline_ms = value
            except ValueError:
                pass
    if deadline_ms is None and serve_cfg.default_deadline_ms > 0:
        deadline_ms = serve_cfg.default_deadline_ms
    if deadline_ms is None:
        return None
    return t_received + deadline_ms / 1e3


def _resolve_resumable(request: web.Request, req) -> bool:
    """Per-request stream-resumption opt-out: body ``resumable`` beats the
    ``X-Resumable`` header beats the server default (resume). Only the
    explicit falsy header values opt out — proxies inject headers the
    caller never wrote, so anything unrecognized means default."""
    if req.resumable is not None:
        return bool(req.resumable)
    raw = request.headers.get("X-Resumable", "").strip().lower()
    if raw in ("0", "false", "no", "off"):
        return False
    return True


_TENANT_RE = None


def _request_tenant(request: web.Request) -> tuple[str, str]:
    """(tenant, priority) for this request. The tenant key is the auth
    principal when auth is on (a client cannot spoof another tenant by
    header once authenticated), else a header-safe ``X-Tenant`` value, else
    the shared default tenant. ``X-Priority: batch`` opts into the
    shed-earlier tier; anything else is interactive."""
    global _TENANT_RE
    if _TENANT_RE is None:
        import re

        _TENANT_RE = re.compile(r"[A-Za-z0-9._:-]{1,64}")
    from sentio_tpu.runtime.replica import (
        DEFAULT_TENANT,
        PRIORITY_BATCH,
        PRIORITY_INTERACTIVE,
    )

    auth = request.get("auth")
    if auth and auth.get("sub"):
        tenant = f"user:{auth['sub']}"
    else:
        raw = request.headers.get("X-Tenant", "").strip()
        tenant = raw if raw and _TENANT_RE.fullmatch(raw) else DEFAULT_TENANT
    priority = (
        PRIORITY_BATCH
        if request.headers.get("X-Priority", "").strip().lower() == "batch"
        else PRIORITY_INTERACTIVE
    )
    return tenant, priority


async def chat(request: web.Request) -> web.Response:
    # receipt: where the request's span tree and its pool_wait stage start
    t_received = time.perf_counter()
    container: DependencyContainer = request.app["container"]
    body = await _json_body(request)
    req = parse_chat_request(body, container.settings.serve)
    deadline_ts = _resolve_deadline_ts(request, req, container.settings.serve, t_received)
    tenant, priority = _request_tenant(request)
    # shed BEFORE any work is spent on the request and, for a stream, before
    # response.prepare commits the 200 status line (an SSE stream can only
    # degrade after that, never 429/503). The request threads are as many as
    # the service admits, so a caller the engine would refuse is told so
    # here and none is parked in front of it
    service = container.peek("generation_service")
    if service is not None and hasattr(service, "check_admission"):
        try:
            if getattr(service, "supports_tenants", False):
                # replica tier: WFQ tenant check + the routed replica's
                # own admission, exactly as the submit will see them
                service.check_admission(
                    deadline_ts, tenant=tenant, priority=priority,
                    prompt=req.question,
                )
            else:
                service.check_admission(deadline_ts)
        except SentioError:
            raise  # typed shed/deadline → 429/503/504 with Retry-After
        except Exception:  # noqa: BLE001 — closed/broken paged path
            # this pre-check only turns a typed shed into a status
            # before the work starts; what else the service raises, the
            # request itself reports
            logger.debug("admission pre-check skipped", exc_info=True)
    if req.stream:
        return await _chat_stream(request, container, req, deadline_ts,
                                  tenant=tenant, priority=priority,
                                  resumable=_resolve_resumable(request, req),
                                  t_received=t_received)
    result = await container.chat_handler.process_chat_request(
        question=req.question,
        top_k=req.top_k,
        temperature=req.temperature,
        mode=req.mode,
        thread_id=req.thread_id,
        deadline_ts=deadline_ts,
        tenant=tenant,
        priority=priority,
        t_received=t_received,
    )
    return web.json_response(result)


async def _chat_stream(request: web.Request, container: DependencyContainer, req,
                       deadline_ts: Optional[float] = None,
                       tenant: Optional[str] = None,
                       priority: Optional[str] = None,
                       resumable: bool = True,
                       t_received: Optional[float] = None) -> web.StreamResponse:
    """SSE token streaming (reference generator.py:298-333 / openai SSE).
    The whole pipeline — retrieval, rerank, selection, then the generator's
    token iterator — runs on ONE of the server's request threads
    (``DependencyContainer.request_threads``: as many as the generation
    service admits) and is pumped into the response via a queue. The
    flight-record id travels in ``X-Request-Id``
    (client-pinnable via ``thread_id``) so a streamed request's trace is
    retrievable from /debug/flight afterwards.

    **Session continuity**: a replica dying mid-stream does NOT surface
    here when a fronting ReplicaSet can resume it — the token iterator
    below is the set's ``generate_stream``, whose resume-by-replay splices
    the delivered prefix onto a survivor and keeps yielding post-splice
    pieces, so the SSE wire sees one uninterrupted, gap- and
    duplicate-free stream (the keepalive loop bridges the replay-prefill
    gap). Only an opted-out or budget-exhausted stream still gets the
    typed mid-stream error event (wire format unchanged)."""
    import re
    import uuid

    from sentio_tpu.infra.tracing import stream_written

    # the id is reflected into a response header — a client-supplied
    # thread_id only pins it when header-safe (no CR/LF/control/unicode),
    # otherwise the client reads the generated id back from X-Request-Id
    request_id = (
        req.thread_id
        if req.thread_id and re.fullmatch(r"[A-Za-z0-9._:-]{1,128}", req.thread_id)
        else uuid.uuid4().hex[:12]
    )
    response = web.StreamResponse(
        headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
            "X-Request-Id": request_id,
        }
    )
    await response.prepare(request)
    loop = asyncio.get_running_loop()
    queue: asyncio.Queue = asyncio.Queue(maxsize=256)
    stop = threading.Event()

    def put(item) -> bool:
        # blocking put with backpressure AND a disconnect escape hatch: when
        # the consumer stops draining (client gone), `stop` is set and the
        # producer exits instead of blocking a request thread forever
        while not stop.is_set():
            fut = asyncio.run_coroutine_threadsafe(queue.put(item), loop)
            try:
                fut.result(timeout=0.5)
                return True
            except concurrent.futures.TimeoutError:
                # cancel() False = the put actually completed in the race
                # window — treat as delivered or the token would be enqueued
                # twice on retry
                if not fut.cancel():
                    return True
            except Exception:  # noqa: BLE001 — loop closed / cancelled
                return False
        return False

    def produce() -> None:
        # pipeline + degradation live in the handler, mirroring /chat; the
        # handler yields typed events — ("sources", [...]) before the first
        # token, ("token", str) increments, ("verdict", {...}) after the
        # stream (full graph-stage parity: select + verify ride the stream).
        # With VERIFY_MODE=async|gated the handler yields ("done", "")
        # itself as soon as the answer completes, then a trailing
        # ("verify", {...}) verdict — the internal ("eos", "") sentinel
        # (never written to the wire) marks producer exhaustion either way.
        for kind, payload in container.chat_handler.stream_chat_sync(
            question=req.question,
            top_k=req.top_k,
            temperature=req.temperature,
            mode=req.mode,
            request_id=request_id,
            deadline_ts=deadline_ts,
            tenant=tenant,
            priority=priority,
            resumable=resumable,
            t_received=t_received,
        ):
            if not put((kind, payload)):
                return
        put(("eos", ""))

    task = container.request_threads.run(produce)
    # SSE liveness: while the producer is silent (long prefill, a slow —
    # or wedged — decode pump), emit comment keepalives so the client can
    # distinguish "still working" from a dead connection and apply its own
    # timeout policy. Comments are invisible to EventSource consumers.
    keepalive_s = getattr(container.settings.serve, "sse_keepalive_s", 0.0)
    wrote_done = False
    try:
        while True:
            try:
                if keepalive_s and keepalive_s > 0:
                    kind, payload = await asyncio.wait_for(
                        queue.get(), timeout=keepalive_s)
                else:
                    kind, payload = await queue.get()
            except asyncio.TimeoutError:
                await response.write(b": keepalive\n\n")
                continue
            if kind == "done":
                # answer complete; the connection STAYS OPEN when a
                # trailing async-verify verdict is still coming (the
                # keepalive loop above bridges the audit decode)
                await response.write(b"data: [DONE]\n\n")
                wrote_done = True
                continue
            if kind == "eos":
                if not wrote_done:
                    await response.write(b"data: [DONE]\n\n")
                break
            await response.write(f"data: {json.dumps({kind: payload})}\n\n".encode())
            if kind == "token":
                # the pump queued these tokens a while ago: one stream_lag sample
                stream_written(request_id)
    finally:
        stop.set()
        # drain so a producer blocked mid-put resolves, then join it
        while not queue.empty():
            queue.get_nowait()
        await task
    await response.write_eof()
    return response


async def embed(request: web.Request) -> web.Response:
    container: DependencyContainer = request.app["container"]
    body = await _json_body(request)
    req = parse_embed_request(body, container.settings.serve)
    stats = await asyncio.to_thread(container.ingestor.ingest_document, req.content, req.metadata)
    return web.json_response({"status": "ok", "stats": stats.to_dict()})


async def upload(request: web.Request) -> web.Response:
    """Multipart binary-document ingest — the browser upload path.

    Closes the reference UI's file flow (streamlit_app.py:27-318 there,
    which ingests PDF/TXT client-side): files post as multipart/form-data,
    each part spools to a temp file so the suffix-dispatched readers in
    ops/ingest.py (docx via stdlib zipfile+XML, gated pdf, text formats)
    parse it, then the server chunks + embeds + indexes. Per-file errors
    are reported per file; one bad document never fails the batch."""
    container: DependencyContainer = request.app["container"]
    if not (request.content_type or "").startswith("multipart/"):
        raise SchemaError([{"field": "body", "error": "multipart/form-data required"}])
    # the request's own flight record: each file's ``ingest.*`` stages are
    # spans on it (the first 64 of them; the record's ``ingest`` sums them all)
    from sentio_tpu.infra.flight import get_flight_recorder

    recorder = get_flight_recorder()
    upload_id = f"upload-{next(_upload_seq)}"
    recorder.start_request(upload_id, t_received=time.perf_counter(), endpoint="/upload")
    ingested = {"files": 0, "docs": 0, "chunks": 0, "stage_ms": {}}
    try:
        return await _upload(request, container, upload_id, ingested)
    finally:
        recorder.finish_request(upload_id, ingest=dict(
            ingested, index_size=container.dense_index.size,
            stage_ms={k: round(v, 3) for k, v in ingested["stage_ms"].items()}))


_upload_seq = itertools.count(1)


async def _upload(request: web.Request, container: DependencyContainer, upload_id: str,
                  ingested: dict) -> web.Response:
    import tempfile

    from sentio_tpu.ops.ingest import SUPPORTED_SUFFIXES

    reader = await request.multipart()
    files: list[dict] = []
    # one cap for the WHOLE request (all parts): aiohttp's client_max_size
    # guards read()/post() but multipart() + read_chunk stream unbounded,
    # and a per-part cap would still let one request carry unlimited parts
    cap = container.settings.serve.max_upload_mb * 1024 * 1024
    total = 0
    while True:
        part = await reader.next()
        if part is None:
            break
        keep = part.filename is not None
        name = os.path.basename(part.filename) if keep else ""
        suffix = Path(name).suffix.lower()
        if keep and suffix not in SUPPORTED_SUFFIXES:
            files.append({"filename": name, "error": f"unsupported type {suffix!r}"})
            keep = False
        # EVERY part's bytes count against the cap, including skipped ones —
        # advancing to the next part drains the current one through the
        # server either way, so uncounted skips would let one request
        # stream unlimited data under an 'unsupported type' label
        chunks: list[bytes] = []
        over = False
        while True:
            chunk = await part.read_chunk(64 * 1024)
            if not chunk:
                break
            total += len(chunk)
            if total > cap:
                over = True
                break
            if keep:
                chunks.append(chunk)
        if not keep and not over:
            continue
        if over:
            # stop reading ENTIRELY (don't stream the remainder to /dev/null)
            # but keep the per-file record of everything already ingested so
            # the client knows what not to re-send
            files.append({
                "filename": name,
                "error": f"upload exceeds {container.settings.serve.max_upload_mb} MB request cap",
            })
            return web.json_response({"status": "error", "files": files}, status=413)
        data = b"".join(chunks)
        with tempfile.TemporaryDirectory(prefix="sentio-upload-") as tmp:
            # keep the original (sanitized) name: source metadata and the
            # suffix dispatch in load_file both come from the path
            path = Path(tmp) / name
            path.write_bytes(data)

            def parse_and_index(ing, p=path, src=name):
                docs = ing.load_file(p)
                for doc in docs:
                    # the browser's filename, not the ephemeral temp path
                    doc.metadata["source"] = src
                return ing.ingest_documents(docs)

            try:
                with tracing.span("ingest", request_id=upload_id, filename=name):
                    stats = await asyncio.to_thread(parse_and_index, container.ingestor)
            except Exception as exc:  # noqa: BLE001 — per-file isolation
                files.append({"filename": name, "error": str(exc)})
                continue
        ingested["files"] += 1
        ingested["docs"] += stats.documents_loaded
        ingested["chunks"] += stats.chunks_stored
        for stage, seconds in stats.stage_s.items():
            ingested["stage_ms"][stage] = ingested["stage_ms"].get(stage, 0.0) + seconds * 1e3
        entry = {"filename": name, **stats.to_dict()}
        if stats.errors:
            entry["error"] = "; ".join(str(e) for e in stats.errors[:3])
        files.append(entry)
    if not files:
        raise SchemaError([{"field": "file", "error": "no file parts in form data"}])
    ok = any("error" not in f for f in files)
    return web.json_response({"status": "ok" if ok else "error", "files": files},
                             status=200 if ok else 422)


async def clear(request: web.Request) -> web.Response:
    container: DependencyContainer = request.app["container"]
    n = await asyncio.to_thread(container.ingestor.clear)
    return web.json_response({"status": "ok", "documents_removed": n})


async def health(request: web.Request) -> web.Response:
    report = request.app["container"].health_handler.basic()
    # "degraded" (1 ≤ healthy replicas < N) stays 200: the pod is serving
    # at reduced capacity and the supervisor is rebuilding — a 503 here
    # would make k8s restart a half-alive pod and lose the survivors too
    status = 503 if report["status"] == "unhealthy" else 200
    return web.json_response(report, status=status)


async def health_detailed(request: web.Request) -> web.Response:
    report = await request.app["container"].health_handler.detailed()
    status = 200 if report["status"] == "healthy" else 503
    return web.json_response(report, status=status)


async def health_ready(request: web.Request) -> web.Response:
    report = request.app["container"].health_handler.ready()
    return web.json_response(report, status=200 if report["ready"] else 503)


async def health_live(request: web.Request) -> web.Response:
    return web.json_response(request.app["container"].health_handler.live())


def _speculative_info(container: DependencyContainer) -> dict:
    """Honest operator view of the draft-checkpoint knob: active only when
    some serving path actually speculates; otherwise names the exclusion."""
    gen = container.settings.generator
    out: dict = {"draft_configured": bool(gen.draft_checkpoint_path)}
    if not gen.draft_checkpoint_path or gen.provider != "tpu":
        out["active"] = False
        return out
    reason = ""
    if container.mesh is not None:
        reason = "device mesh configured (paged speculation is single-chip)"
    elif gen.prefill_chunk:
        reason = ("PREFILL_CHUNK set (chunked prefill excludes paged "
                  "speculation)")
    out["active"] = not reason
    if reason:
        out["ignored_reason"] = reason
    return out


def _params_of(component) -> dict:
    """What an encoder's weights are HELD as (ops/embedder.py, ops/reranker.py:
    cast once at load to the dtype the forward computes in) and their bytes on
    the device; nulls for a fake with no model."""
    return {"param_dtype": getattr(component, "param_dtype", None),
            "param_bytes": getattr(component, "param_bytes", None)}


def _model_config_of(component) -> Optional[dict]:
    """The config a component's model ACTUALLY runs at (None for fakes with
    no model) — a reranker with no checkpoint is ``EncoderConfig.tiny()``,
    and only this shows a toy standing on the serving path."""
    cfg = getattr(component, "model_config", None)
    return dataclasses.asdict(cfg) if dataclasses.is_dataclass(cfg) else None


async def info(request: web.Request) -> web.Response:
    container: DependencyContainer = request.app["container"]
    settings = container.settings
    decoder = container.decoder
    service = container.peek("generation_service")
    serving = service.stats() if service is not None else {}
    return web.json_response(
        {
            "service": "sentio-tpu",
            "version": __import__("sentio_tpu").__version__,
            "retrieval": {
                "strategy": settings.retrieval.strategy,
                "fusion": settings.retrieval.fusion_method,
                "top_k": settings.retrieval.top_k,
                "corpus_size": container.dense_index.size,
                "bm25_backend": getattr(container.sparse_index, "backend", None),
            },
            "embedder": {
                "provider": settings.embedder.provider,
                "model": _model_config_of(container.embedder),
                **_params_of(container.embedder),
            },
            "reranker": {
                "enabled": settings.rerank.enabled,
                "kind": settings.rerank.kind,
                "model": _model_config_of(container.reranker),
                **_params_of(container.reranker),
            },
            "generator": {
                "provider": settings.generator.provider,
                "preset": settings.generator.model_preset,
                "model": _model_config_of(decoder),
                "verifier": settings.generator.use_verifier,
                # the paged decode path as the engine resolved it: page
                # representation, whether decode attention is the Pallas
                # page-table walk or the XLA gather, and whether prefill
                # attention is the flash kernel that knows a prior
                "kv_quant": serving.get("kv_quant"),
                "paged_attention": serving.get("paged_attention"),
                "prefill_attention": serving.get("prefill_attention"),
                # and whether a decode step writes its K and V rows by the
                # kernel that leaves the pool in HBM or by the XLA scatter
                "page_write": serving.get("page_write"),
                # a routed family only (null otherwise): how the decode
                # program's three grouped expert matmuls a layer are tiled,
                # ``[rows, tk, tn]``, and the grid steps an expert costs
                "expert_tiles": serving.get("expert_tiles"),
                "pool_hbm_bytes": serving.get("pool_hbm_bytes"),
                # of it, what one token keeps in the pages over all layers (``model`` says
                # which of them attend inside a window: a row holds the pages all the same)
                "kv_bytes_per_token": serving.get("kv_bytes_per_token"),
                # admissions by the lane they took: ``free`` held no request,
                # ``spent`` was handed on while its row's last tick was in flight
                "lane_admissions": {kind: serving.get(f"lane_admissions_{kind}")
                                    for kind in LANE_ADMISSION_KINDS},
                # a latent family only: what ONE token leaves in the pool a
                # layer (1,152 B at 512 + 64 in bf16)
                **({"pool_token_layer_bytes": serving["pool_token_layer_bytes"]}
                   if "pool_token_layer_bytes" in serving else {}),
                # a family with convolution layers only: of ``pool_hbm_bytes``,
                # the state it keeps per decode slot and per page beside K and V
                **({"conv_state_bytes": serving["conv_state_bytes"]}
                   if "conv_state_bytes" in serving else {}),
                # a family with Mamba layers only: of ``pool_hbm_bytes``, its
                # state per decode slot and the bounded pool of snapshots the
                # prefix cache chooses from (``SSM_SNAPSHOTS`` of them), with
                # how many a page boundary owns now; and whether a decode step
                # updates a slot's state by the kernel or by the XLA form
                **({key: serving.get(key) for key in ("ssm_state_bytes", "ssm_snapshot_bytes", "ssm_snapshots",
                                                      "ssm_snapshots_held", "ssm_update")}
                   if "ssm_state_bytes" in serving else {}),
                # a configured draft accelerates the decode tick
                # (runtime/paged_spec.py); its exclusions (chunked prefill,
                # device mesh) are surfaced here for operators
                "speculative": _speculative_info(container),
            },
            "device": (device_stats(container.mesh, decoder.model_config)
                       if decoder is not None else None),
            # how many /chat pipelines run at a time, and what said so
            # (the generation service's max_queue; asyncio's default width
            # where no engine is served)
            "request_threads": container.request_threads.width,
            "request_threads_from": container.request_threads.origin,
            # where this process keeps JAX's persistent compile cache
            # (infra/compile_cache.py; None = not placed, e.g. under tests)
            "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
            # what the start cost (infra/startup.py): process start → ready
            # tiled by phase, every compile of the process by part and cache
            # outcome (the warm-up's follow ``ready``), the ingest stages
            "startup": _startup_info(container),
        }
    )


def _startup_info(container: DependencyContainer) -> dict:
    ingestor = container.peek("ingestor")
    return {**startup.info(),
            "ingest": ingestor.stage_summary() if hasattr(ingestor, "stage_summary") else {}}


def _publish_serving_gauges(container: DependencyContainer):
    """Refresh decode-engine metrics at scrape time (occupancy, queue depth,
    free pages — the numbers an HPA or operator actually tunes against;
    prior rounds collected them in the engine but published them nowhere).
    Returns the stats dict (or None) so callers can embed it without a
    second, skew-prone lookup."""
    m = get_metrics()
    for model in ("embedder", "reranker"):  # the weights each encoder holds
        held = getattr(container.peek(model), "param_bytes", None)
        if held is not None:
            m.set_encoder_param_bytes(model, held)
    service = container.peek("generation_service")
    if service is None:  # never built (non-tpu provider / paged off)
        return None
    try:
        stats = service.stats()
    except Exception:  # noqa: BLE001 — metrics must not break the scrape
        return None
    for key in (
        "active_slots", "queued", "queued_inbox", "free_pages",
        "avg_active_slots", "max_active_slots",
        "ttft_p50_ms", "ttft_p95_ms", "spec_tokens_per_verify",
        # radix prefix cache: fraction of prompt tokens served read-only
        # from cached KV, and the pages the cache currently holds — the
        # two numbers that say whether prefix caching is paying for itself
        "prefix_hit_token_ratio", "prefix_cache_pages", "prefix_cache_nodes",
        # overload posture: admission bound and whether a drain is underway
        "max_queue", "draining",
        # static KV page-pool footprint (bytes) — halves under KV_QUANT=int8
        "pool_hbm_bytes",
        # a family with a snapshot pool only: the slots a page boundary owns now
        "ssm_snapshots_held",
    ):
        if key in stats:
            m.set_serving_stat(key, float(stats[key]))
    for event in ("ticks", "completed", "ttft_count",
                  "prefix_hits", "prefix_misses",
                  "prefix_hit_tokens", "prefix_miss_tokens",
                  # raw counters so Prometheus can compute a WINDOWED
                  # tokens-per-verify (the lifetime-average gauge above
                  # flattens draft-quality regressions on long uptimes)
                  "spec_verifies", "spec_emitted",
                  # overload & crash-containment outcomes (lifetime totals;
                  # sentio_tpu_shed_total{reason} carries the fine labels)
                  "shed", "expired", "cancelled", "requeued",
                  "tick_failures", "pump_leaked",
                  # cross-replica failover retries (ReplicaSet layer)
                  "failovers"):
        if event in stats:
            m.bump_serving_total(event, float(stats[event]))
    # pump duty cycle (infra/phases.py): host/device/idle fractions per
    # replica — host-fraction is THE GIL-pressure signal. A bare service
    # exports its own replica row; a ReplicaSet exports one per member.
    replica_rows = stats.get("replicas") or [stats]
    for row in replica_rows:
        duty = row.get("duty_cycle")
        if duty:
            m.record_duty_cycle(row.get("replica", 0), duty)
    # multi-replica tier: the aggregate keeps every dashboard working; the
    # replica-labeled gauge says WHICH replica is hot (occupancy/queue/pool
    # per replica — the signals that justify or indict the router)
    for replica_stats in stats.get("replicas", ()):  # ReplicaSet only
        replica = replica_stats.get("replica", 0)
        for key in ("active_slots", "queued", "queued_inbox", "free_pages",
                    "prefix_cache_pages", "prefix_hit_token_ratio",
                    "pool_hbm_bytes", "ttft_p50_ms", "completed", "shed"):
            if key in replica_stats:
                m.set_replica_stat(replica, key, float(replica_stats[key]))
    return stats


async def metrics_endpoint(request: web.Request) -> web.Response:
    _publish_serving_gauges(request.app["container"])
    return web.Response(
        body=get_metrics().export_prometheus(),
        content_type="text/plain",
        charset="utf-8",
    )


async def metrics_performance(request: web.Request) -> web.Response:
    from sentio_tpu.infra.monitoring import performance_monitor, resource_monitor

    serving = _publish_serving_gauges(request.app["container"])
    return web.json_response(
        {
            "metrics": get_metrics().export_json(),
            "system": performance_monitor.collect_system(),
            "verdict": resource_monitor.health_verdict(),
            "serving": serving,
        }
    )


def _stitch_flight_record(container: DependencyContainer, request_id: str,
                          record: dict) -> dict:
    """Splice worker-side flight truth into a router flight record.

    Thread-replica modes share one flight recorder, so the router record
    already carries the engine section and tick window — stamp
    ``engine_window: "local"`` and return. In process/socket mode the
    engine lives in worker processes: issue ``fetch_flight`` to every
    worker replica (the owner is whichever holds the record), re-base its
    tick timestamps onto the router's perf_counter timeline via the
    ClockSync shift, and merge the engine section in
    (``engine_window: "stitched"``). Workers that are dead or partitioned
    are reported EXPLICITLY in ``replicas_unavailable`` — a half-answer
    must never be silently indistinguishable from a full one. Runs
    blocking RPCs; call from a worker thread."""
    from sentio_tpu.infra.flight import get_flight_recorder

    service = container.peek("generation_service")
    members = list(getattr(service, "_services", None)
                   or ([service] if service is not None else []))
    fetchable = [svc for svc in members
                 if callable(getattr(svc, "fetch_flight", None))]
    if not fetchable:
        record["engine_window"] = "local"
        return record
    from sentio_tpu.infra.flight import shift_spans

    router_origin = get_flight_recorder().origin()
    unavailable: list[dict] = []
    stitched = False
    for svc in fetchable:
        try:
            reply = svc.fetch_flight(request_id=request_id)
        except Exception as exc:  # noqa: BLE001 — typed death, timeout, ...
            unavailable.append({
                "replica": getattr(svc, "replica_id", None),
                "error": type(exc).__name__,
            })
            continue
        wrec = reply.get("record")
        if not wrec:
            continue  # this worker never served the request
        shift, bound = svc.flight_shift_s(router_origin)
        engine = dict(wrec.get("engine") or {})
        if engine.get("t_submit_s") is not None:
            engine["t_submit_s"] = round(
                float(engine["t_submit_s"]) + shift, 6)
        merged_engine = dict(record.get("engine") or {})
        merged_engine.update(engine)
        record["engine"] = merged_engine
        ticks = []
        for tick in wrec.get("ticks") or []:
            shifted = dict(tick)
            if "t_s" in shifted:
                shifted["t_s"] = round(float(shifted["t_s"]) + shift, 6)
            ticks.append(shifted)
        if ticks:
            record["ticks"] = ticks
        # the worker wrote the engine-side stages: they ride in on the
        # same shift, under this record's root
        record["spans"] = (record.get("spans") or []) + shift_spans(
            [sp for sp in wrec.get("spans") or [] if sp["parent"] is not None],
            shift)
        if wrec.get("ticks_truncated"):
            record["ticks_truncated"] = True
        record["engine_window"] = "stitched"
        record["engine_replica"] = reply.get("replica")
        record["engine_epoch"] = reply.get("epoch")
        if bound is not None:
            record["clock_uncertainty_s"] = round(bound, 6)
        stitched = True
        break
    if not stitched:
        # process/socket mode but no worker produced the record: the
        # router-only view is all there is — say so, loudly
        record["engine_window"] = "remote"
    if unavailable:
        record["replicas_unavailable"] = unavailable
    return record


async def debug_flight(request: web.Request) -> web.Response:
    """One completed (or in-flight) request's flight record: its span tree
    (``spans``: one ``request`` root, every other span naming its parent;
    the request stages among them, ``stages_ms`` tiling receipt → first
    token), graph node timings, and the engine-tick window its decode rode
    (occupancy, queue depth, prefill/decode splits, row-steps, page-pool
    levels) plus TTFT/TPOT.
    In process/socket replica mode the engine tick window lives in the
    worker process — it is fetched on demand and clock-rebased into the
    router record (``engine_window`` says which view you got: ``local`` /
    ``stitched`` / ``remote``, with unreachable workers listed in
    ``replicas_unavailable``). ``?format=chrome`` returns the (stitched)
    record's window as a Chrome/Perfetto trace instead (open the JSON in
    ui.perfetto.dev): the tick slices with their phase decomposition, the
    request span, and the verify verdict on one timeline. Auth-gated when
    auth is enabled — /debug is NOT in the open-paths list, unlike
    /metrics — because records quote request shape and timing."""
    from sentio_tpu.infra.flight import get_flight_recorder

    container: DependencyContainer = request.app["container"]
    request_id = request.match_info["request_id"]
    record = get_flight_recorder().get(request_id)
    if record is None:
        raise web.HTTPNotFound(
            text=json.dumps({"error": f"no flight record for {request_id!r}"}),
            content_type="application/json",
        )
    record = await asyncio.to_thread(
        _stitch_flight_record, container, request_id, record)
    if request.query.get("format") == "chrome":
        from sentio_tpu.infra.chrome_trace import build_chrome_trace

        return web.json_response(build_chrome_trace(
            record.pop("ticks", []), [record]))
    return web.json_response(record)


async def debug_flight_summary(request: web.Request) -> web.Response:
    """Where the retained finished requests waited: count, mean and median
    per request stage (infra/phases.py), the conservation residual (the
    stages sum to each request's server-side TTFT by construction) and the
    retained ticks' counted row-steps; ``?last=N`` keeps the N requests
    that finished last (a load window without its warm-up).
    ``?format=chrome`` returns the whole ring — every retained tick and
    request lane — as one Chrome/Perfetto trace. This process's recorder
    only: with process or socket replicas the engine-side stages live in
    the workers' records."""
    from sentio_tpu.infra.flight import get_flight_recorder

    if request.query.get("format") == "chrome":
        from sentio_tpu.infra.chrome_trace import flight_to_chrome

        return web.json_response(flight_to_chrome())
    try:
        last = int(request.query.get("last", "0"))
    except ValueError:
        raise SchemaError([{"field": "last", "error": "must be a whole number"}]) from None
    return web.json_response(get_flight_recorder().stage_summary(last=max(last, 0)))


async def debug_profile(request: web.Request) -> web.Response:
    """On-demand windowed XLA profiling: arm ``jax.profiler`` for
    ``?seconds=N`` (0.1–60, default 3; at most ``PROFILE_MAX_SECONDS``, the
    answer says how long) and stop it, writing the device trace under ``?dir=`` / ``JAX_PROFILER_DIR`` / a tmp directory. Every
    pump iteration runs under a ``decode_tick`` step annotation carrying
    the flight tick number, its phases under ``tick.<phase>`` and the
    request stages under their names (infra/tracing.py), so the host plane
    of the trace says what the program was doing, on the device's clock.
    The Python tracer is off (a 4 s window with it holds 176k frame
    events); ``?python=1`` brings the frames back. Single-flight (the
    profiler is process-global); auth-gated like every /debug route.
    Blocking work runs on a worker thread — the event loop keeps serving
    while the window is open."""
    import tempfile

    from sentio_tpu.infra.tracing import profile_window

    try:
        seconds = float(request.query.get("seconds", "3"))
    except ValueError:
        raise SchemaError([{"field": "seconds",
                            "error": "must be a number"}]) from None
    if not 0.1 <= seconds <= 60.0:
        raise SchemaError([{"field": "seconds",
                            "error": "must be within [0.1, 60]"}])
    container: DependencyContainer = request.app["container"]
    log_dir = (
        request.query.get("dir")
        or container.settings.observability.profiler_dir
        or tempfile.mkdtemp(prefix="sentio-xla-profile-")
    )
    python_tracer = request.query.get("python", "0").lower() in ("1", "true", "yes")
    # the deployment's bound on a window (``PROFILE_MAX_SECONDS``): the answer's ``seconds`` says what was traced
    window = min(seconds, container.settings.observability.profile_max_seconds)
    outcome = await asyncio.to_thread(profile_window, window, log_dir, python_tracer)
    status = 200 if outcome.get("started") else 409
    return web.json_response({**outcome, "asked_seconds": seconds}, status=status)


async def auth_token(request: web.Request) -> web.Response:
    """Password → JWT pair (reference auth flow, utils/auth.py there)."""
    container: DependencyContainer = request.app["container"]
    auth = container.auth_manager
    if auth is None:
        raise web.HTTPNotFound(
            text=json.dumps({"error": "auth disabled"}), content_type="application/json"
        )
    body = await _json_body(request)
    username = body.get("username", "")
    password = body.get("password", "")
    tokens = auth.authenticate(username, password)
    return web.json_response(tokens)


# ------------------------------------------------------------------ assembly


def create_app(
    container: Optional[DependencyContainer] = None,
    settings: Optional[Settings] = None,
    initialize: bool = True,
) -> web.Application:
    setup_log_sanitization()
    container = container or DependencyContainer(settings=settings or get_settings())
    set_container(container)

    # security headers outermost so even synthesized error responses carry
    # them; error handling next so every inner exception becomes JSON
    app = web.Application(
        middlewares=[
            security_headers_middleware,
            error_middleware,
            _make_observability_middleware(container),
            _make_auth_middleware(container),
        ],
        # the 1 MiB default stays: /chat + /embed bodies are JSON and should
        # never approach it, and /upload streams multipart with its OWN
        # max_upload_mb cap (multipart() bypasses client_max_size anyway)
    )
    app["container"] = container

    app.router.add_get("/", ui_page)
    app.router.add_post("/chat", chat)
    app.router.add_post("/embed", embed)
    app.router.add_post("/upload", upload)
    app.router.add_post("/clear", clear)
    app.router.add_get("/health", health)
    app.router.add_get("/health/detailed", health_detailed)
    app.router.add_get("/health/ready", health_ready)
    app.router.add_get("/health/live", health_live)
    app.router.add_get("/info", info)
    app.router.add_get("/metrics", metrics_endpoint)
    app.router.add_get("/metrics/performance", metrics_performance)
    app.router.add_get("/debug/flight", debug_flight_summary)
    app.router.add_get("/debug/flight/{request_id}", debug_flight)
    app.router.add_get("/debug/profile", debug_profile)
    app.router.add_post("/auth/token", auth_token)

    async def on_startup(app: web.Application) -> None:
        if initialize:
            await asyncio.to_thread(container.initialize_all)
        from sentio_tpu.analysis.audit import fence

        if fence.enabled():
            # SENTIO_COMPILE_FENCE=1 (canary/CI pods): warm the paged
            # engine's single-request compile variants, then arm — any
            # LATER XLA compile at a registered jit family hard-fails the
            # tick with the offending family + abstract signature
            def _warm_and_arm() -> None:
                service = container.peek("generation_service")
                if service is None:
                    # nothing to warm (paged path off / lazy init): arming
                    # anyway would fail the FIRST request's cold compile
                    logger.warning(
                        "compile fence: no paged generation service; "
                        "fence NOT armed"
                    )
                    return
                stats = service.warmup()
                logger.info(
                    "compile fence: warmup compiled %d variants over "
                    "%d prompts; arming",
                    stats["xla_compiles"], stats["prompts"],
                )
                fence.arm()

            def _warm_and_arm_phase() -> None:
                with startup.phase("warmup"):
                    _warm_and_arm()

            await asyncio.to_thread(_warm_and_arm_phase)
        # everything is built: until the socket accepts is the ``listen`` phase
        startup.listening_from()

    async def on_cleanup(app: web.Application) -> None:
        # graceful drain BEFORE teardown: stop admitting (new submits shed
        # 503), give in-flight decodes the configured window to finish, then
        # close — callers mid-generation get answers, not connection resets
        service = container.peek("generation_service")
        if service is not None and hasattr(service, "drain"):
            try:
                outcome = await asyncio.to_thread(
                    service.drain, container.settings.serve.drain_deadline_s
                )
                if not outcome.get("drained", True):
                    logger.warning(
                        "shutdown drain abandoned %d in-flight request(s) "
                        "after %.1fs", outcome.get("abandoned", 0),
                        container.settings.serve.drain_deadline_s,
                    )
            except Exception:  # noqa: BLE001 — shutdown is best-effort
                logger.warning("shutdown drain failed", exc_info=True)
        container.cleanup()
        set_container(None)

    app.on_startup.append(on_startup)
    app.on_cleanup.append(on_cleanup)
    return app


def run_server(settings: Optional[Settings] = None) -> None:
    settings = settings or get_settings()
    app = create_app(settings=settings)
    logger.info("serving on %s:%d", settings.serve.host, settings.serve.port)

    def listening(*_lines: Any, **_kw: Any) -> None:
        # aiohttp calls its ``print`` once, when every site accepts: the
        # start is over (infra/startup.py closes and tiles the record)
        try:
            startup.mark_ready()
        except Exception:  # noqa: BLE001 — the account of a start must never stop the server it describes
            logger.warning("the startup record could not be closed", exc_info=True)

    web.run_app(app, host=settings.serve.host, port=settings.serve.port, print=listening)
