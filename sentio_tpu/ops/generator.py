"""LLMGenerator: citation-grounded answer generation over the TPU engine.

Parity with /root/reference/src/core/llm/generator.py:19-333 and
chat_adapter.py:29-94: numbered ``[n] Source … score`` context assembly with
an instruction footer, temperature-by-mode (fast/balanced/quality/creative =
0.0/0.3/0.2/0.7), sync + streaming paths, and a provider seam — the exact
swap point the reference used for OpenAI-compatible APIs — now dispatching
to the in-process continuous-batching service (runtime/service.py behind the
replica tier). An ``echo`` provider is the deterministic offline fake (the
reference's mock-mode test pattern).
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass, field
from typing import Iterator, Optional, Protocol, Sequence

from sentio_tpu.config import GeneratorConfig, get_settings
from sentio_tpu.models.document import Document
from sentio_tpu.ops.prompts import PromptBuilder

logger = logging.getLogger(__name__)


class ChatProvider(Protocol):
    """``request_id`` is the flight-recorder trace id (serving layer's
    query_id); providers that have no engine-side telemetry ignore it. The
    generator only forwards it when set, so minimal third-party/test
    providers without the kwarg keep working untraced."""

    name: str

    def chat(
        self, prompt: str, max_new_tokens: int, temperature: float,
        request_id: Optional[str] = None,
    ) -> str: ...
    def stream(
        self, prompt: str, max_new_tokens: int, temperature: float,
        request_id: Optional[str] = None,
    ) -> Iterator[str]: ...


@dataclass
class EchoProvider:
    """Deterministic fake: answers by quoting the top source. Lets the whole
    pipeline (graph, API, CLI, tests) run with zero hardware and stable
    output, like the reference's hash-mock embedder did for embeddings."""

    name: str = "echo"

    def chat(self, prompt: str, max_new_tokens: int, temperature: float,
             request_id: Optional[str] = None) -> str:
        line = ""
        for cand in prompt.splitlines():
            if cand.strip().startswith("[1]"):
                line = cand.strip()
                break
        if line:
            return f"Based on the provided sources, the most relevant finding is: {line}"
        return "No sources were provided, so no grounded answer is available."

    def stream(self, prompt: str, max_new_tokens: int, temperature: float,
               request_id: Optional[str] = None) -> Iterator[str]:
        text = self.chat(prompt, max_new_tokens, temperature)
        for i in range(0, len(text), 16):
            yield text[i : i + 16]


@dataclass
class TpuProvider:
    """Dispatches to the in-process TPU runtime: every chat call joins the
    SHARED decode batch of ``service`` (the continuous-batching pump over
    the paged KV pool, behind the replica tier) — concurrent requests
    coalesce on device instead of serializing. A failure of the service is
    the caller's to see: typed errors as they are, anything else as raised."""

    service: object  # ReplicaSet | PagedGenerationService
    name: str = "tpu"

    def _tenant_kwargs(self, tenant: Optional[str],
                       priority: Optional[str]) -> dict:
        """Tenant/priority kwargs, only when the attached service is the
        multi-replica tier (a bare PagedGenerationService takes neither)."""
        if not getattr(self.service, "supports_tenants", False):
            return {}
        out: dict = {}
        if tenant is not None:
            out["tenant"] = tenant
        if priority is not None:
            out["priority"] = priority
        return out

    @staticmethod
    def _fill_stats(stats: Optional[dict], result) -> None:
        """Copy a PagedResult's logprob accumulators into the caller's
        stats dict (the confidence gate's signal — ops/confidence.py)."""
        if stats is not None:
            stats.update(result.stats_dict())

    def _stream_takes(self, kwarg: str) -> bool:
        """Whether the attached service's ``generate_stream`` accepts
        ``kwarg`` — introspected ONCE per provider per kwarg, not per
        streamed request (the probe sits on the hot path)."""
        cache = getattr(self, "_stream_kwarg_ok", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_stream_kwarg_ok", cache)
        cached = cache.get(kwarg)
        if cached is None:
            import inspect

            try:
                cached = kwarg in inspect.signature(
                    self.service.generate_stream).parameters
            except (TypeError, ValueError):
                cached = False
            cache[kwarg] = cached
        return cached

    def _stream_takes_stats(self) -> bool:
        return self._stream_takes("stats_out")

    def chat(self, prompt: str, max_new_tokens: int, temperature: float,
             request_id: Optional[str] = None,
             deadline_ts: Optional[float] = None,
             tenant: Optional[str] = None,
             priority: Optional[str] = None,
             stats: Optional[dict] = None) -> str:
        result = self.service.generate(
            prompt, max_new_tokens=max_new_tokens, temperature=temperature,
            request_id=request_id, deadline_ts=deadline_ts,
            **self._tenant_kwargs(tenant, priority),
        )
        if result.finish_reason == "error":
            raise RuntimeError("paged decode failed")
        self._fill_stats(stats, result)
        return result.text

    def stream(self, prompt: str, max_new_tokens: int, temperature: float,
               request_id: Optional[str] = None,
               deadline_ts: Optional[float] = None,
               tenant: Optional[str] = None,
               priority: Optional[str] = None,
               stats: Optional[dict] = None,
               resumable: Optional[bool] = None) -> Iterator[str]:
        stream_kwargs = self._tenant_kwargs(tenant, priority)
        if stats is not None and self._stream_takes_stats():
            # only our own service implementations take stats_out; a
            # test fake with the bare generate_stream signature keeps
            # working (the gate then sees no logprobs and never skips)
            stream_kwargs["stats_out"] = stats
        if resumable is False and self._stream_takes("resumable"):
            # per-request opt-out of resume-by-replay (PR 14's knob,
            # ReplicaSet.generate_stream): a mid-stream replica death
            # then keeps the typed mid-stream error. Only the replica
            # tier takes it; bare services have nothing to resume.
            stream_kwargs["resumable"] = False
        yield from self.service.generate_stream(
            prompt, max_new_tokens=max_new_tokens, temperature=temperature,
            request_id=request_id, deadline_ts=deadline_ts,
            **stream_kwargs,
        )


@dataclass
class OpenAIProvider:
    """OpenAI-compatible remote chat provider — the pluggable alternative the
    reference keeps as its primary path (/root/reference/src/core/llm/
    providers/openai.py:44-314: httpx client against ``{base_url}/chat/
    completions``, bearer auth, retry loop, SSE streaming). Here it is the
    FALLBACK seam: the default provider is the in-process TPU engine, and
    this adapter exists for split deployments (retrieval on the TPU host,
    generation on a remote endpoint) and for measuring the API-baseline
    configs in eval/. Zero-egress images point it at loopback mocks."""

    base_url: str = "http://127.0.0.1:8000/v1"
    api_key: str = ""
    model: str = "default"
    timeout_s: float = 60.0
    max_retries: int = 2
    name: str = "openai"
    # endpoint-reported (or locally counted) token usage of the last
    # successful chat(); empty before the first call
    last_usage: dict = field(default_factory=dict)
    # guards base_url switches + client/retired-client bookkeeping: chat()
    # runs on concurrent worker threads, and unguarded 404 fallbacks could
    # flap base_url back and forth or drop a pooled client unclosed
    _lock: threading.Lock = field(
        default_factory=threading.Lock, init=False, repr=False, compare=False
    )

    def _client(self):
        """One pooled httpx.Client per provider — reused across calls and
        retries (a client per request would pay a TCP/TLS handshake each).
        Double-checked under the lock so two racing first calls cannot each
        build a client and strand one unclosed."""
        client = getattr(self, "_client_cached", None)
        if client is None:
            with self._lock:
                client = getattr(self, "_client_cached", None)
                if client is None:
                    import httpx

                    headers = {"Content-Type": "application/json"}
                    if self.api_key:
                        headers["Authorization"] = f"Bearer {self.api_key}"
                    client = httpx.Client(
                        base_url=self.base_url.rstrip("/"),
                        timeout=self.timeout_s, headers=headers,
                    )
                    object.__setattr__(self, "_client_cached", client)
        return client

    def close(self) -> None:
        with self._lock:
            doomed = []
            client = getattr(self, "_client_cached", None)
            if client is not None:
                doomed.append(client)
                object.__setattr__(self, "_client_cached", None)
            doomed.extend(getattr(self, "_retired_clients", []))
            object.__setattr__(self, "_retired_clients", [])
        for old in doomed:
            old.close()

    def _payload(self, prompt: str, max_new_tokens: int, temperature: float) -> dict:
        return {
            "model": self.model,
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max_new_tokens,
            "temperature": temperature,
        }

    def _alt_base(self) -> Optional[str]:
        """OpenRouter-style deployments vary between ``…/api/v1`` and
        ``…/v1`` (reference openai.py:124-144 there). A 404 on a base URL
        whose PATH contains ``/api`` gets ONE retry against the stripped
        base; a hit permanently switches the client. Only the path is
        rewritten — an ``api.`` hostname must survive untouched."""
        from urllib.parse import urlsplit, urlunsplit

        parts = urlsplit(self.base_url)
        if "/api/" in parts.path or parts.path.endswith("/api"):
            new_path = parts.path.replace("/api", "", 1)
            return urlunsplit(parts._replace(path=new_path))
        return None

    def _switch_base(self, new_base: str,
                     only_from: Optional[str] = None) -> bool:
        """Rebind the base URL WITHOUT closing the old client: concurrent
        serving threads may have requests in flight on it (closing would
        fail them mid-call). Superseded clients park until close().

        Compare-and-swap under the lock: with ``only_from`` set, the switch
        happens only while ``base_url`` still holds that value — a thread
        whose 404 raced another thread's already-completed fallback becomes
        a no-op instead of re-switching (or re-reverting) the URL out from
        under everyone. Returns whether THIS call performed the switch."""
        with self._lock:
            if only_from is not None and self.base_url != only_from:
                return False
            if self.base_url == new_base:
                return False
            old = getattr(self, "_client_cached", None)
            if old is not None:
                retired = getattr(self, "_retired_clients", None)
                if retired is None:
                    retired = []
                    object.__setattr__(self, "_retired_clients", retired)
                retired.append(old)
                object.__setattr__(self, "_client_cached", None)
            object.__setattr__(self, "base_url", new_base)
            return True

    def count_tokens(self, text: str) -> int:
        """Token estimate for budget math when the endpoint returns no
        ``usage`` block (reference openai.py:251-269 there). tiktoken when
        present; a words×4/3 estimate otherwise (not in the base image)."""
        try:
            import tiktoken  # noqa: PLC0415 — optional, absent in base image

            return len(tiktoken.encoding_for_model(self.model).encode(text))
        except Exception:  # noqa: BLE001 — any failure degrades to estimate
            return max(int(len(text.split()) * 4 / 3), 1)

    def _note_usage(self, body: dict, prompt: str, reply: str) -> None:
        """Keep the call's token counts (``last_usage``) — endpoint-reported
        ``usage`` when present (a reported 0 is honored), counted locally
        otherwise."""
        usage = body.get("usage") or {}
        completion = usage.get("completion_tokens")
        if completion is None:
            completion = self.count_tokens(reply)
        prompt_toks = usage.get("prompt_tokens")
        if prompt_toks is None:
            prompt_toks = self.count_tokens(prompt)
        object.__setattr__(self, "last_usage", {
            "prompt_tokens": int(prompt_toks),
            "completion_tokens": int(completion),
        })

    def chat(self, prompt: str, max_new_tokens: int, temperature: float,
             request_id: Optional[str] = None) -> str:
        import random
        import time

        last_exc: Exception | None = None
        for attempt in range(self.max_retries + 1):
            try:
                resp = self._client().post(
                    "/chat/completions",
                    json=self._payload(prompt, max_new_tokens, temperature),
                )
                if resp.status_code == 404 and not str(
                    resp.request.url
                ).startswith(self.base_url.rstrip("/")):
                    # raced a concurrent thread's fallback switch: this 404
                    # came from the RETIRED base — re-issue on the current
                    # client instead of failing the call hard
                    resp = self._client().post(
                        "/chat/completions",
                        json=self._payload(prompt, max_new_tokens, temperature),
                    )
                alt = self._alt_base() if resp.status_code == 404 else None
                if alt:
                    old = self.base_url
                    switched = self._switch_base(alt, only_from=old)
                    try:
                        resp = self._client().post(
                            "/chat/completions",
                            json=self._payload(prompt, max_new_tokens, temperature),
                        )
                    except Exception:
                        # probe blew up before any status — the switch is
                        # unverified, keep the configured base (but only if
                        # WE switched: a concurrent thread's verified switch
                        # must not be reverted by our failed probe)
                        if switched:
                            self._switch_base(old, only_from=alt)
                        raise
                    if resp.status_code >= 400 and switched:
                        # the alternate is no better — undo the switch so a
                        # genuinely-404 deployment keeps its configured base
                        self._switch_base(old, only_from=alt)
                resp.raise_for_status()
                body = resp.json()
                reply = body["choices"][0]["message"]["content"]
                self._note_usage(body, prompt, reply)
                return reply
            except Exception as exc:  # noqa: BLE001 — retry transport/5xx/429
                status = getattr(getattr(exc, "response", None), "status_code", None)
                if status is not None and 400 <= status < 500 and status != 429:
                    raise  # auth/config errors don't heal with retries
                last_exc = exc
                if attempt < self.max_retries:
                    time.sleep(min(2.0**attempt, 4.0) * (0.5 + random.random() / 2))
        raise RuntimeError(f"openai provider failed after {self.max_retries + 1} attempts") from last_exc

    def stream(self, prompt: str, max_new_tokens: int, temperature: float,
               request_id: Optional[str] = None) -> Iterator[str]:
        """SSE stream (``data: {...}`` lines, ``[DONE]`` sentinel). Falls back
        to one non-streaming call if the endpoint rejects stream=True."""
        import json as _json

        payload = {**self._payload(prompt, max_new_tokens, temperature), "stream": True}
        saw_sse = False
        try:
            body_lines: list[str] = []
            with self._client().stream(
                "POST", "/chat/completions", json=payload
            ) as resp:
                resp.raise_for_status()
                for line in resp.iter_lines():
                    if not line.startswith("data:"):
                        body_lines.append(line)
                        continue
                    saw_sse = True
                    data = line[len("data:"):].strip()
                    if data == "[DONE]":
                        return
                    try:
                        delta = _json.loads(data)["choices"][0]["delta"]
                    except (KeyError, IndexError, ValueError):
                        continue
                    chunk = delta.get("content")
                    if chunk:
                        yield chunk
            if not saw_sse:
                # endpoint ignored stream=True and sent one JSON completion
                reply = _json.loads("\n".join(body_lines))
                yield reply["choices"][0]["message"]["content"]
        except Exception:  # noqa: BLE001 — endpoints without SSE support
            if saw_sse:
                # the stream broke mid-answer — surfacing a silently
                # truncated reply as complete would be worse than failing
                raise
            yield self.chat(prompt, max_new_tokens, temperature)

    @classmethod
    def from_config(cls, cfg: GeneratorConfig) -> "OpenAIProvider":
        return cls(
            base_url=cfg.api_base or cls.base_url,
            api_key=cfg.api_key,
            model=cfg.api_model or cls.model,
            timeout_s=cfg.api_timeout_s,
        )


_PROVIDERS: dict[str, type] = {}


def register_provider(name: str):
    """Decorator registry (reference: llm/providers/__init__.py:12-41)."""

    def deco(cls):
        _PROVIDERS[name] = cls
        return cls

    return deco


register_provider("echo")(EchoProvider)
register_provider("tpu")(TpuProvider)
register_provider("openai")(OpenAIProvider)


def get_provider(name: str, **kwargs):
    cls = _PROVIDERS.get(name)
    if cls is None:
        raise ValueError(f"unknown LLM provider {name!r}; known: {sorted(_PROVIDERS)}")
    return cls(**kwargs)


@dataclass
class LLMGenerator:
    provider: ChatProvider = field(default_factory=EchoProvider)
    config: GeneratorConfig = field(default_factory=lambda: get_settings().generator)
    prompts: PromptBuilder = field(default_factory=PromptBuilder)

    # ---------------------------------------------------------- context build

    def prepare_context(self, documents: Sequence[Document]) -> str:
        """Numbered, citation-ready context block (reference
        generator.py:193-254): '[n] Source: … (score …)' headers + text."""
        if not documents:
            return "(no context documents)"
        blocks = []
        for i, doc in enumerate(documents, start=1):
            source = doc.metadata.get("source") or doc.metadata.get("source_file") or doc.id
            score = doc.score()
            header = f"[{i}] Source: {source} (score {score:.3f})"
            blocks.append(f"{header}\n{doc.content.strip()}")
        return "\n\n".join(blocks)

    def build_prompt(self, query: str, documents: Sequence[Document]) -> str:
        instruction = self.prompts.load("profile")
        context = self.prepare_context(documents)
        return self.prompts.build("retrieve", instruction=instruction, context=context, query=query)

    # ------------------------------------------------------------- generation

    def _method_accepts(self, method: str, kwarg: str) -> bool:
        """Whether the provider's ``method`` takes ``kwarg`` — externally
        registered providers with older signatures must keep working
        (untraced / deadline-blind) instead of TypeError-ing into the
        degradation ladder on all traffic. Introspected once per
        (method, kwarg)."""
        cache = getattr(self, "_accepts_kwarg", None)
        if cache is None:
            cache = self._accepts_kwarg = {}
        key = (method, kwarg)
        accepts = cache.get(key)
        if accepts is None:
            import inspect

            try:
                params = inspect.signature(getattr(self.provider, method)).parameters
                accepts = kwarg in params or any(
                    p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
                )
            except (TypeError, ValueError):  # builtins/C callables: assume yes
                accepts = True
            cache[key] = accepts
        return accepts

    def _trace_kwargs(
        self, method: str, request_id: Optional[str],
        deadline_ts: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        stats: Optional[dict] = None,
        resumable: Optional[bool] = None,
    ) -> dict:
        """The optional per-request context kwargs (trace id, absolute
        deadline, WFQ tenant key + priority tier, confidence-stats sink,
        stream-resumption opt-out) the provider's method is able to
        receive. ``resumable`` is forwarded only on opt-OUT (False) —
        True is every layer's default, so omitting it keeps minimal
        test/third-party providers working."""
        out: dict = {}
        if request_id and self._method_accepts(method, "request_id"):
            out["request_id"] = request_id
        if deadline_ts is not None and self._method_accepts(method, "deadline_ts"):
            out["deadline_ts"] = deadline_ts
        if tenant is not None and self._method_accepts(method, "tenant"):
            out["tenant"] = tenant
        if priority is not None and self._method_accepts(method, "priority"):
            out["priority"] = priority
        if stats is not None and self._method_accepts(method, "stats"):
            out["stats"] = stats
        if resumable is False and self._method_accepts(method, "resumable"):
            out["resumable"] = False
        return out

    def generate(
        self,
        query: str,
        documents: Sequence[Document],
        mode: Optional[str] = None,
        temperature: Optional[float] = None,
        max_new_tokens: Optional[int] = None,
        request_id: Optional[str] = None,
        deadline_ts: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        stats: Optional[dict] = None,
    ) -> str:
        prompt = self.build_prompt(query, documents)
        temp = temperature if temperature is not None else self.config.temperature(mode)
        return self.provider.chat(
            prompt,
            max_new_tokens=max_new_tokens or self.config.max_new_tokens,
            temperature=temp,
            **self._trace_kwargs("chat", request_id, deadline_ts,
                                 tenant, priority, stats),
        )

    def stream(
        self,
        query: str,
        documents: Sequence[Document],
        mode: Optional[str] = None,
        temperature: Optional[float] = None,
        max_new_tokens: Optional[int] = None,
        request_id: Optional[str] = None,
        deadline_ts: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        stats: Optional[dict] = None,
        resumable: Optional[bool] = None,
    ) -> Iterator[str]:
        prompt = self.build_prompt(query, documents)
        temp = temperature if temperature is not None else self.config.temperature(mode)
        yield from self.provider.stream(
            prompt,
            max_new_tokens=max_new_tokens or self.config.max_new_tokens,
            temperature=temp,
            **self._trace_kwargs("stream", request_id, deadline_ts,
                                 tenant, priority, stats, resumable),
        )

    def chat_raw(self, prompt: str, max_new_tokens: int, temperature: float,
                 request_id: Optional[str] = None,
                 deadline_ts: Optional[float] = None,
                 tenant: Optional[str] = None,
                 priority: Optional[str] = None) -> str:
        """Direct provider access (verifier path — shares the weights). A
        ``request_id`` ties the call into the flight recorder, so the
        verify node's engine admission shows up on the same trace as the
        generate node's; ``tenant``/``priority`` charge the verify decode
        to the REQUESTING tenant's WFQ quota instead of the shared default
        (a tenant's verify traffic must not ride free and starve others)."""
        return self.provider.chat(
            prompt, max_new_tokens=max_new_tokens, temperature=temperature,
            **self._trace_kwargs("chat", request_id, deadline_ts,
                                 tenant, priority),
        )


def create_generator(settings=None, service=None) -> LLMGenerator:
    """env→generator wiring (reference: llm/factory.py:14-69)."""
    settings = settings or get_settings()
    cfg = settings.generator
    if cfg.provider == "tpu" and service is not None:
        provider = TpuProvider(service=service)
    elif cfg.provider == "tpu":
        # no service supplied (tests, host-only dev) → deterministic echo
        provider = EchoProvider()
    elif cfg.provider == "openai":
        provider = OpenAIProvider.from_config(cfg)
    else:
        provider = get_provider(cfg.provider)
    return LLMGenerator(provider=provider, config=cfg)
