"""HTTP surface tests over the real ASGI-equivalent aiohttp app.

Mirrors the reference's API test pattern (src/tests/api/conftest.py there:
TestClient over the app with fake backends injected) — here the fakes are
the hash embedder + echo generator, so the WHOLE stack runs: middleware,
validation, rate limits, handlers, graph, indexes.
"""

import asyncio
import contextvars
import dataclasses
import os
import threading

import pytest
from aiohttp.test_utils import TestClient, TestServer

from sentio_tpu.config import (
    AuthConfig,
    EmbedderConfig,
    GeneratorConfig,
    MeshConfig,
    RerankConfig,
    ServeConfig,
    Settings,
)
from sentio_tpu.models.llama import LlamaConfig
from sentio_tpu.serve.app import create_app
from sentio_tpu.serve.dependencies import DependencyContainer

def fast_settings(**over) -> Settings:
    s = Settings(
        embedder=EmbedderConfig(provider="hash", dim=32),
        generator=GeneratorConfig(provider="echo", use_verifier=False, max_new_tokens=32),
        rerank=RerankConfig(enabled=True, kind="passthrough"),
    )
    for key, value in over.items():
        setattr(s, key, value)
    return s


def run(coro):
    return asyncio.run(coro)


async def with_client(settings, fn, container=None, middlewares=()):
    container = container or DependencyContainer(settings=settings)
    app = create_app(container=container)
    app.middlewares.extend(middlewares)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client, container)
    finally:
        await client.close()


async def seed(client, texts):
    for text in texts:
        resp = await client.post("/embed", json={"content": text})
        assert resp.status == 200, await resp.text()


class TestChatEndpoint:
    def test_chat_happy_path(self):
        async def body(client, container):
            await seed(client, ["jax compiles python to xla", "tpus have a systolic mxu"])
            resp = await client.post("/chat", json={"question": "what compiles to xla?"})
            assert resp.status == 200
            data = await resp.json()
            assert data["answer"]
            assert isinstance(data["sources"], list) and data["sources"]
            assert data["metadata"]["degraded"] is False
            assert "latency_ms" in data["metadata"]

        run(with_client(fast_settings(), body))

    def test_chat_validation_errors(self):
        async def body(client, container):
            for payload, field in [
                ({}, "question"),
                ({"question": ""}, "question"),
                ({"question": "x" * 3000}, "question"),
                ({"question": "ok", "top_k": 0}, "top_k"),
                ({"question": "ok", "top_k": 99}, "top_k"),
                ({"question": "ok", "temperature": 3.0}, "temperature"),
                ({"question": "ok", "mode": "bogus"}, "mode"),
            ]:
                resp = await client.post("/chat", json=payload)
                assert resp.status == 422, (payload, resp.status)
                data = await resp.json()
                assert any(e["field"] == field for e in data["details"])

        run(with_client(fast_settings(), body))

    def test_chat_user_top_k_respected(self):
        async def body(client, container):
            await seed(client, [f"fact number {i} about topic" for i in range(8)])
            resp = await client.post("/chat", json={"question": "facts about topic", "top_k": 2})
            data = await resp.json()
            assert len(data["sources"]) <= 2

        run(with_client(fast_settings(), body))

    def test_degradation_ladder_never_500s(self):
        class Boom:
            def invoke(self, *a, **k):
                raise RuntimeError("device on fire")

        async def body(client, container):
            container.override("graph", Boom())
            resp = await client.post("/chat", json={"question": "anything at all"})
            assert resp.status == 200
            data = await resp.json()
            assert data["metadata"]["degraded"] is True
            assert data["metadata"]["tier"] in ("query_cache", "disk_cache", "template", "apology")
            assert data["answer"]

        run(with_client(fast_settings(), body))

    def test_chat_stream_sse(self):
        async def body(client, container):
            await seed(client, ["streaming tokens over sse"])
            resp = await client.post(
                "/chat", json={"question": "stream me an answer", "stream": True}
            )
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/event-stream")
            raw = (await resp.read()).decode()
            assert "data:" in raw and "[DONE]" in raw

        run(with_client(fast_settings(), body))

    def test_chat_stream_sse_keepalive_during_silence(self):
        """ISSUE 10 satellite: while the producer is silent past the
        configured interval (a slow — or wedged — decode), the SSE wire
        carries comment keepalives so the client can tell 'still working'
        from a dead connection; real events still follow."""
        import time as _time

        async def body(client, container):
            def slow_stream(**kwargs):
                _time.sleep(0.4)  # silence > several keepalive intervals
                yield ("token", "late answer")

            container.chat_handler.stream_chat_sync = slow_stream
            resp = await client.post(
                "/chat", json={"question": "slow stream", "stream": True})
            assert resp.status == 200
            raw = (await resp.read()).decode()
            assert ": keepalive" in raw, raw
            assert "late answer" in raw and "[DONE]" in raw

        run(with_client(
            fast_settings(serve=ServeConfig(sse_keepalive_s=0.05)), body))


class TestEmbedAndClear:
    def test_embed_validates_and_indexes(self):
        async def body(client, container):
            resp = await client.post("/embed", json={"content": "a document body"})
            assert resp.status == 200
            data = await resp.json()
            assert data["stats"]["chunks_stored"] == 1
            assert container.dense_index.size == 1

            resp = await client.post("/embed", json={"content": ""})
            assert resp.status == 422

        run(with_client(fast_settings(), body))

    def test_embed_rate_limited(self):
        settings = fast_settings(
            serve=ServeConfig(rate_limit_embed_per_min=3, rate_limit_default_per_min=100)
        )

        async def body(client, container):
            statuses = []
            for i in range(5):
                resp = await client.post("/embed", json={"content": f"doc {i}"})
                statuses.append(resp.status)
            assert statuses[:3] == [200, 200, 200]
            assert 429 in statuses[3:]
            limited = await client.post("/embed", json={"content": "one more"})
            assert limited.headers.get("Retry-After")

        run(with_client(settings, body))

    def test_clear(self):
        async def body(client, container):
            await seed(client, ["to be deleted"])
            resp = await client.post("/clear")
            assert resp.status == 200
            assert (await resp.json())["documents_removed"] == 1
            assert container.dense_index.size == 0

        run(with_client(fast_settings(), body))


class TestHealthAndInfo:
    def test_health_suite(self):
        async def body(client, container):
            basic = await client.get("/health")
            assert basic.status == 200
            assert (await basic.json())["status"] == "healthy"

            live = await client.get("/health/live")
            assert (await live.json())["status"] == "alive"

            ready = await client.get("/health/ready")
            assert ready.status == 200  # create_app initializes eagerly

            detailed = await client.get("/health/detailed")
            assert detailed.status == 200
            report = await detailed.json()
            assert report["components"]["embedder"]["healthy"]
            assert report["components"]["dense_index"]["healthy"]
            # second call inside the 10s window is served from cache
            again = await (await client.get("/health/detailed")).json()
            assert again["cached"] is True

        run(with_client(fast_settings(), body))

    def test_health_replica_degraded_stays_200_unhealthy_503(self):
        """Replica failure domains on /health: 1 ≤ serving < N reports
        ``degraded`` with HTTP 200 (k8s must keep routing to the half-alive
        pod while the supervisor rebuilds), and ``unhealthy`` → 503 only at
        ZERO serving replicas (restarting is now the best move)."""

        class HalfAliveSet:
            def health_summary(self):
                return {
                    "status": "degraded", "healthy_replicas": 1,
                    "serving_replicas": 1, "total_replicas": 2,
                    "replicas": [
                        {"replica": 0, "state": "HEALTHY", "since_s": 5.0,
                         "rebuilds": 0},
                        {"replica": 1, "state": "REBUILDING",
                         "since_s": 1.0, "rebuilds": 0,
                         "reason": "engine latched broken"},
                    ],
                }

            def close(self):
                pass

        class DeadSet(HalfAliveSet):
            def health_summary(self):
                return {
                    "status": "unhealthy", "healthy_replicas": 0,
                    "serving_replicas": 0, "total_replicas": 2,
                    "replicas": [],
                }

        async def body(client, container):
            container.override("generation_service", HalfAliveSet())
            resp = await client.get("/health")
            assert resp.status == 200
            data = await resp.json()
            assert data["status"] == "degraded"
            assert data["replicas"]["serving_replicas"] == 1
            assert data["replicas"]["replicas"][1]["state"] == "REBUILDING"
            container.override("generation_service", DeadSet())
            resp = await client.get("/health")
            assert resp.status == 503
            assert (await resp.json())["status"] == "unhealthy"

        run(with_client(fast_settings(), body))

    def test_info(self):
        async def body(client, container):
            resp = await client.get("/info")
            data = await resp.json()
            assert data["service"] == "sentio-tpu"
            assert data["retrieval"]["strategy"] == "hybrid"
            assert data["generator"]["provider"] == "echo"

        run(with_client(fast_settings(), body))

    def test_info_and_metrics_say_what_the_encoders_hold(self):
        """``param_dtype`` / ``param_bytes`` of both encoders on /info and the
        ``sentio_tpu_encoder_param_bytes{model}`` gauge: the weights as the
        serving classes keep them (cast once at load to the forward's dtype);
        nulls and no series for a fake that holds no model."""

        async def body(client, container):
            info = await (await client.get("/info")).json()
            metrics = await (await client.get("/metrics")).text()
            return info, metrics

        info, metrics = run(with_client(fast_settings(), body))
        assert info["embedder"]["param_dtype"] is None and info["reranker"]["param_bytes"] is None
        settings = fast_settings(
            embedder=EmbedderConfig(provider="tpu", model_preset="tiny"),
            rerank=RerankConfig(enabled=True, kind="cross_encoder"))
        info, metrics = run(with_client(
            settings, body, container=DependencyContainer(settings=settings, mesh=None)))
        for model in ("embedder", "reranker"):
            held = info[model]["param_bytes"]
            # EncoderConfig.tiny(): 113.9 k parameters, all but the norms' 640 in bf16
            assert info[model]["param_dtype"] == "bfloat16" and 217_000 < held < 219_000, info[model]
            assert f'sentio_tpu_encoder_param_bytes{{model="{model}"}} {float(held)}' in metrics

    def test_info_and_health_of_the_served_decoder(self):
        """What /info and /health say of the decoder and the device is read
        from outside (the benchmark's server driver checks `generator.model`
        and `device.n_devices` before it measures): the key set is pinned,
        and so are the values a tiny preset gives."""

        def leaves(d, prefix=""):
            for k, v in d.items():
                yield prefix + k
                if isinstance(v, dict):
                    yield from leaves(v, prefix + k + ".")

        async def body(client, container):
            info = await (await client.get("/info")).json()
            assert set(info) == {
                "service", "version", "retrieval", "embedder", "reranker",
                "generator", "device", "request_threads",
                "request_threads_from", "compile_cache_dir", "startup"}
            assert set(info["startup"]) == {
                "process_start_unix", "ready_s", "phases", "weights", "compile", "ingest"}
            assert set(leaves(info["generator"])) == {
                "provider", "preset", "verifier", "kv_quant",
                "paged_attention", "prefill_attention", "page_write", "expert_tiles", "pool_hbm_bytes",
                "kv_bytes_per_token", "lane_admissions", "lane_admissions.free", "lane_admissions.spent",
                "speculative",
                "speculative.draft_configured", "speculative.active",
                "model", *("model." + f.name for f in
                           dataclasses.fields(LlamaConfig))}
            assert info["generator"]["model"] == dataclasses.asdict(
                LlamaConfig.tiny())
            # K and V of 2 kv heads of 16 over 2 layers, bf16 pages
            assert info["generator"]["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 2
            device = info["device"]
            assert {"platform", "kind", "n_devices", "mesh", "model"} \
                <= set(device) <= {"platform", "kind", "n_devices", "mesh",
                                   "model", "memory"}
            assert device["model"] == {"layers": 2, "dim": 64, "vocab": 512}
            assert device["n_devices"] == 8 and device["mesh"] is None
            detailed = await (await client.get("/health/detailed")).json()
            engine = detailed["components"]["engine"]
            assert engine["healthy"] is True
            assert {k: v for k, v in engine.items()
                    if k not in ("healthy", "memory")} \
                == {k: v for k, v in device.items() if k != "memory"}

        settings = fast_settings(generator=GeneratorConfig(
            provider="tpu", model_preset="tiny", use_verifier=False,
            max_new_tokens=8, kv_page_size=16, kv_max_pages_per_seq=8,
            max_batch_size=2,
        ))
        run(with_client(settings, body,
                        container=DependencyContainer(settings=settings,
                                                      mesh=None)))

    def test_metrics_endpoints(self):
        async def body(client, container):
            await client.post("/chat", json={"question": "count this request"})
            prom = await client.get("/metrics")
            assert prom.status == 200
            assert "requests" in (await prom.text())
            perf = await client.get("/metrics/performance")
            assert perf.status == 200
            assert "metrics" in await perf.json()

        run(with_client(fast_settings(), body))

    def test_security_headers(self):
        async def body(client, container):
            resp = await client.get("/health")
            assert resp.headers["X-Content-Type-Options"] == "nosniff"
            assert resp.headers["X-Frame-Options"] == "DENY"

        run(with_client(fast_settings(), body))

    def test_ui_page(self):
        async def body(client, container):
            resp = await client.get("/")
            assert resp.status == 200
            page = await resp.text()
            assert "sentio-tpu" in page
            # upload flow + health badge (reference streamlit_app.py:27-318:
            # client-side chunking into /embed, backend health indicator)
            assert 'type="file"' in page and "/embed" in page
            assert "chunks(" in page
            assert "/health" in page and 'id="dot"' in page

        run(with_client(fast_settings(), body))


    @pytest.mark.parametrize("gen_kw, mesh, named", [
        (dict(prefill_chunk=512), None, "PREFILL_CHUNK"),
        (dict(), MeshConfig(dp_size=8), "mesh"),
    ], ids=["prefill-chunk", "mesh"])
    def test_info_speculative_resolution(self, gen_kw, mesh, named):
        """/info names the exact reason a configured draft is inactive
        (operators must never see a dead knob reported as active)."""

        async def body(client, container):
            # a mesh is only built when a MESH_* axis asks for one
            assert (container.mesh is not None) == (mesh is not None)
            data = await (await client.get("/info")).json()
            spec = data["generator"]["speculative"]
            assert spec["draft_configured"] is True
            assert spec["active"] is False
            assert named in spec["ignored_reason"]

        settings = fast_settings(generator=GeneratorConfig(
            provider="tpu", model_preset="tiny", use_verifier=False,
            draft_checkpoint_path="/nonexistent-draft", **gen_kw,
        ), **({"mesh": mesh} if mesh else {}))
        container = (None if mesh else
                     DependencyContainer(settings=settings, mesh=None))
        run(with_client(settings, body, container=container))


class TestAuth:
    def test_auth_flow(self):
        settings = fast_settings(auth=AuthConfig(enabled=True, jwt_secret="s" * 32))

        async def body(client, container):
            # protected endpoint rejects anonymous
            resp = await client.post("/chat", json={"question": "who goes there"})
            assert resp.status == 401
            # health stays open
            assert (await client.get("/health")).status == 200

            container.auth_manager.create_user("ada", "Correct-Horse-Battery-9", role="admin")
            tok = await client.post(
                "/auth/token", json={"username": "ada", "password": "Correct-Horse-Battery-9"}
            )
            assert tok.status == 200
            access = (await tok.json())["access_token"]

            ok = await client.post(
                "/chat",
                json={"question": "authorized now"},
                headers={"Authorization": f"Bearer {access}"},
            )
            assert ok.status == 200

        run(with_client(settings, body))


class TestPagedServing:
    """Concurrent /chat requests must coalesce on the device: the paged
    continuous-batching service (runtime/service.py) is the default decode
    path, and concurrent requests share its decode ticks instead of
    serializing one generation per request (the round-1 gap)."""

    def test_concurrent_chat_through_paged_decode(self):
        settings = fast_settings(
            generator=GeneratorConfig(
                provider="tpu", model_preset="tiny", use_verifier=False,
                max_new_tokens=24, mode="fast",  # greedy: deterministic
                kv_page_size=16,
                kv_max_pages_per_seq=8, max_batch_size=4,
            ),
        )

        async def body(client, container):
            await seed(client, [
                "jax compiles python functions to xla programs",
                "tpus multiply matrices in a systolic array",
                "paged kv caches avoid memory fragmentation",
            ])
            service = container.generation_service
            assert service is not None, "paged decode service was not built"
            questions = [
                "what compiles python to xla?",
                "how do tpus multiply matrices quickly?",
                "why do paged kv caches help memory?",
                "what is a systolic array used for?",
            ]
            # overlap is guaranteed by construction: this container's engine
            # is fresh, so the first admitted request pays multi-second jit
            # tracing+compile inside its first tick, during which the other
            # (near-simultaneous) requests reach the inbox and join at the
            # next tick — decode ticks are ~ms, compile is ~s
            resps = await asyncio.gather(*[
                client.post("/chat", json={"question": q}) for q in questions
            ])
            for resp in resps:
                assert resp.status == 200, await resp.text()
                data = await resp.json()
                assert data["metadata"]["degraded"] is False
                assert data["metadata"]["generator"] == "tpu"
            stats = service.stats()
            assert stats["completed"] >= len(questions)
            assert stats["max_active_slots"] >= 2, (
                f"concurrent chats never shared a decode tick: {stats}"
            )
            # every per-request page returned to the pool after the burst —
            # except what the radix prefix cache retained (warmed template
            # head + the admitted prompts' full-page spans)
            held = stats.get("prefix_cache_pages", 0)
            assert stats["free_pages"] == stats["total_pages"] - 1 - held

            # the decode-engine stats must be PUBLISHED, not just collected:
            # prometheus gauges on /metrics, full dict on /metrics/performance
            prom = await (await client.get("/metrics")).text()
            assert 'sentio_tpu_serving_stat{stat="max_active_slots"}' in prom
            assert 'sentio_tpu_serving_stat{stat="free_pages"}' in prom
            assert 'sentio_tpu_serving_events_total{event="completed"}' in prom
            perf = await (await client.get("/metrics/performance")).json()
            assert perf["serving"]["completed"] >= len(questions)
            assert "avg_active_slots" in perf["serving"]

        run(with_client(settings, body))


class TestStreamingParity:
    """The SSE path must traverse the SAME graph semantics as /chat:
    select (dedup + token budget) before streaming, verify after
    (reference factory.py:191-208 — streaming uses identical stages)."""

    def test_stream_carries_sources_tokens_and_verdict(self):
        async def body(client, container):
            await seed(client, ["alpha document about streaming"])
            resp = await client.post(
                "/chat", json={"question": "what about streaming?", "stream": True}
            )
            assert resp.status == 200
            import json as _json

            events = []
            for line in (await resp.read()).decode().splitlines():
                if line.startswith("data:"):
                    data = line[5:].strip()
                    if data == "[DONE]":
                        events.append(("done", None))
                    else:
                        events.append(next(iter(_json.loads(data).items())))
            kinds = [k for k, _ in events]
            assert kinds[0] == "sources", kinds
            assert "token" in kinds
            assert "verdict" in kinds, "verifier must audit the streamed answer"
            assert kinds[-1] == "done"
            # verify comes after every token (post-stream audit)
            assert kinds.index("verdict") > max(
                i for i, k in enumerate(kinds) if k == "token"
            )

        settings = fast_settings()
        settings.generator.use_verifier = True
        run(with_client(settings, body))

    def test_stream_enforces_selector_budget(self):
        async def body(client, container):
            # many docs, tiny budget: selection must cap what streams
            await seed(client, [f"budget doc {i} " + "x" * 200 for i in range(8)])
            settings = container.settings
            settings.generator.context_token_budget = 60  # ~240 chars → 1 doc
            resp = await client.post(
                "/chat", json={"question": "budget doc", "top_k": 8, "stream": True}
            )
            import json as _json

            sources = None
            for line in (await resp.read()).decode().splitlines():
                if line.startswith("data:") and '"sources"' in line:
                    sources = _json.loads(line[5:].strip())["sources"]
                    break
            assert sources is not None, "stream must announce selected sources"
            assert 1 <= len(sources) <= 2, (
                f"token budget not enforced before streaming: {len(sources)} docs"
            )

        run(with_client(fast_settings(), body))


class TestPagedStreamingService:
    def test_generate_stream_matches_generate(self):
        from sentio_tpu.models.llama import LlamaConfig
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.service import PagedGenerationService

        cfg = LlamaConfig.tiny()

        def build():
            return PagedGenerationService(ContinuousBatchingEngine(
                model_config=cfg, max_slots=2, page_size=16,
                max_pages_per_seq=8, steps_per_tick=4,
            ))

        svc_a, svc_b = build(), build()
        try:
            want = svc_a.generate("stream parity prompt", max_new_tokens=12,
                                  temperature=0.0)
            pieces = list(svc_b.generate_stream(
                "stream parity prompt", max_new_tokens=12, temperature=0.0
            ))
            assert "".join(pieces) == want.text
            # incremental: more than one chunk for a 12-token answer at
            # steps_per_tick=4 (unless the model EOS'd in the first tick)
            if len(want.tokens) > 4:
                assert len(pieces) >= 2
        finally:
            svc_a.close()
            svc_b.close()


class TestSseStreamResume:
    """ISSUE 14 satellite: session continuity on the SSE wire. A replica
    dying mid-stream under a 2-replica set must be INVISIBLE to the SSE
    client — the delivered prefix replays onto the survivor and the wire
    carries one gapless, duplicate-free token sequence. Only an exhausted
    resume budget still surfaces the typed mid-stream error event (the
    pre-resume wire format, unchanged)."""

    QUESTION = "what compiles python to xla programs?"

    @staticmethod
    def _container(settings):
        # meshless replicas, like every direct-engine replica test: the
        # conftest forces 8 virtual CPU devices, and the dp-split mesh
        # path shards each replica's pool onto a submesh while the shared
        # weights stay on the full mesh — a layout mismatch that predates
        # (and is orthogonal to) stream resumption
        return DependencyContainer(settings=settings, mesh=None)

    @staticmethod
    def _settings(**serve_over):
        return fast_settings(
            generator=GeneratorConfig(
                provider="tpu", model_preset="tiny", use_verifier=False,
                max_new_tokens=24, mode="fast",  # greedy: deterministic
                kv_page_size=16,
                kv_max_pages_per_seq=8, max_batch_size=4,
                # a 24-token answer must span several delivered chunks or
                # there is no "mid-stream" window to kill inside: an idle
                # queue runs the BIG tick (decode_max_tick_steps, default
                # 64), which would ship the whole answer in one harvest
                decode_steps_per_tick=4, decode_max_tick_steps=4,
            ),
            serve=ServeConfig(
                replicas=2,
                # no supervisor thread: the drill flips exactly one fault
                # and must not race an async rebuild (supervised recovery
                # is drilled in test_chaos)
                replica_supervise=False,
                **serve_over,
            ),
        )

    @staticmethod
    def _sse_events(raw: str) -> list:
        import json as _json

        events = []
        for line in raw.splitlines():
            if not line.startswith("data:"):
                continue
            payload = line[len("data:"):].strip()
            if payload == "[DONE]":
                events.append(("done", None))
                continue
            obj = _json.loads(payload)
            (kind, value), = obj.items()
            events.append((kind, value))
        return events

    def test_midstream_kill_is_invisible_on_the_wire(self):
        from sentio_tpu.infra import faults
        from sentio_tpu.infra.flight import get_flight_recorder

        async def body(client, container):
            await seed(client, ["jax compiles python functions to xla"])
            # reference: the same question, no fault — greedy decode makes
            # the answer deterministic, so the faulted run must match it
            resp = await client.post("/chat", json={
                "question": self.QUESTION, "stream": True,
                "temperature": 0.0})
            assert resp.status == 200
            reference = self._sse_events((await resp.read()).decode())
            want = "".join(v for k, v in reference if k == "token")
            assert want, reference
            # the serve engine pipelines dispatch (decode_pipeline_depth=2,
            # the production default): tick 1's tokens harvest — and
            # deliver — at tick 2, so the FIRST tick whose death finds a
            # delivered chunk is tick 3 (skip=2). The victim replica is
            # whichever pump is decoding this one stream, so no routing
            # determinism is needed; the resume replays onto the idle
            # sibling
            faults.arm("paged.step", faults.FaultRule(
                error=RuntimeError("sse drill: midstream death"),
                times=1, skip=2))
            try:
                resp = await client.post("/chat", json={
                    "question": self.QUESTION, "stream": True,
                    "temperature": 0.0})
                assert resp.status == 200
                raw = (await resp.read()).decode()
            finally:
                faults.reset()
            events = self._sse_events(raw)
            got = "".join(v for k, v in events if k == "token")
            # gapless, duplicate-free: byte-identical to the no-fault run
            assert got == want, (got, want)
            kinds = [k for k, _ in events]
            assert "error" not in kinds, events
            assert kinds[-1] == "done", events
            # the resume is visible to OPERATORS: stats, flight, /metrics
            stats = container.generation_service.stats()
            assert stats["stream_resumes"] == 1, stats["stream_resumes"]
            resumed = [t for t in get_flight_recorder().timeline()
                       if t.get("event") == "stream_resumed"]
            assert resumed and resumed[-1]["replayed_tokens"] >= 1
            prom = await (await client.get("/metrics")).text()
            assert 'sentio_tpu_stream_resumes_total{outcome="resumed"}' \
                in prom

        settings = self._settings()
        run(with_client(settings, body, container=self._container(settings)))

    def test_exhausted_budget_keeps_typed_error_wire_format(self):
        from sentio_tpu.infra import faults

        async def body(client, container):
            await seed(client, ["jax compiles python functions to xla"])
            # ticks 1+2 pass (pipelined dispatch: tick 1's tokens DELIVER
            # at tick 2), hit 3 kills the victim mid-stream, hit 4 kills
            # the RESUMED attempt on the survivor — the budget (1,
            # following the failover budget) is spent, so the client gets
            # the pre-resume contract: a typed mid-stream error event, then
            # [DONE]; no new event kinds, no prose after real tokens
            faults.arm("paged.step", faults.FaultRule(
                error=RuntimeError("sse drill: double death"),
                times=2, skip=2))
            try:
                resp = await client.post("/chat", json={
                    "question": self.QUESTION, "stream": True,
                    "temperature": 0.0})
                assert resp.status == 200  # mid-stream: the 200 is committed
                raw = (await resp.read()).decode()
            finally:
                faults.reset()
            events = self._sse_events(raw)
            kinds = [k for k, _ in events]
            assert kinds.count("error") == 1, events
            error = next(v for k, v in events if k == "error")
            assert error["code"], error
            assert "retryable" in error, error
            # tokens were delivered before the death; the error event ends
            # the stream (with [DONE]) instead of appending apology prose
            assert kinds.index("error") > kinds.index("token"), events
            assert kinds[-1] == "done", events
            assert set(kinds) <= {"sources", "token", "error", "done"}
            stats = container.generation_service.stats()
            assert stats["resume_exhausted"] == 1, stats
            prom = await (await client.get("/metrics")).text()
            assert 'sentio_tpu_stream_resumes_total{outcome="exhausted"}' \
                in prom

        settings = self._settings(crash_retry_budget=0)
        run(with_client(settings, body, container=self._container(settings)))

    def test_per_request_resumable_opt_out(self):
        """ISSUE 15 satellite: the env-only PR 14 opt-out becomes
        per-request — body ``resumable: false`` (and the ``X-Resumable``
        header) ride HTTP → handler → generator → ReplicaSet, so a
        mid-stream death under an opted-out stream keeps the typed
        mid-stream error event even though the resume budget was
        available; an opted-IN sibling request on the same set still
        resumes."""
        from sentio_tpu.infra import faults

        async def body(client, container):
            await seed(client, ["jax compiles python functions to xla"])

            async def faulted_stream(payload, headers=None):
                faults.arm("paged.step", faults.FaultRule(
                    error=RuntimeError("sse drill: opt-out death"),
                    times=1, skip=2))
                try:
                    resp = await client.post("/chat", json=payload,
                                             headers=headers or {})
                    assert resp.status == 200
                    return self._sse_events((await resp.read()).decode())
                finally:
                    faults.reset()

            # body-field opt-out: delivered tokens + typed error event
            events = await faulted_stream({
                "question": self.QUESTION, "stream": True,
                "temperature": 0.0, "resumable": False})
            kinds = [k for k, _ in events]
            assert kinds.count("error") == 1, events
            assert kinds.index("error") > kinds.index("token"), events
            assert kinds[-1] == "done", events
            # header opt-out: same typed wire contract
            events = await faulted_stream(
                {"question": self.QUESTION, "stream": True,
                 "temperature": 0.0},
                headers={"X-Resumable": "0"})
            assert [k for k, _ in events].count("error") == 1, events
            stats = container.generation_service.stats()
            # the opt-out is per-request, not a latched mode: nothing was
            # resumed (test_midstream_kill_is_invisible_on_the_wire pins
            # that a default request on this same config DOES resume)
            assert stats["stream_resumes"] == 0, stats
            prom = await (await client.get("/metrics")).text()
            assert 'sentio_tpu_stream_resumes_total{outcome="opt_out"}' \
                in prom

        settings = self._settings()
        run(with_client(settings, body, container=self._container(settings)))

    def test_resumable_field_validation(self):
        async def body(client, container):
            resp = await client.post("/chat", json={
                "question": "any", "stream": True, "resumable": "nope"})
            assert resp.status == 422
            data = await resp.json()
            assert any(e["field"] == "resumable" for e in data["details"])

        run(with_client(fast_settings(), body))


class TestOverloadMapping:
    """Typed shed/deadline errors → HTTP 429/503/504 + Retry-After — the
    overload story's wire contract (ServiceOverloaded must NEVER be eaten
    by the degradation ladder into a 200 apology)."""

    def test_shed_maps_to_429_with_retry_after(self):
        from sentio_tpu.infra.exceptions import ServiceOverloaded

        class SheddingGraph:
            def invoke(self, *a, **k):
                raise ServiceOverloaded(
                    "decode queue full", status=429, retry_after_s=7.0)

        async def body(client, container):
            container.override("graph", SheddingGraph())
            resp = await client.post("/chat", json={"question": "any"})
            assert resp.status == 429
            assert resp.headers.get("Retry-After") == "7"
            data = await resp.json()
            assert data["error"]["code"] == "OVERLOADED"
            assert data["error"]["retryable"] is True

        run(with_client(fast_settings(), body))

    def test_draining_maps_to_503(self):
        from sentio_tpu.infra.exceptions import ServiceOverloaded

        class DrainingGraph:
            def invoke(self, *a, **k):
                raise ServiceOverloaded("service is draining", status=503,
                                        retry_after_s=5.0)

        async def body(client, container):
            container.override("graph", DrainingGraph())
            resp = await client.post("/chat", json={"question": "any"})
            assert resp.status == 503
            assert resp.headers.get("Retry-After") == "5"

        run(with_client(fast_settings(), body))

    def test_deadline_exceeded_maps_to_504(self):
        from sentio_tpu.infra.exceptions import DeadlineExceededError

        class ExpiredGraph:
            def invoke(self, *a, **k):
                raise DeadlineExceededError("deadline expired mid-decode")

        async def body(client, container):
            container.override("graph", ExpiredGraph())
            resp = await client.post("/chat", json={"question": "any"})
            assert resp.status == 504
            data = await resp.json()
            assert data["error"]["code"] == "DEADLINE_EXCEEDED"

        run(with_client(fast_settings(), body))

    def test_replica_unavailable_maps_to_503_with_retry_after(self):
        """A broken/closed decode replica surfaces as a typed 503 +
        Retry-After (ReplicaUnavailable) instead of the old untyped
        RuntimeError → opaque 500 — the supervisor rebuilds replicas in
        place, so 'come back shortly' is the honest wire answer."""
        from sentio_tpu.infra.exceptions import ReplicaUnavailable

        class BrokenReplicaGraph:
            def invoke(self, *a, **k):
                raise ReplicaUnavailable(
                    "paged decode engine is down (reset failed; awaiting "
                    "supervised rebuild)", retry_after_s=4.0)

        async def body(client, container):
            container.override("graph", BrokenReplicaGraph())
            resp = await client.post("/chat", json={"question": "any"})
            assert resp.status == 503
            assert resp.headers.get("Retry-After") == "4"
            data = await resp.json()
            assert data["error"]["code"] == "SERVICE_UNAVAILABLE"
            assert data["error"]["retryable"] is True
            # NOT a degraded 200 apology: the ladder is bypassed
            assert "answer" not in data

        run(with_client(fast_settings(), body))

    def test_stream_precheck_sheds_replica_unavailable_before_sse(self):
        """The SSE pre-check path: every replica down → typed 503 before
        the 200 status line commits (previously the untyped RuntimeError
        was swallowed and the stream limped into the degraded ladder)."""
        from sentio_tpu.infra.exceptions import ReplicaUnavailable

        class DownSet:
            supports_tenants = True

            def check_admission(self, deadline_ts=None, tenant=None,
                                priority=None, prompt=None):
                raise ReplicaUnavailable(
                    "no serving replica available", retry_after_s=2.0)

        async def body(client, container):
            container.override("generation_service", DownSet())
            resp = await client.post(
                "/chat", json={"question": "stream me", "stream": True})
            assert resp.status == 503
            assert resp.headers.get("Retry-After") == "2"

        run(with_client(fast_settings(), body))

    def test_ladder_still_catches_plain_failures(self):
        """Regression guard: ONLY typed shed errors skip the ladder — a
        plain pipeline crash still degrades to 200."""

        class Boom:
            def invoke(self, *a, **k):
                raise RuntimeError("device on fire")

        async def body(client, container):
            container.override("graph", Boom())
            resp = await client.post("/chat", json={"question": "any"})
            assert resp.status == 200
            assert (await resp.json())["metadata"]["degraded"] is True

        run(with_client(fast_settings(), body))

    def test_stream_precheck_sheds_before_sse(self):
        """stream=True is shed with a REAL 429 before the SSE 200 status
        line commits (after prepare the only option is degrading)."""
        from sentio_tpu.infra.exceptions import ServiceOverloaded

        class FakeService:
            def check_admission(self, deadline_ts=None):
                raise ServiceOverloaded("decode queue full", status=429,
                                        retry_after_s=3.0)

        async def body(client, container):
            container.override("generation_service", FakeService())
            resp = await client.post(
                "/chat", json={"question": "stream me", "stream": True})
            assert resp.status == 429
            assert resp.headers.get("Retry-After") == "3"

        run(with_client(fast_settings(), body))

    def test_deadline_ms_validation(self):
        async def body(client, container):
            for bad in (0, -5, "fast", True, 3_600_001):
                resp = await client.post(
                    "/chat", json={"question": "ok", "deadline_ms": bad})
                assert resp.status == 422, bad
                data = await resp.json()
                assert any(e["field"] == "deadline_ms" for e in data["details"])

        run(with_client(fast_settings(), body))

    def test_deadline_header_rides_metadata_to_flight_record(self):
        """X-Deadline-Ms lands in the flight record and in state.metadata
        (the echo provider ignores it, so the request still succeeds)."""
        from sentio_tpu.infra.flight import get_flight_recorder

        async def body(client, container):
            await seed(client, ["deadline plumbing document"])
            resp = await client.post(
                "/chat",
                json={"question": "deadline plumbing?", "thread_id": "dl-test"},
                headers={"X-Deadline-Ms": "30000"},
            )
            assert resp.status == 200
            record = get_flight_recorder().get("dl-test")
            assert record is not None
            assert 0 < record["deadline_ms"] <= 30000

        run(with_client(fast_settings(), body))


class TestUpload:
    """Multipart binary-document ingest (/upload) — the browser file path
    the reference serves via Streamlit (streamlit_app.py:27-318 there)."""

    @staticmethod
    def make_docx(tmp_path, text="uploaded docx speaks of pallas kernels"):
        import zipfile

        path = tmp_path / "doc.docx"
        xml = (
            '<?xml version="1.0"?><w:document><w:body>'
            f"<w:p><w:r><w:t>{text}</w:t></w:r></w:p>"
            "</w:body></w:document>"
        )
        with zipfile.ZipFile(path, "w") as zf:
            zf.writestr("word/document.xml", xml)
        return path

    def test_docx_roundtrip(self, tmp_path):
        import aiohttp

        path = self.make_docx(tmp_path)

        async def body(client, container):
            form = aiohttp.FormData()
            form.add_field("file", path.read_bytes(), filename="doc.docx",
                           content_type="application/octet-stream")
            resp = await client.post("/upload", data=form)
            assert resp.status == 200, await resp.text()
            data = await resp.json()
            [entry] = data["files"]
            assert entry["filename"] == "doc.docx"
            assert entry["chunks_embedded"] >= 1 and "error" not in entry
            # the uploaded content is immediately retrievable
            resp = await client.post("/chat", json={"question": "what speaks of pallas?"})
            chat = await resp.json()
            assert any("doc.docx" in str(s.get("metadata", {}).get("source", ""))
                       for s in chat["sources"])

        run(with_client(fast_settings(), body))

    def test_text_file_via_upload(self, tmp_path):
        import aiohttp

        async def body(client, container):
            form = aiohttp.FormData()
            form.add_field("file", b"plain text about ring attention",
                           filename="notes.txt")
            resp = await client.post("/upload", data=form)
            assert resp.status == 200
            [entry] = (await resp.json())["files"]
            assert entry["chunks_embedded"] >= 1

        run(with_client(fast_settings(), body))

    def test_unsupported_suffix_and_bad_docx(self, tmp_path):
        import aiohttp

        async def body(client, container):
            form = aiohttp.FormData()
            form.add_field("file", b"\x7fELF", filename="a.exe")
            form.add_field("file", b"not a zip", filename="broken.docx")
            resp = await client.post("/upload", data=form)
            assert resp.status == 422  # every file failed
            data = await resp.json()
            errors = {f["filename"]: f.get("error", "") for f in data["files"]}
            assert "unsupported" in errors["a.exe"]
            assert errors["broken.docx"]

        run(with_client(fast_settings(), body))

    def test_non_multipart_rejected(self):
        async def body(client, container):
            resp = await client.post("/upload", json={"file": "nope"})
            assert resp.status == 422

        run(with_client(fast_settings(), body))

    def test_request_cap_returns_413(self):
        import aiohttp

        from sentio_tpu.config import ServeConfig

        async def body(client, container):
            form = aiohttp.FormData()
            form.add_field("file", b"x" * 4096, filename="big.txt")
            resp = await client.post("/upload", data=form)
            assert resp.status == 413
            data = await resp.json()
            assert "cap" in data["files"][-1]["error"]

        run(with_client(fast_settings(serve=ServeConfig(max_upload_mb=0)), body))

    def test_skipped_part_bytes_count_toward_cap(self):
        import aiohttp

        from sentio_tpu.config import ServeConfig

        async def body(client, container):
            form = aiohttp.FormData()
            # unsupported type would be skipped — its bytes must still trip
            # the request cap rather than streaming through uncounted
            form.add_field("file", b"y" * 4096, filename="huge.exe")
            resp = await client.post("/upload", data=form)
            assert resp.status == 413

        run(with_client(fast_settings(serve=ServeConfig(max_upload_mb=0)), body))


class TestRequestThreads:
    """The streams a server carries follow from what its engine admits
    (PR 39): ``/chat``'s pipelines, streamed and unstreamed, run on threads
    the server owns, as many as the generation service's ``max_queue`` —
    not on asyncio's default executor, whose ``min(32, cores + 4)`` threads
    stood in front of the engine's own admission."""

    _probe: contextvars.ContextVar = contextvars.ContextVar("request_threads_probe", default=None)

    class _Admitting:
        """What the server reads of a generation service, and no engine."""

        def __init__(self, max_queue):
            self.max_queue = max_queue

        def stats(self):
            return {"max_queue": self.max_queue}

        def check_admission(self, deadline_ts=None):
            return None

    class _HeldGenerator:
        """Every caller arrives, then waits for the test's release."""

        provider = None

        def __init__(self, probe):
            self.release = threading.Event()
            self.arrived = []
            self._probe = probe

        def _arrive(self):
            self.arrived.append((threading.current_thread().name, self._probe.get()))
            assert self.release.wait(60.0), "the test never released its callers"

        def generate(self, query, docs, **kwargs):
            self._arrive()
            return "held answer"

        def stream(self, query, docs, **kwargs):
            self._arrive()
            yield "held answer"

    @staticmethod
    def _request_threads_alive():
        from sentio_tpu.serve.dependencies import RequestThreads

        return [t.name for t in threading.enumerate()
                if t.name.startswith(RequestThreads.THREAD_PREFIX)]

    @pytest.mark.parametrize("stream", [True, False], ids=["streamed", "unstreamed"])
    def test_every_caller_reaches_the_generator(self, stream):
        """Three callers more than asyncio's default executor has threads:
        ALL of them stand in the generator before any is released, none
        waited for a thread, and each carried its handler's context."""
        from aiohttp import web

        from sentio_tpu.infra.flight import get_flight_recorder

        callers = min(32, (os.cpu_count() or 1) + 4) + 3
        held = self._HeldGenerator(self._probe)
        container = DependencyContainer(
            settings=fast_settings(), generator=held,
            generation_service=self._Admitting(64))

        @web.middleware
        async def mark(request, handler):
            self._probe.set(request.path)
            return await handler(request)

        async def arrivals(n):
            for _ in range(1200):
                if len(held.arrived) >= n:
                    break
                await asyncio.sleep(0.01)
            return len(held.arrived)

        async def body(client, container):
            await seed(client, ["request threads carry every caller"])
            # the first request of a process imports what its handler needs
            held.release.set()
            await (await client.post("/chat", json={
                "question": "first of the process", "stream": stream})).read()
            held.release.clear()
            held.arrived.clear()
            posts = []
            try:
                # one after the other, each once the one before stands in
                # the generator: a wait for a thread is then all that a
                # `pool_wait` can hold, with no other pipeline on the cores
                for i in range(callers):
                    posts.append(asyncio.ensure_future(client.post("/chat", json={
                        "question": f"caller {i} asks", "stream": stream,
                        "thread_id": f"held-{stream}-{i}"})))
                    assert await arrivals(i + 1) == i + 1, (
                        f"caller {i + 1} of {callers} never reached the "
                        "generator: it waits for a thread")
            finally:
                held.release.set()
            for resp in await asyncio.gather(*posts):
                assert resp.status == 200
                assert "held answer" in (await resp.read()).decode()
            assert {name.rsplit("_", 1)[0] for name, _ in held.arrived} <= {
                "sentio-request", "asyncio"}  # the graph's own hop under /chat
            assert {mark for _, mark in held.arrived} == {"/chat"}
            waits = []
            for i in range(callers):
                record = get_flight_recorder().get(f"held-{stream}-{i}")
                waits += [(sp["t1_s"] - sp["t0_s"]) * 1e3 for sp in record["spans"]
                          if sp["name"] == "pool_wait"]
            assert len(waits) == callers
            assert max(waits) < 50.0, sorted(waits)

        run(with_client(None, body, container=container, middlewares=[mark]))
        assert not self._request_threads_alive()

    @staticmethod
    def _engine_settings(**serve_over):
        return fast_settings(
            generator=GeneratorConfig(
                provider="tpu", model_preset="tiny", use_verifier=False,
                max_new_tokens=8, mode="fast", kv_page_size=16,
                kv_max_pages_per_seq=8, max_batch_size=2,
            ),
            serve=ServeConfig(**serve_over),
        )

    def test_span_context_reaches_the_stages_on_a_request_thread(self):
        """The stages find their request through the context the hop
        carries: the flight record of a streamed and of an unstreamed
        request holds all nine TTFT stages, `embed` and `rerank` written by
        the threads below, tiling the server-side time to first token."""
        from sentio_tpu.infra.phases import TTFT_STAGES

        async def body(client, container):
            await seed(client, ["the span context travels with the request"])
            for stream in (True, False):
                rid = f"ctx-{stream}"
                resp = await client.post("/chat", json={
                    "question": "what travels with the request?",
                    "stream": stream, "thread_id": rid})
                assert resp.status == 200
                await resp.read()
                record = await (await client.get(f"/debug/flight/{rid}")).json()
                assert tuple(record["stages_ms"]) == TTFT_STAGES
                assert sum(record["stages_ms"].values()) == pytest.approx(
                    record["ttft_server_ms"], abs=1e-6)
                for stage in ("embed", "rerank", "prefill"):
                    assert record["stages_ms"][stage] > 0.0, record["stages_ms"]
                parents = {sp["name"]: sp["parent"] for sp in record["spans"]}
                assert parents["pool_wait"] == "request"
                assert parents["embed"] == "graph.retrieve"
                assert parents["rerank"] == "graph.rerank"

        run(with_client(self._engine_settings(), body))

    @pytest.mark.parametrize("stream", [True, False], ids=["streamed", "unstreamed"])
    def test_the_request_past_max_queue_is_shed_by_the_engine(self, stream):
        """ADMISSION_MAX_QUEUE=2: two requests fill the engine's queue (its
        pump wedged), and the third is told `queue_full` by the engine's
        own admission — a typed 429 at once, not a wait for a thread that
        the two hold."""
        from sentio_tpu.infra import faults
        from sentio_tpu.infra.metrics import get_metrics

        async def body(client, container):
            await seed(client, ["a full queue sheds the next caller"])
            info = await (await client.get("/info")).json()
            assert (info["request_threads"], info["request_threads_from"]) == (2, "max_queue")
            replica = container.generation_service._services[0]
            wedge = threading.Event()
            faults.arm("paged.step", faults.FaultRule(stall_event=wedge))
            shed_before = get_metrics().export_json()["counters"]
            try:
                # a tenant each: one tenant's fair share of a queue of two
                # is one, and it is the ENGINE's bound that is wanted here
                held = [asyncio.ensure_future(client.post(
                    "/chat", headers={"X-Tenant": f"tenant-{i}"}, json={
                        "question": f"held caller {i}", "stream": stream}))
                    for i in range(2)]
                for _ in range(1200):
                    if replica.backlog() >= 2:
                        break
                    await asyncio.sleep(0.05)
                assert replica.backlog() == 2
                resp = await asyncio.wait_for(client.post(
                    "/chat", headers={"X-Tenant": "tenant-2"}, json={
                        "question": "the caller past the queue", "stream": stream}),
                    timeout=20.0)
                assert resp.status == 429, await resp.text()
                data = await resp.json()
                assert data["error"]["code"] == "OVERLOADED"
                assert "queue full (2/2" in data["error"]["message"]
                assert int(resp.headers["Retry-After"]) >= 1
                assert replica.backlog() == 2
            finally:
                wedge.set()
                faults.reset()
            for resp in await asyncio.gather(*held):
                assert resp.status == 200
                await resp.read()
            shed_after = get_metrics().export_json()["counters"]
            grown = {k: v - shed_before.get(k, 0) for k, v in shed_after.items()
                     if "shed" in k and v != shed_before.get(k, 0)}
            assert len(grown) == 1 and "queue_full" in next(iter(grown)), grown

        run(with_client(self._engine_settings(admission_max_queue=2), body))

    @pytest.mark.parametrize("service, want", [
        (_Admitting(40), (40, "max_queue")),
        (None, (min(32, (os.cpu_count() or 1) + 4), "default_executor")),
    ], ids=["max_queue", "no_engine"])
    def test_info_states_the_width_and_cleanup_joins_the_threads(self, service, want):
        container = DependencyContainer(
            settings=fast_settings(), generation_service=service)

        async def body(client, container):
            await seed(client, ["the width is read off the service"])
            info = await (await client.get("/info")).json()
            assert (info["request_threads"], info["request_threads_from"]) == want
            for stream in (True, False):
                resp = await client.post("/chat", json={
                    "question": "who carries me?", "stream": stream})
                assert resp.status == 200
                await resp.read()
            assert self._request_threads_alive()

        run(with_client(None, body, container=container))
        assert not self._request_threads_alive()
