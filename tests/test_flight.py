"""Flight recorder: ring-buffer bounds, thread-safety, TTFT/TPOT capture on
the paged serving path (/chat and its SSE stream), and the /debug/flight
endpoint's 404 + auth behavior."""

from __future__ import annotations

import asyncio
import json
import threading
import time

import pytest

from sentio_tpu.infra.flight import (
    FlightRecorder,
    get_flight_recorder,
    set_flight_recorder,
)


@pytest.fixture()
def recorder():
    rec = FlightRecorder(max_ticks=64, max_requests=8)
    set_flight_recorder(rec)
    yield rec
    set_flight_recorder(None)


class TestRingBuffer:
    def test_tick_ring_is_bounded(self, recorder):
        for i in range(500):
            recorder.record_tick(dur_ms=1.0, active_slots=i % 4)
        timeline = recorder.timeline()
        assert len(timeline) == 64
        # oldest events fell off; sequence numbers stay monotonic
        assert timeline[0]["tick"] == 500 - 64 + 1
        assert [e["tick"] for e in timeline] == sorted(e["tick"] for e in timeline)
        snap = recorder.snapshot()
        assert snap["ticks_recorded"] == 500
        assert snap["ticks_retained"] == 64

    def test_request_table_is_bounded_with_lru_eviction(self, recorder):
        for i in range(20):
            recorder.start_request(f"req-{i}")
        assert recorder.get("req-0") is None  # evicted
        assert recorder.get("req-19") is not None
        assert recorder.dropped_requests == 12
        assert recorder.snapshot()["requests_retained"] == 8

    def test_get_slices_the_request_tick_window(self, recorder):
        recorder.record_tick(active_slots=9)  # before the request
        recorder.start_request("r")
        recorder.note_engine_submit("r")
        recorder.record_tick(active_slots=1, queue_depth=2)
        recorder.record_tick(active_slots=2, queue_depth=0)
        recorder.finish_engine("r", ttft_ms=5.0, tokens=3)
        recorder.record_tick(active_slots=7)  # after the request
        record = recorder.get("r")
        assert [e["active_slots"] for e in record["ticks"]] == [1, 2]
        assert record["engine"]["ttft_ms"] == 5.0

    def test_unknown_request_returns_none(self, recorder):
        assert recorder.get("nope") is None

    def test_thread_safety_under_concurrent_writers(self, recorder):
        """Concurrent pump-style tick appends + request lifecycles must not
        corrupt bounds or raise. 8 writers x 200 ops is far past what one
        engine pump produces between scrapes."""
        errors: list[BaseException] = []

        def pump(tid: int):
            try:
                for i in range(200):
                    recorder.record_tick(dur_ms=0.1, active_slots=tid,
                                         queue_depth=i % 3)
                    rid = f"t{tid}-r{i % 5}"
                    recorder.start_request(rid)
                    recorder.note_engine_submit(rid)
                    recorder.add_node_timings(rid, {"generate": 1.0})
                    recorder.finish_engine(rid, ttft_ms=1.0, tokens=i)
                    recorder.finish_request(rid, status="done")
                    recorder.get(rid)
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=pump, args=(t,)) for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(recorder.timeline()) == 64
        assert recorder.snapshot()["ticks_recorded"] == 8 * 200
        assert recorder.snapshot()["requests_retained"] <= 8

    def test_start_request_resets_a_finished_record(self, recorder):
        """Multi-turn conversations pin thread_id (the trace id): turn 2
        must start a fresh record, not sum its node timings onto turn 1's."""
        recorder.start_request("thread-1", endpoint="/chat")
        recorder.add_node_timings("thread-1", {"generate": 10.0})
        recorder.finish_request("thread-1", status="done")
        recorder.start_request("thread-1", endpoint="/chat")
        record = recorder.get("thread-1")
        assert "node_timings_ms" not in record  # turn 1's timings gone
        assert record["status"] == "active"
        recorder.add_node_timings("thread-1", {"generate": 7.0})
        assert recorder.get("thread-1")["node_timings_ms"] == {"generate": 7.0}

    def test_node_timings_merge_across_invocations(self, recorder):
        recorder.add_node_timings("r", {"generate": 10.0}, graph_path=["generate"])
        recorder.add_node_timings("r", {"generate": 5.0, "verify": 2.0})
        record = recorder.get("r")
        assert record["node_timings_ms"] == {"generate": 15.0, "verify": 2.0}


class TestMetricsSnapshotHonesty:
    """Satellite: the JSON histogram export must not present windowed
    quantiles under a full-run sample count (the old snapshot silently
    truncated to 1000 observations and reported a biased p50 as if it
    covered everything)."""

    def test_true_count_dropped_and_p95(self):
        from sentio_tpu.infra.metrics import InMemoryMetrics

        mem = InMemoryMetrics()
        for i in range(1500):
            mem.observe("lat", (), float(i))
        h = mem.snapshot()["histograms"]["lat()"]
        assert h["count"] == 1500
        assert h["window"] == 1000
        assert h["dropped"] == 500
        # quantiles come from the retained window (values 500..1499)
        assert h["p50"] == 1000.0
        assert h["p95"] == 1450.0
        # mean is LIFETIME (sum over all 1500), not window-biased
        assert h["mean"] == pytest.approx(sum(range(1500)) / 1500)

    def test_small_histogram_has_zero_dropped(self):
        from sentio_tpu.infra.metrics import InMemoryMetrics

        mem = InMemoryMetrics()
        for i in range(10):
            mem.observe("x", (), float(i))
        h = mem.snapshot()["histograms"]["x()"]
        assert h["count"] == 10 and h["dropped"] == 0 and h["p95"] == 9.0


class TestTraceContextCompat:
    def test_legacy_provider_without_request_id_kwarg_stays_working(self):
        """Every real request is traced now — a provider with the pre-trace
        chat/stream signature must run untraced, not TypeError into the
        degradation ladder on 100% of traffic."""
        from sentio_tpu.ops.generator import LLMGenerator

        class Legacy:
            name = "legacy"

            def chat(self, prompt, max_new_tokens, temperature):
                return "ok"

            def stream(self, prompt, max_new_tokens, temperature):
                yield "ok"

        gen = LLMGenerator(provider=Legacy())
        assert gen.generate("q", [], request_id="rid-1") == "ok"
        assert list(gen.stream("q", [], request_id="rid-1")) == ["ok"]

    def test_single_tick_completion_records_ttft_but_no_tpot(self, recorder):
        """A generation that finishes inside its first pump tick has no
        post-first-token interval: recording tpot=0.0 would drag the
        histogram's p50 toward a throughput the engine doesn't have."""
        from sentio_tpu.infra.metrics import MetricsCollector
        from sentio_tpu.runtime.paged import PagedResult
        from sentio_tpu.runtime.service import PagedGenerationService, _Ticket

        metrics = MetricsCollector()
        ticket = _Ticket("p", 8, 0.0, request_id="one-tick", t_submit=0.0)
        result = PagedResult(request_id=0, text="abc", tokens=[1, 2, 3],
                             prompt_tokens=5, finish_reason="stop")
        PagedGenerationService._note_finished(
            ticket, result, 0.5, metrics, recorder)
        histos = metrics.memory.snapshot()["histograms"]
        assert histos["ttft('paged',)"]["count"] == 1
        assert "tpot('paged',)" not in histos
        assert recorder.get("one-tick")["engine"]["tpot_ms"] is None


# --------------------------------------------------------------- paged path


@pytest.mark.slow
class TestServiceTelemetry:
    """TTFT/TPOT + tick events recorded by the decode pump for traced
    requests, concurrent engine ticks included."""

    def _service(self):
        from sentio_tpu.models.llama import LlamaConfig
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine
        from sentio_tpu.runtime.service import PagedGenerationService

        engine = ContinuousBatchingEngine(
            model_config=LlamaConfig.tiny(), max_slots=4, page_size=16,
            max_pages_per_seq=4, steps_per_tick=4,
        )
        return PagedGenerationService(engine)

    def test_generate_records_ttft_tpot_and_tick_window(self, recorder):
        from sentio_tpu.infra.metrics import MetricsCollector, set_metrics

        metrics = MetricsCollector()
        set_metrics(metrics)
        try:
            service = self._service()
            result = service.generate(
                "hello flight", max_new_tokens=8, request_id="gen-1"
            )
            service.close()
            record = recorder.get("gen-1")
            assert record is not None
            engine = record["engine"]
            assert engine["ttft_ms"] >= 0.0
            assert engine["tokens"] == len(result.tokens)
            assert engine["finish_reason"] == result.finish_reason
            assert record["ticks"], "request window must hold >=1 tick event"
            tick = record["ticks"][0]
            for field in ("active_slots", "queue_depth", "free_pages",
                          "prefill_tokens", "decode_tokens", "dur_ms"):
                assert field in tick, tick
            histos = metrics.memory.snapshot()["histograms"]
            assert histos["ttft('paged',)"]["count"] >= 1
            assert "tick_duration()" in histos
        finally:
            set_metrics(None)

    def test_stream_and_concurrent_tickets_all_traced(self, recorder):
        from sentio_tpu.infra.metrics import MetricsCollector, set_metrics

        metrics = MetricsCollector()
        set_metrics(metrics)
        try:
            service = self._service()
            out: dict[str, list[str]] = {}

            def consume(rid: str):
                out[rid] = list(service.generate_stream(
                    f"prompt for {rid}", max_new_tokens=12, request_id=rid
                ))

            threads = [
                threading.Thread(target=consume, args=(f"st-{i}",))
                for i in range(3)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            service.close()
            for i in range(3):
                record = recorder.get(f"st-{i}")
                assert record is not None and "engine" in record, record
                assert record["engine"]["tokens"] >= 0
            # TPOT requires >1 token over >1 tick; TTFT must always land,
            # labeled with the streaming path (blocking calls get 'paged')
            assert metrics.memory.snapshot()["histograms"][
                "ttft('stream',)"]["count"] >= 3
        finally:
            set_metrics(None)


# --------------------------------------------------------------- HTTP layer


@pytest.mark.slow
class TestFlightEndpoint:
    def _settings(self, **over):
        from sentio_tpu.config import (
            EmbedderConfig,
            GeneratorConfig,
            RerankConfig,
            Settings,
        )

        s = Settings(
            embedder=EmbedderConfig(provider="hash", dim=32),
            generator=GeneratorConfig(
                provider="tpu", model_preset="tiny", use_verifier=False,
                max_new_tokens=16, mode="fast",
                kv_page_size=16, kv_max_pages_per_seq=8, max_batch_size=4,
            ),
            rerank=RerankConfig(enabled=False),
        )
        for key, value in over.items():
            setattr(s, key, value)
        return s

    async def _with_client(self, settings, fn):
        from aiohttp.test_utils import TestClient, TestServer

        from sentio_tpu.serve.app import create_app
        from sentio_tpu.serve.dependencies import DependencyContainer

        container = DependencyContainer(settings=settings)
        app = create_app(container=container)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await fn(client, container)
        finally:
            await client.close()

    def test_chat_flight_record_roundtrip(self, recorder):
        """Acceptance: a completed /chat request's record is retrievable at
        /debug/flight/{request_id} with graph node timings AND >=1 engine
        tick event carrying occupancy/queue-depth fields."""

        async def body(client, container):
            resp = await client.post("/embed", json={
                "content": "tpus multiply matrices in a systolic array"
            })
            assert resp.status == 200
            resp = await client.post("/chat", json={
                "question": "what multiplies matrices?",
                "thread_id": "flight-chat-1",
            })
            assert resp.status == 200
            data = await resp.json()
            assert data["metadata"]["query_id"] == "flight-chat-1"

            flight = await client.get("/debug/flight/flight-chat-1")
            assert flight.status == 200
            record = await flight.json()
            assert record["status"] == "done"
            assert record["node_timings_ms"].get("generate") is not None
            assert record["engine"]["tokens"] >= 0
            assert record["engine"]["ttft_ms"] >= 0.0
            assert record["ticks"], "no engine tick events in the record"
            assert "active_slots" in record["ticks"][0]
            assert "queue_depth" in record["ticks"][0]

            missing = await client.get("/debug/flight/who-dis")
            assert missing.status == 404

        asyncio.run(self._with_client(self._settings(), body))

    def test_chat_flight_chrome_format(self, recorder):
        """?format=chrome returns the record's window as a Perfetto-openable
        Chrome trace: tick slices with nested phase slices, the request
        span, on one timeline."""

        async def body(client, container):
            await client.post("/embed", json={
                "content": "tpus multiply matrices in a systolic array"
            })
            resp = await client.post("/chat", json={
                "question": "what multiplies matrices?",
                "thread_id": "flight-chrome-1",
            })
            assert resp.status == 200

            chrome = await client.get(
                "/debug/flight/flight-chrome-1?format=chrome")
            assert chrome.status == 200
            trace = await chrome.json()
            events = trace["traceEvents"]
            names = {e["name"] for e in events}
            assert "request flight-chrome-1" in names
            assert any(n.startswith("tick ") for n in names)
            from sentio_tpu.infra.phases import TICK_PHASES

            assert names & set(TICK_PHASES), "no phase slices on the trace"

            missing = await client.get(
                "/debug/flight/who-dis?format=chrome")
            assert missing.status == 404

        asyncio.run(self._with_client(self._settings(), body))

    def test_debug_profile_window(self, recorder, tmp_path):
        """/debug/profile arms jax.profiler for the window and reports the
        trace directory; malformed/oversized windows 422."""

        async def body(client, container):
            resp = await client.get(
                f"/debug/profile?seconds=0.1&dir={tmp_path}")
            assert resp.status == 200
            out = await resp.json()
            assert out["started"] is True
            assert out["log_dir"] == str(tmp_path)

            bad = await client.get("/debug/profile?seconds=oops")
            assert bad.status == 422
            too_long = await client.get("/debug/profile?seconds=9999")
            assert too_long.status == 422

        asyncio.run(self._with_client(self._settings(), body))

    def test_debug_profile_window_is_bounded_by_the_deployment(self, recorder, tmp_path):
        """``PROFILE_MAX_SECONDS``: a longer window asked for is traced that long and the answer says so."""
        from sentio_tpu.config import ObservabilityConfig

        async def body(client, container):
            t0 = time.perf_counter()
            out = await (await client.get(f"/debug/profile?seconds=30&dir={tmp_path}")).json()
            assert out["started"] is True and (out["seconds"], out["asked_seconds"]) == (0.1, 30.0)
            assert time.perf_counter() - t0 < 20.0

        settings = self._settings()
        settings.observability = ObservabilityConfig(profile_max_seconds=0.1)
        asyncio.run(self._with_client(settings, body))

    def test_sse_stream_records_ttft(self, recorder):
        """The SSE path must trace too: X-Request-Id names the record, and
        the paged pump stamps TTFT/TPOT for the streamed sequence."""

        async def body(client, container):
            await client.post("/embed", json={"content": "streaming evidence doc"})
            resp = await client.post("/chat", json={
                "question": "what streams?", "stream": True,
                "thread_id": "flight-sse-1",
            })
            assert resp.status == 200
            assert resp.headers["X-Request-Id"] == "flight-sse-1"
            await resp.read()  # drain the stream to completion

            flight = await client.get("/debug/flight/flight-sse-1")
            assert flight.status == 200
            record = await flight.json()
            assert record["status"] == "done"
            assert record["node_timings_ms"].get("generate") is not None
            assert record["engine"]["ttft_ms"] >= 0.0

        asyncio.run(self._with_client(self._settings(), body))

    def _rag_settings(self):
        """The whole pipeline on device models: encoder, cross-encoder,
        generator and a synchronous audit."""
        from sentio_tpu.config import (
            EmbedderConfig,
            GeneratorConfig,
            RerankConfig,
            Settings,
        )

        return Settings(
            embedder=EmbedderConfig(provider="tpu", model_preset="tiny", coalesce=False),
            generator=GeneratorConfig(
                provider="tpu", model_preset="tiny", use_verifier=True,
                max_new_tokens=12, verifier_max_tokens=6, mode="fast",
                kv_page_size=16, kv_max_pages_per_seq=24,
                max_batch_size=4, decode_steps_per_tick=4, decode_max_tick_steps=4,
            ),
            rerank=RerankConfig(enabled=True, kind="cross_encoder"),
        )

    @staticmethod
    def _check_tree(record, expect_parent):
        spans = record["spans"]
        roots = [sp for sp in spans if sp["parent"] is None]
        assert len(roots) == 1 and roots[0]["name"] == "request" and spans[0] is roots[0]
        names = {sp["name"] for sp in spans}
        assert all(sp["parent"] in names for sp in spans[1:]), spans
        for name, parent in expect_parent.items():
            got = {sp["parent"] for sp in spans if sp["name"] == name}
            assert got == (parent if isinstance(parent, set) else {parent}), (name, got)
        # the audit's admission hangs under `verify`, whole
        audit = [sp["name"] for sp in spans if sp["parent"] == "verify"]
        assert audit == ["inbox_wait", "slot_wait", "prefill", "decode"]
        # the stages tile receipt -> first token exactly
        assert sum(record["stages_ms"].values()) == pytest.approx(
            record["ttft_server_ms"], abs=1e-6)
        for stage in ("embed", "rerank", "prefill"):
            assert record["stages_ms"][stage] > 0.0, record["stages_ms"]
        prefill = next(sp for sp in spans
                       if sp["name"] == "prefill" and sp["parent"] != "verify")
        assert prefill["fields"]["segments"] == 1
        assert prefill["fields"]["prompt_tokens"] > 0
        assert prefill["fields"]["ticks"][0] <= prefill["fields"]["ticks"][1]

    def test_span_tree_of_a_streamed_and_a_graph_request(self, recorder):
        """/debug/flight/{id} returns the span tree: one root, every parent
        resolves, the request stages hang where their work ran and the
        audit's stages under ``verify`` — on the SSE path and through the
        graph executor alike. /debug/flight without an id sums the stages
        up; ?format=chrome returns the whole ring."""

        async def body(client, container):
            for i in range(3):
                resp = await client.post("/embed", json={
                    "content": f"systolic arrays multiply matrices, note {i}"})
                assert resp.status == 200
            resp = await client.post("/chat", json={
                "question": "what multiplies matrices?", "stream": True,
                "thread_id": "tree-sse"})
            text = (await resp.read()).decode()
            events = [json.loads(line[6:]) for line in text.splitlines()
                      if line.startswith("data: {")]
            assert "usage" in events[-1], events[-1]  # last data event before [DONE]
            assert text.rstrip().endswith("data: [DONE]")
            record = await (await client.get("/debug/flight/tree-sse")).json()
            assert events[-1]["usage"] == {
                "prompt_tokens": record["engine"]["prompt_tokens"],
                "answer_tokens": record["engine"]["tokens"]}
            self._check_tree(record, {
                "pool_wait": "request", "graph.retrieve": "request",
                "embed": "graph.retrieve", "sparse_fuse": "graph.retrieve",
                "rerank": "graph.rerank", "select": "graph.select",
                "verify": "request"})
            assert record["stream_lag_max_ms"] >= 0.0

            resp = await client.post("/chat", json={
                "question": "what multiplies matrices?", "thread_id": "tree-graph"})
            assert resp.status == 200
            record = await (await client.get("/debug/flight/tree-graph")).json()
            self._check_tree(record, {
                "pool_wait": "request", "graph.retrieve": "request",
                "embed": "graph.retrieve", "rerank": "graph.rerank",
                "select": "graph.select", "graph.generate": "request",
                "decode": {"graph.generate", "verify"}, "verify": "graph.verify"})

            latest = await (await client.get("/debug/flight?last=1")).json()
            assert latest["requests"] == 1  # the graph request finished last
            assert "stream_lag" not in latest["stages_ms"]
            assert (await client.get("/debug/flight?last=x")).status == 422
            summary = await (await client.get("/debug/flight")).json()
            assert summary["requests"] == 2
            assert summary["residual_ms_max"] < 1e-6
            assert summary["stages_ms"]["prefill"]["count"] == 2
            assert summary["stages_ms"]["verify"]["count"] == 2
            assert summary["stages_ms"]["stream_lag"]["count"] == 1
            tiles = sum(v["mean"] for k, v in summary["stages_ms"].items()
                        if k not in ("decode", "verify", "stream_lag"))
            assert tiles == pytest.approx(summary["ttft_server_ms"]["mean"], abs=0.01)
            steps = summary["row_steps"]
            assert steps["useful"] > 0 and steps["empty"] > 0
            ticks = [e for e in recorder.timeline() if "row_steps" in e]
            assert sum(steps.values()) == 4 * sum(e["sub_steps"] for e in ticks)

            ring = await (await client.get("/debug/flight?format=chrome")).json()
            names = {e["name"] for e in ring["traceEvents"]}
            assert {"request tree-sse", "request tree-graph", "embed", "prefill"} <= names
            assert any(n.startswith("tick ") for n in names)

            metrics_text = await (await client.get("/metrics")).text()
            for stage in ("pool_wait", "embed", "sparse_fuse", "rerank", "select",
                          "inbox_wait", "slot_wait", "prefill", "other"):
                assert f'sentio_tpu_request_stage_seconds_count{{stage="{stage}"}} 2.0' \
                    in metrics_text, stage

        from sentio_tpu.infra.metrics import MetricsCollector, set_metrics

        set_metrics(MetricsCollector())  # the counts below are this test's alone
        try:
            asyncio.run(self._with_client(self._rag_settings(), body))
        finally:
            set_metrics(None)

    def test_debug_profile_python_tracer_is_opt_in(self, recorder, tmp_path, monkeypatch):
        import sentio_tpu.infra.tracing as tracing_mod

        calls = []
        monkeypatch.setattr(
            tracing_mod, "profile_window",
            lambda seconds, log_dir, python_tracer=False: calls.append(python_tracer)
            or {"started": True})

        async def body(client, container):
            assert (await client.get(f"/debug/profile?seconds=0.1&dir={tmp_path}")).status == 200
            assert (await client.get(
                f"/debug/profile?seconds=0.1&dir={tmp_path}&python=1")).status == 200

        asyncio.run(self._with_client(self._settings(), body))
        assert calls == [False, True]

    def test_debug_flight_is_auth_gated(self, recorder):
        """With auth enabled, /debug/flight requires credentials (unlike
        /metrics, which stays open for scrapers)."""
        from sentio_tpu.config import AuthConfig

        settings = self._settings(auth=AuthConfig(enabled=True, jwt_secret="s" * 32))

        async def body(client, container):
            resp = await client.get("/debug/flight/anything")
            assert resp.status == 401
            # /metrics stays open
            assert (await client.get("/metrics")).status == 200

            container.auth_manager.create_user(
                "ada", "Correct-Horse-Battery-9", role="admin"
            )
            tok = await client.post("/auth/token", json={
                "username": "ada", "password": "Correct-Horse-Battery-9"
            })
            access = (await tok.json())["access_token"]
            resp = await client.get(
                "/debug/flight/anything",
                headers={"Authorization": f"Bearer {access}"},
            )
            assert resp.status == 404  # authed, but no such record

        asyncio.run(self._with_client(settings, body))
