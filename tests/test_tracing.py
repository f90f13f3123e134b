"""infra/tracing.py — the span layer: a span is a profiler annotation and,
with a request id, a span on that request's flight record, naming the span
that caused it; request stages tile the time to first token; a profile
window without the Python tracer holds the pump's step and phase events.
One module holds every test that arms the profiler: it is process-global."""

import asyncio
import sys
import threading
from pathlib import Path

import pytest

from sentio_tpu.infra import tracing
from sentio_tpu.infra.flight import FlightRecorder, set_flight_recorder
from sentio_tpu.infra.metrics import MetricsCollector, set_metrics
from sentio_tpu.infra.phases import TICK_PHASES, TTFT_STAGES, tile_ttft
from sentio_tpu.infra.tracing import profile_window, span, stamp

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def recorder():
    rec = FlightRecorder()
    set_flight_recorder(rec)
    yield rec
    set_flight_recorder(None)


@pytest.fixture
def metrics():
    collector = MetricsCollector()
    set_metrics(collector)
    yield collector
    set_metrics(None)


def _stage_counts(collector) -> dict:
    histos = collector.export_json()["histograms"]
    return {key.split("'")[1]: val["count"] for key, val in histos.items()
            if key.startswith("request_stage")}


def _tiny_service():
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.service import PagedGenerationService

    return PagedGenerationService(ContinuousBatchingEngine(
        max_slots=2, page_size=16, max_pages_per_seq=4,
        steps_per_tick=4, max_tick_steps=4))


class TestSpan:
    def test_span_lands_on_the_record_with_parent_and_fields(self, recorder):
        with span("graph.rerank", request_id="r1", replica_id=0):
            with span("rerank", pairs=3):  # id and parent come from the context
                pass
        spans = recorder.get("r1")["spans"]
        assert [sp["name"] for sp in spans] == ["request", "rerank", "graph.rerank"]
        rerank = spans[1]
        assert rerank["parent"] == "graph.rerank" and rerank["fields"] == {"pairs": 3}
        assert spans[2]["parent"] == "request"  # no parent given: under the root
        assert spans[2]["t0_s"] <= rerank["t0_s"] <= rerank["t1_s"] <= spans[2]["t1_s"]
        assert tracing.current() == (None, None)  # the context is restored

    def test_no_request_id_writes_no_record(self, recorder):
        with span("embed"):
            assert tracing.current() == (None, "embed")
        assert recorder.records() == []

    def test_another_requests_span_is_not_a_parent(self, recorder):
        with span("graph.retrieve", request_id="a"):
            with span("embed", request_id="b"):
                assert tracing.parent_for("b") == "embed"
                assert tracing.parent_for("a") is None
        assert recorder.get("b")["spans"][1]["parent"] == "request"

    def test_context_crosses_to_thread_but_not_a_bare_thread(self, recorder):
        seen = {}

        async def hop():
            with span("graph.retrieve", request_id="hop"):
                seen["to_thread"] = await asyncio.to_thread(tracing.current)
                thread = threading.Thread(
                    target=lambda: seen.update(bare=tracing.current()), name="bare")
                thread.start()
                thread.join(timeout=10)

        asyncio.run(hop())
        assert seen["to_thread"] == ("hop", "graph.retrieve")
        assert seen["bare"] == (None, None)

    def test_body_exception_leaves_as_itself(self, recorder):
        """The pump's crash containment and the chaos drills key off the
        original exception type: neither a span nor the step annotation may
        wrap, swallow or replace it — and the span is still recorded."""
        with pytest.raises(KeyError, match="tick blew up"):
            with tracing.tick_annotation(7):
                with span("prefill", request_id="boom"):
                    raise KeyError("tick blew up")
        assert [sp["name"] for sp in recorder.get("boom")["spans"]] == ["request", "prefill"]
        assert tracing.current() == (None, None)

    def test_spans_per_record_are_bounded(self, recorder):
        from sentio_tpu.infra.flight import MAX_SPANS_PER_RECORD

        for i in range(MAX_SPANS_PER_RECORD + 5):
            stamp("graph.loop", float(i), float(i) + 0.5, "many")
        record = recorder.get("many")
        assert len(record["spans"]) == MAX_SPANS_PER_RECORD + 1  # + the root
        assert record["spans_dropped"] == 5


class TestStages:
    def test_late_stages_are_observed_at_close_the_audits_children_not(
            self, recorder, metrics):
        stamp("decode", 1.0, 1.5, "r")                    # the answer's
        stamp("decode", 2.0, 2.25, "r", parent="verify")  # the audit's
        with span("verify", request_id="r"):
            pass
        stamp("embed", 0.1, 0.2, "r")  # a tile stage waits for the first token
        assert _stage_counts(metrics) == {"decode": 1, "verify": 1}
        assert len(recorder.get("r")["spans"]) == 5  # all four are spans

    def test_first_token_tiles_receipt_to_now_exactly(self, recorder, metrics):
        origin = recorder.origin()
        recorder.start_request("t", t_received=origin + 1.0)
        stamp("pool_wait", origin + 1.0, origin + 1.1, "t")
        stamp("embed", origin + 1.2, origin + 1.5, "t", parent="graph.retrieve")
        stamp("rerank", origin + 1.5, origin + 1.7, "t")
        tracing.close_ttft("t", origin + 3.0, [
            ("inbox_wait", origin + 1.8, origin + 1.9, {}),
            ("slot_wait", origin + 1.9, origin + 1.9, {}),
            ("prefill", origin + 1.9, origin + 3.0, {"segments": 2}),
        ])
        record = recorder.get("t")
        stages = record["stages_ms"]
        assert tuple(stages) == TTFT_STAGES
        assert sum(stages.values()) == pytest.approx(record["ttft_server_ms"], abs=1e-6)
        assert record["ttft_server_ms"] == pytest.approx(2000.0, abs=1e-3)
        assert stages["other"] == pytest.approx(200.0, abs=1e-3)  # 1.1-1.2 and 1.7-1.8
        assert stages["sparse_fuse"] == 0.0 and stages["slot_wait"] == 0.0
        # every tile stage observed once, the zeros included
        assert _stage_counts(metrics) == dict.fromkeys(TTFT_STAGES, 1)
        sums = {k.split("'")[1]: v["mean"] for k, v in
                metrics.export_json()["histograms"].items()}
        assert sum(sums.values()) == pytest.approx(2.0, abs=1e-6)

    def test_a_request_is_tiled_once_and_the_audit_never(self, recorder, metrics):
        origin = recorder.origin()
        recorder.start_request("once", t_received=origin)
        engine = [("inbox_wait", origin, origin + 0.1, {}),
                  ("slot_wait", origin + 0.1, origin + 0.1, {}),
                  ("prefill", origin + 0.1, origin + 0.5, {})]
        tracing.close_ttft("once", origin + 0.5, engine)
        tracing.close_ttft("once", origin + 0.9, engine, parent="verify")
        tracing.close_ttft("once", origin + 0.9, engine)  # a second admission
        assert _stage_counts(metrics) == dict.fromkeys(TTFT_STAGES, 1)
        assert recorder.get("once")["ttft_server_ms"] == pytest.approx(500.0, abs=1e-3)
        parents = [sp["parent"] for sp in recorder.get("once")["spans"]
                   if sp["name"] == "prefill"]
        assert parents == ["request", "verify", "request"]

    def test_untraced_caller_is_tiled_from_its_engine_stages(self, recorder, metrics):
        tracing.close_ttft(None, 2.0, [("inbox_wait", 1.0, 1.25, {}),
                                       ("slot_wait", 1.25, 1.5, {}),
                                       ("prefill", 1.5, 2.0, {})])
        assert _stage_counts(metrics) == dict.fromkeys(TTFT_STAGES, 1)
        assert recorder.records() == []

    def test_unknown_stage_raises_at_the_writer(self, metrics):
        with pytest.raises(KeyError, match="unknown stage"):
            tile_ttft({"pool_wait": 0.1, "emebd": 0.2}, 1.0)
        with pytest.raises(KeyError, match="unknown stage"):
            tile_ttft({"other": 0.1}, 1.0)  # the residual is never written
        with pytest.raises(KeyError, match="unknown stage"):
            metrics.record_request_stage("queue", 0.1)
        assert _stage_counts(metrics) == {}

    def test_stream_lag_counts_from_the_oldest_uncovered_put(self, recorder, metrics):
        recorder.start_request("s")
        recorder.note_stream_put("s", 10.0)
        recorder.note_stream_put("s", 10.2)  # coalesces into the same write
        assert recorder.take_stream_lag("s", 10.5) == pytest.approx(0.5)
        assert recorder.take_stream_lag("s", 10.6) is None  # nothing pending
        recorder.note_stream_put("s", 11.0)
        assert recorder.take_stream_lag("s", 11.1) == pytest.approx(0.1)
        assert recorder.get("s")["stream_lag_max_ms"] == pytest.approx(500.0)
        recorder.note_stream_put("s", 12.0)
        tracing.stream_written("s")
        assert _stage_counts(metrics) == {"stream_lag": 1}
        recorder.note_stream_put("s", 13.0)
        recorder.finish_request("s")  # an unwritten put does not outlive the request
        assert recorder.take_stream_lag("s", 14.0) is None


class TestExecutorSpans:
    def _graph(self, **kw):
        from sentio_tpu.graph.executor import END, GraphBuilder

        return (
            GraphBuilder()
            .add_node("alpha", lambda state: {"metadata": {"replica_id": 1}})
            .add_node("beta", lambda state: {"metadata": {"b": tracing.current()}}, **kw)
            .add_edge("alpha", "beta")
            .add_edge("beta", END)
            .set_entry("alpha")
            .compile()
        )

    def test_node_spans_carry_the_request_and_the_replica(self, recorder):
        state = self._graph().invoke({"metadata": {"query_id": "req-42"}})
        assert state["metadata"]["b"] == ("req-42", "graph.beta")
        spans = recorder.get("req-42")["spans"]
        assert [sp["name"] for sp in spans] == ["request", "graph.alpha", "graph.beta"]
        assert spans[1]["fields"] == {"replica_id": -1}
        assert spans[2]["fields"] == {"replica_id": 1}  # stamped upstream

    def test_no_query_id_no_record(self, recorder):
        state = self._graph().invoke({"metadata": {}})
        assert state["metadata"]["b"] == (None, "graph.beta")
        assert recorder.records() == []

    def test_detached_node_span(self, recorder):
        from sentio_tpu.graph.executor import wait_detached

        self._graph(detached=True).invoke({"metadata": {"query_id": "req-44"}})
        assert wait_detached(timeout_s=10)
        beta = [sp for sp in recorder.get("req-44")["spans"] if sp["name"] == "graph.beta"]
        assert len(beta) == 1 and beta[0]["fields"]["detached"] is True

    def test_node_failure_keeps_its_type_through_the_span(self, recorder):
        from sentio_tpu.graph.executor import END, GraphBuilder

        class Shed(Exception):
            soft_fail_exempt = True

        def boom(state):
            raise Shed("typed")

        graph = (GraphBuilder().add_node("boom", boom).add_edge("boom", END)
                 .set_entry("boom").compile())
        with pytest.raises(Shed):
            graph.invoke({"metadata": {"query_id": "req-45"}})
        assert recorder.get("req-45")["spans"][1]["name"] == "graph.boom"


class TestPump:
    def test_failed_tick_propagates_the_original_type_to_containment(
            self, recorder, metrics, caplog):
        """A tick that raises inside the decode_tick annotation reaches the
        pump's crash containment as itself (the log names the type), the
        failed tick is recorded, and the requeued ticket still finishes
        with its stages closed."""
        from sentio_tpu.infra import faults

        class DeviceFault(RuntimeError):
            pass

        svc = _tiny_service()
        try:
            with faults.inject("paged.step", error=DeviceFault("hbm"), times=1):
                with caplog.at_level("ERROR", logger="sentio_tpu.runtime.service"):
                    result = svc.generate("pump probe", max_new_tokens=3,
                                          request_id="pump-1", timeout_s=120)
        finally:
            faults.reset()
            svc.close()
        assert result.finish_reason in ("stop", "length")
        raised = [r.exc_info[0] for r in caplog.records if r.exc_info]
        assert DeviceFault in raised
        assert [e for e in recorder.timeline() if e.get("event") == "tick_failure"]
        names = [sp["name"] for sp in recorder.get("pump-1")["spans"]]
        assert names == ["request", "inbox_wait", "slot_wait", "prefill", "decode"]

    def test_tick_event_names_its_annotations_step(self, recorder, metrics):
        svc = _tiny_service()
        try:
            svc.generate("step probe", max_new_tokens=6, timeout_s=120)
        finally:
            svc.close()
        ticks = [e for e in recorder.timeline() if "phase_ms" in e]
        assert ticks and all(e["step"] == e["tick"] for e in ticks)


class TestProfileWindow:
    def test_window_runs_and_writes(self, tmp_path):
        out = profile_window(0.01, str(tmp_path))
        assert out["started"] is True
        assert out["log_dir"] == str(tmp_path)
        assert out["python_tracer"] is False

    def test_single_flight(self, tmp_path, monkeypatch):
        """The jax profiler is process-global: a second concurrent window
        is refused (409 at the endpoint), not interleaved. Deterministic:
        pin the busy flag directly instead of racing thread scheduling."""
        import sentio_tpu.infra.tracing as tracing_mod

        monkeypatch.setattr(tracing_mod, "_profile_active", True)
        refused = profile_window(0.01, str(tmp_path))
        assert refused["started"] is False
        assert "already active" in refused["error"]
        # releasing the flag restores normal operation
        monkeypatch.setattr(tracing_mod, "_profile_active", False)
        assert profile_window(0.01, str(tmp_path))["started"] is True

    def test_window_holds_ticks_phases_and_stages_without_python_frames(
            self, tmp_path, recorder, metrics):
        """What a chip run's ``/debug/profile`` window must hold, on the
        CPU: ``decode_tick`` steps carrying the flight tick number,
        ``tick.<phase>`` events and request-stage events on ``/host:CPU``,
        no Python frame, and ``benchmark/trace.py::load_events`` (as it
        stands) reads them — so ``label_gap`` names idle gaps by them."""
        from jax.profiler import ProfileData

        sys.path.insert(0, str(REPO))
        from benchmark.trace import find_xplane, load_events

        svc = _tiny_service()
        try:
            svc.generate("warm the programs", max_new_tokens=6, timeout_s=300)
            window = threading.Thread(
                target=profile_window, args=(1.5, str(tmp_path)), name="profile-window")
            window.start()
            import time

            time.sleep(0.3)
            svc.generate("inside the window", max_new_tokens=6,
                         request_id="in-window", timeout_s=120)
            window.join(timeout=60)
            assert not window.is_alive()
        finally:
            svc.close()
        xplane = find_xplane(tmp_path)
        host = [p for p in ProfileData.from_file(str(xplane)).planes
                if p.name == "/host:CPU"]
        assert host, "no /host:CPU plane"
        events = [ev for line in host[0].lines for ev in line.events]
        steps = [dict(ev.stats) for ev in events if ev.name == "decode_tick"]
        assert steps and all("step_num" in s for s in steps)
        flight_ticks = {e["tick"] for e in recorder.timeline()}
        assert {int(s["step_num"]) for s in steps} <= flight_ticks | {max(flight_ticks) + 1}
        prefill = [dict(ev.stats) for ev in events if ev.name == "prefill"]
        assert any(s.get("request_id") == "in-window" for s in prefill)
        assert not [ev.name for ev in events if ev.name.startswith("$")], "Python frames"
        names = {name for name, _s, _d, _line in load_events(xplane)["host"]}
        assert "decode_tick" in names
        assert {f"tick.{p}" for p in TICK_PHASES if p != "other"} <= names
