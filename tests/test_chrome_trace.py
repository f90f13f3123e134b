"""Chrome/Perfetto trace exporter (infra/chrome_trace.py).

Golden test: a deterministic fake flight record round-trips to the
committed JSON byte-for-byte (the exporter is a pure function over plain
dicts). Schema test: phase slices nest exactly inside their tick slice
and the phase durations sum to the tick's pump wall time within 5% —
the invariant that makes the Perfetto view trustworthy."""

import json
from pathlib import Path

from sentio_tpu.infra.chrome_trace import build_chrome_trace, flight_to_chrome
from sentio_tpu.infra.flight import FlightRecorder, set_flight_recorder
from sentio_tpu.infra.phases import TICK_PHASES

GOLDEN = Path(__file__).parent / "fixtures" / "chrome_trace_golden.json"

# a deterministic two-tick, one-request, one-health-event flight timeline
# (the exact field shapes FlightRecorder.timeline()/records() emit)
FAKE_TICKS = [
    {
        "tick": 1, "t_s": 0.0100, "replica": 0,
        "dur_ms": 6.0, "pump_ms": 8.0,
        "phase_ms": {
            "inbox_drain": 1.0, "admission_build": 1.0,
            "prefill_dispatch": 2.0, "decode_dispatch": 2.0,
            "device_wait": 1.5, "deliver": 0.4, "other": 0.1,
        },
        "active_slots": 2, "queue_depth": 1, "inbox_depth": 0,
        "prefill_tokens": 32, "decode_tokens": 8, "free_pages": 10,
        "xla_compiles": 0,
    },
    {
        "tick": 2, "t_s": 0.0200, "replica": 0,
        "dur_ms": 4.0, "pump_ms": 5.0,
        "phase_ms": {
            "inbox_drain": 0.2, "admission_build": 0.3,
            "prefill_dispatch": 0.0, "decode_dispatch": 1.5,
            "device_wait": 2.5, "deliver": 0.4, "other": 0.1,
        },
        "active_slots": 2, "queue_depth": 0, "inbox_depth": 0,
        "prefill_tokens": 0, "decode_tokens": 8, "free_pages": 10,
        "xla_compiles": 0,
    },
    {
        "tick": 3, "t_s": 0.0250, "replica": 0,
        "event": "replica_health", "state": "QUARANTINED",
        "prior": "HEALTHY", "reason": "stalled",
    },
]

FAKE_RECORDS = [
    {
        "request_id": "req-1", "status": "done", "t_start_s": 0.001,
        "latency_ms": 30.0, "endpoint": "/chat", "mode": "fast",
        "question_chars": 24,
        "engine": {
            "replica_id": 0, "t_submit_s": 0.004, "ttft_ms": 8.0,
            "tokens": 8, "prompt_tokens": 16, "prefix_hit_tokens": 0,
            "finish_reason": "stop", "tpot_ms": 1.5,
            "tick_first": 0, "tick_last": 2,
        },
        "verify": {
            "mode": "async", "outcome": "pass", "confidence": 0.9,
            "verdict_ms": 12.0,
        },
        # as FlightRecorder stores them: a span without a parent hangs
        # under the request root the exporter synthesizes
        "ttft_server_ms": 11.0,
        "spans": [
            {"name": "pool_wait", "t0_s": 0.001, "t1_s": 0.002, "parent": None},
            {"name": "embed", "t0_s": 0.002, "t1_s": 0.003,
             "parent": "graph.retrieve"},
            {"name": "graph.retrieve", "t0_s": 0.002, "t1_s": 0.0035,
             "parent": None},
            {"name": "inbox_wait", "t0_s": 0.004, "t1_s": 0.0045, "parent": None},
            {"name": "slot_wait", "t0_s": 0.0045, "t1_s": 0.005, "parent": None},
            {"name": "prefill", "t0_s": 0.005, "t1_s": 0.012, "parent": None,
             "fields": {"segments": 1, "ticks": [1, 1], "prompt_tokens": 16,
                        "prefix_hit_tokens": 0}},
            {"name": "decode", "t0_s": 0.012, "t1_s": 0.031, "parent": None,
             "fields": {"tokens": 8, "ticks": [1, 2]}},
            {"name": "verify", "t0_s": 0.031, "t1_s": 0.043, "parent": None},
        ],
    },
]


def _build():
    return build_chrome_trace(FAKE_TICKS, FAKE_RECORDS)


class TestGolden:
    def test_round_trips_to_committed_json(self):
        """Deterministic: the committed artifact IS the exporter's output.
        On intentional format changes, regenerate with
        ``python -m tests.test_chrome_trace`` and review the diff."""
        got = _build()
        want = json.loads(GOLDEN.read_text())
        assert got == want

    def test_deterministic(self):
        assert _build() == _build()


class TestSchema:
    def _events(self):
        return _build()["traceEvents"]

    def _tick_slices(self):
        return [e for e in self._events()
                if e["ph"] == "X" and e["name"].startswith("tick ")]

    def test_phases_nest_inside_their_tick(self):
        """Every phase slice sits on the tick's pid/tid and falls entirely
        within the tick's [ts, ts+dur] window — Perfetto renders them as
        children of the tick, never bleeding into a neighbour."""
        events = self._events()
        ticks = self._tick_slices()
        assert len(ticks) == 2
        phase_names = set(TICK_PHASES)
        phase_slices = [e for e in events
                        if e["ph"] == "X" and e["name"] in phase_names]
        assert phase_slices, "no phase slices emitted"
        for phase in phase_slices:
            parents = [
                t for t in ticks
                if t["pid"] == phase["pid"] and t["tid"] == phase["tid"]
                and t["ts"] - 1e-6 <= phase["ts"]
                and phase["ts"] + phase["dur"] <= t["ts"] + t["dur"] + 1e-6
            ]
            assert len(parents) == 1, (
                f"phase {phase['name']} at ts={phase['ts']} does not nest "
                f"in exactly one tick (found {len(parents)})"
            )

    def test_phase_sum_matches_tick_wall_within_5pct(self):
        events = self._events()
        phase_names = set(TICK_PHASES)
        for tick in self._tick_slices():
            inside = [
                e for e in events
                if e["ph"] == "X" and e["name"] in phase_names
                and e["pid"] == tick["pid"] and e["tid"] == tick["tid"]
                and tick["ts"] - 1e-6 <= e["ts"] < tick["ts"] + tick["dur"]
            ]
            total = sum(e["dur"] for e in inside)
            assert abs(total - tick["dur"]) <= 0.05 * tick["dur"], (
                f"{tick['name']}: phase sum {total}µs vs wall {tick['dur']}µs"
            )

    def test_request_lane_is_laid_out_from_the_spans(self):
        events = self._events()
        req = [e for e in events if e["name"] == "request req-1"]
        assert len(req) == 1 and req[0]["ph"] == "X"
        assert req[0]["ts"] == 1000.0  # 0.001 s → µs
        assert req[0]["dur"] == 30000.0
        assert req[0]["args"]["ttft_server_ms"] == 11.0
        lane = {e["name"]: e for e in events
                if e["tid"] == req[0]["tid"] and e["pid"] == req[0]["pid"]
                and e["ph"] == "X"}
        assert set(lane) == {"request req-1", "pool_wait", "embed", "graph.retrieve",
                             "inbox_wait", "slot_wait", "prefill", "decode", "verify"}
        # a span names the span that caused it; one without hangs under the root
        assert lane["embed"]["args"]["parent"] == "graph.retrieve"
        assert lane["prefill"]["args"]["parent"] == "request"
        assert lane["prefill"]["ts"] == 5000.0 and lane["prefill"]["dur"] == 7000.0
        assert lane["prefill"]["args"]["segments"] == 1
        # first token = where prefill ends and decode starts
        assert lane["decode"]["ts"] == lane["prefill"]["ts"] + lane["prefill"]["dur"]
        # the async audit trails the answer: it overhangs the request slice
        assert lane["verify"]["args"]["outcome"] == "pass"
        assert lane["verify"]["ts"] == req[0]["ts"] + req[0]["dur"]
        assert lane["verify"]["dur"] == 12000.0

    def test_a_record_without_spans_still_gets_its_root(self):
        record = {k: v for k, v in FAKE_RECORDS[0].items() if k != "spans"}
        events = build_chrome_trace([], [record])["traceEvents"]
        assert [e["name"] for e in events if e["ph"] == "X"] == ["request req-1"]

    def test_health_instant(self):
        events = self._events()
        health = [e for e in events if e["name"].startswith("health:")]
        assert len(health) == 1
        assert health[0]["ph"] == "i" and health[0]["s"] == "p"
        assert health[0]["args"]["state"] == "QUARANTINED"

    def test_metadata_rows(self):
        events = self._events()
        procs = [e for e in events if e["name"] == "process_name"]
        assert [p["args"]["name"] for p in procs] == ["replica 0"]
        threads = [e for e in events if e["name"] == "thread_name"]
        assert {t["args"]["name"] for t in threads} == {
            "pump", "request lane 1"}


class TestLiveRecorder:
    def test_flight_to_chrome_full_timeline(self):
        rec = FlightRecorder()
        set_flight_recorder(rec)
        try:
            rec.start_request("live-1", endpoint="/chat", mode="fast")
            rec.note_engine_submit("live-1", replica_id=0)
            rec.record_tick(replica=0, dur_ms=1.0, pump_ms=1.2,
                            phase_ms={p: 1.2 / len(TICK_PHASES)
                                      for p in TICK_PHASES})
            rec.finish_engine("live-1", ttft_ms=0.5, finish_reason="stop")
            rec.finish_request("live-1", status="done")
            trace = flight_to_chrome(rec)
            names = {e["name"] for e in trace["traceEvents"]}
            assert "request live-1" in names
            assert any(n.startswith("tick ") for n in names)
        finally:
            set_flight_recorder(None)

    def test_flight_to_chrome_single_request_window(self):
        rec = FlightRecorder()
        rec.start_request("solo", endpoint="/chat")
        rec.note_engine_submit("solo", replica_id=0)
        rec.record_tick(replica=0, dur_ms=1.0, pump_ms=1.0,
                        phase_ms={"other": 1.0})
        rec.finish_engine("solo", finish_reason="stop")
        rec.finish_request("solo", status="done")
        trace = flight_to_chrome(rec, request_id="solo")
        assert trace is not None
        names = {e["name"] for e in trace["traceEvents"]}
        assert "request solo" in names
        assert flight_to_chrome(rec, request_id="missing") is None


if __name__ == "__main__":
    # regenerate the golden artifact (review the diff before committing)
    GOLDEN.write_text(json.dumps(_build(), indent=1, sort_keys=True) + "\n")
    print(f"rewrote {GOLDEN}")
