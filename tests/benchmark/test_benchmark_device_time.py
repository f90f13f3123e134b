"""The six per-layer metrics of PR 40, which read the program's account of
the device (``sentio_tpu_device_program_seconds_total``,
``sentio_tpu_encoder_forward_seconds_total``,
``sentio_tpu_prefill_turns_total``) and the ``decode`` stage: data files over
the ``prom_delta`` reader as it stands. Each file loads and reads a number
from two ``/metrics`` scrapes of a scratch run (a tiny engine behind its
service on the CPU, the encoders' forwards booked by hand), by hand on
made-up rows, and nothing from a program that lacks the series, as the parent
commit does."""

import json
import sys
import threading
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import readers, server  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
ANSWER = next(m for m in BENCH["end_to_end"] if m["name"] == "answer_latency_p50_ms")["workloads"]
PROGRAMS = "sentio_tpu_device_program_seconds_total"
NEW = {  # name -> (layer, better, moves, cells)
    "device_decode_share": ("admission and batching", "higher", "tpot_p50_ms", CELLS),
    "device_prefill_share": ("admission and batching", "lower", "answer_latency_p50_ms", ANSWER),
    "device_encoder_share": ("HTTP, graph and encoders", "lower", "tpot_p50_ms", CELLS),
    "encoder_queue_share": ("HTTP, graph and encoders", "lower", "answer_latency_p50_ms", ANSWER),
    "prefill_turn_wait_share": ("admission and batching", "lower", "answer_latency_p50_ms",
                                ["dsv2-ep8-rag-long", "mistral7b-rag-long"]),
    "answer_decode_share": ("model step", "lower", "answer_latency_p50_ms", ANSWER),
}


def read(name: str, before, after):
    obs = readers.Observations(prom_before=before, prom_after=after)
    return readers.read_metric(readers.load_metric("per_layer", name), obs)


@pytest.fixture(scope="module")
def scrapes():
    """Two scrapes round a scratch run: three chunked requests through a
    tiny engine's service, and one embed and one rerank forward booked as
    the stamper books them."""
    from sentio_tpu.infra import tracing
    from sentio_tpu.infra.flight import FlightRecorder, set_flight_recorder
    from sentio_tpu.infra.metrics import MetricsCollector, set_metrics
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.service import PagedGenerationService

    collector, stamper = MetricsCollector(), tracing.DeviceStamper()
    set_metrics(collector)
    set_flight_recorder(FlightRecorder())
    tracing.set_stamper(stamper)
    svc = PagedGenerationService(ContinuousBatchingEngine(
        max_slots=4, page_size=16, max_pages_per_seq=8, steps_per_tick=4,
        max_tick_steps=8, prefill_chunk=32, ignore_eos=True))
    try:
        svc.generate("warm every program of the run, " * 5, max_new_tokens=6,
                     request_id="warm", timeout_s=300)
        assert stamper.wait_idle(60)
        before = server.parse_metrics(collector.export_prometheus().decode())
        threads = [threading.Thread(
            target=svc.generate, args=(f"a long prompt, number {i}, " * 6,),
            kwargs={"max_new_tokens": 6, "request_id": f"scratch-{i}", "timeout_s": 300})
            for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        collector.record_device_program("embed", 0.003, queued_s=0.25)
        collector.record_device_program("rerank", 0.004, queued_s=0.10)
        assert stamper.wait_idle(60)
        after = server.parse_metrics(collector.export_prometheus().decode())
    finally:
        svc.close()
        tracing.set_stamper(None)
        set_flight_recorder(None)
        set_metrics(None)
    return before, after


@pytest.mark.parametrize("name", NEW)
def test_each_file_loads_and_reads_a_number_from_a_scratch_runs_two_scrapes(name, scrapes):
    value = read(name, *scrapes)
    assert value is not None and 0.0 <= value <= 100.0
    if name == "device_decode_share":
        assert value > 0.0
    if name == "encoder_queue_share":  # 0.35 queued of 0.357
        assert value == pytest.approx(100.0 * 0.35 / 0.357, abs=1e-6)
    if name == "prefill_turn_wait_share":
        assert value > 0.0  # three prompts of several segments shared the turns


def test_the_three_device_shares_are_parts_of_one_whole(scrapes):
    """decode + prefill (with admit) + encoders + other = 100: idle is in no
    program."""
    spec = dict(readers.load_metric("per_layer", "device_decode_share"), num=["other"])
    obs = readers.Observations(prom_before=scrapes[0], prom_after=scrapes[1])
    other = readers.read_metric(spec, obs)
    parts = [read(n, *scrapes) for n in
             ("device_decode_share", "device_prefill_share", "device_encoder_share")]
    assert sum(parts) + other == pytest.approx(100.0, abs=1e-9)


@pytest.mark.parametrize("name, want", [
    ("device_decode_share", 80.0),       # 3.2 of 4.0 s booked
    ("device_prefill_share", 15.0),      # (0.5 + 0.1) / 4
    ("device_encoder_share", 4.0),       # (0.1 + 0.06) / 4
    ("encoder_queue_share", 98.0),       # 7.84 queued, 0.16 running
    ("prefill_turn_wait_share", 80.0),   # 36 taken, 144 waited
    ("answer_decode_share", 68.0),       # 2.55 s decoding of a 3.75 s answer
])
def test_share_is_the_windows_delta_over_its_denominator(name, want):
    def rows(scale: float):
        programs = {"decode": 3.2, "prefill": 0.5, "admit": 0.1, "embed": 0.1,
                    "rerank": 0.06, "other": 0.04}
        stages = {"pool_wait": 0.0, "embed": 0.3, "sparse_fuse": 0.0, "rerank": 0.3,
                  "select": 0.0, "inbox_wait": 0.15, "slot_wait": 0.0, "prefill": 0.35,
                  "other": 0.1, "decode": 2.55, "verify": 9.0, "stream_lag": 9.0}
        out = [(PROGRAMS, {"program": k}, 5.0 + scale * v) for k, v in programs.items()]
        out += [("sentio_tpu_encoder_forward_seconds_total", {"part": k}, 5.0 + scale * v)
                for k, v in {"queued": 7.84, "running": 0.16}.items()]
        out += [("sentio_tpu_prefill_turns_total", {"kind": k}, 5.0 + scale * v)
                for k, v in {"taken": 36.0, "waited": 144.0}.items()]
        return out + [("sentio_tpu_request_stage_seconds_sum", {"stage": k}, 5.0 + scale * v)
                      for k, v in stages.items()]

    assert read(name, rows(0.0), rows(1.0)) == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_series_reports_nothing(name):
    """The parent commit has none of the three series, a window may close
    no request, and zeros published from the first tick move nothing: the
    reader returns nothing and the line leaves the metric out."""
    other = [("sentio_tpu_tick_phase_seconds_sum", {"phase": "deliver"}, 1.0)]
    assert read(name, other, other) is None
    zeros = [(PROGRAMS, {"program": p}, 0.0)
             for p in ("decode", "prefill", "admit", "embed", "rerank", "other")]
    assert read(name, zeros, zeros) is None
    short = [(PROGRAMS, {"program": "decode"}, 1.0),
             ("sentio_tpu_request_stage_seconds_sum", {"stage": "prefill"}, 1.0)]
    assert read(name, [], short) is None  # a label the file names is missing


@pytest.mark.parametrize("name", NEW)
def test_entry_is_as_the_issue_set_it(name):
    layer, better, moves, cells = NEW[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": "%", "better": better,
                     "source": "program_counter", "layer": layer, "moves": moves,
                     "workloads": list(cells)}
    assert layer in {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    # appended: the accepted entries stand before it, in their order
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    spec = readers.load_metric("per_layer", name)
    assert spec["reader"] == "prom_delta" and set(spec["num"]) <= set(spec["den"])
    assert len(spec["what"]) > 80
