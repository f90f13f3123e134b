"""Set-up measured from inside (ISSUE 53): the ``startup`` flight record
tiles process start → ready by phase (infra/startup.py), every compile is
timed by program with what the persistent cache answered
(infra/tracing.py's ``jax.monitoring`` listeners → analysis/audit/fence.py),
and an upload's stages are spans on its own record (ops/ingest.py)."""

import asyncio

import pytest
from aiohttp.test_utils import TestClient, TestServer

from sentio_tpu.analysis.audit import fence
from sentio_tpu.analysis.audit.registry import jit_family
from sentio_tpu.config import EmbedderConfig, GeneratorConfig, RerankConfig, Settings
from sentio_tpu.infra import startup, tracing
from sentio_tpu.infra.chrome_trace import build_chrome_trace
from sentio_tpu.infra.flight import FlightRecorder, get_flight_recorder, set_flight_recorder
from sentio_tpu.infra.metrics import MetricsCollector, set_metrics
from sentio_tpu.infra.phases import (
    CACHE_OUTCOMES,
    COMPILE_PARTS,
    INGEST_STAGES,
    STARTUP_PHASES,
)
from sentio_tpu.serve.app import create_app
from sentio_tpu.serve.dependencies import DependencyContainer


def fast_settings() -> Settings:
    return Settings(
        embedder=EmbedderConfig(provider="hash", dim=32),
        generator=GeneratorConfig(provider="echo", use_verifier=False, max_new_tokens=32),
        rerank=RerankConfig(enabled=True, kind="passthrough"),
    )


@pytest.fixture()
def recorder():
    rec = FlightRecorder()
    set_flight_recorder(rec)
    yield rec
    set_flight_recorder(None)


@pytest.fixture()
def metrics():
    m = MetricsCollector()
    set_metrics(m)
    yield m
    set_metrics(None)


@pytest.fixture()
def fresh_start(recorder, metrics):
    """A start of the test's own: the record on its own recorder."""
    startup.reset()
    yield recorder
    startup.reset()


def _counters(metrics, name):
    return {tuple(k[len(name) + 1:-1].replace("'", "").replace(" ", "").rstrip(",").split(",")): v
            for k, v in metrics.export_json()["counters"].items() if k.startswith(f"{name}(")}


def sp(name, t0, t1, **fields):
    return {"name": name, "t0_s": t0, "t1_s": t1, "parent": "request",
            **({"fields": fields} if fields else {})}


# ------------------------------------------------------------------ the tile

TILES = {
    "flat phases and what no span covers": (
        [sp("startup.import", 0.0, 2.0), sp("startup.backend", 2.5, 3.0),
         sp("startup.listen", 9.0, 10.0)],
        10.0, {"import": 2.0, "backend": 0.5, "listen": 1.0, "other": 6.5}),
    "a component built inside another is its child, not counted twice": (
        [sp("startup.ingestor", 0.0, 8.0), sp("startup.embedder", 1.0, 5.0),
         sp("startup.dense_index", 5.0, 6.0)],
        8.0, {"ingestor": 3.0, "embedder": 4.0, "dense_index": 1.0, "other": 0.0}),
    "reads and placements are the weights phase wherever they ran": (
        [sp("startup.decoder", 0.0, 6.0), sp("weights.read", 1.0, 4.0, bytes=100, mmap=False),
         sp("weights.place", 4.0, 5.5, bytes=90), sp("startup.embedder", 6.0, 7.0),
         sp("weights.place", 6.5, 7.0, bytes=10, leaves_cast=3, model="embedder")],
        7.0, {"decoder": 1.5, "weights": 5.0, "embedder": 0.5, "other": 0.0}),
    "a span that is no phase belongs to the phase that holds it": (
        [sp("startup.embedder", 0.0, 4.0), sp("embed", 1.0, 2.0),
         sp("startup.cache_manager", 4.0, 4.5), sp("startup.generation_service", 5.0, 9.0),
         sp("pool.alloc", 5.5, 6.5), sp("prefix.warm", 7.0, 8.5)],
        9.0, {"embedder": 4.0, "generation_service": 1.5, "pool.alloc": 1.0,
              "prefix.warm": 1.5, "other": 1.0}),
    "what follows ready is no part of the start": (
        [sp("startup.import", 0.0, 1.0), sp("startup.graph", 1.0, 3.0),
         sp("startup.chat_handler", 4.5, 5.0)],
        4.0, {"import": 1.0, "graph": 2.0, "other": 1.0}),
}


@pytest.mark.parametrize("case", sorted(TILES))
def test_the_tile_is_self_time_a_phase_and_sums_to_ready(case):
    spans, ready_s, want = TILES[case]
    got = startup.tile(spans, ready_s)
    assert tuple(got["phases"]) == STARTUP_PHASES
    assert got["phases"] == {**dict.fromkeys(STARTUP_PHASES, 0.0), **want}
    assert sum(got["phases"].values()) == pytest.approx(ready_s, abs=1e-6)


def test_the_tile_keeps_what_was_read_and_placed():
    spans, ready_s, _ = TILES["reads and placements are the weights phase wherever they ran"]
    assert startup.tile(spans, ready_s)["weights"] == {
        "read_s": 3.0, "place_s": 2.0, "bytes_read": 100, "bytes_placed": 100, "leaves_cast": 3}


def test_the_tile_counts_from_the_records_own_start():
    """A recorder whose zero is not the process's start (a test's) gives the
    same tile: the spans are taken from the record's ``t_start_s``."""
    spans, ready_s, want = TILES["flat phases and what no span covers"]
    shifted = [dict(s, t0_s=s["t0_s"] - 100.0, t1_s=s["t1_s"] - 100.0) for s in spans]
    assert startup.tile(shifted, ready_s, -100.0)["phases"]["other"] == want["other"]


def test_the_process_start_is_the_operating_systems_not_an_imports():
    import time

    assert startup.process_start() < time.perf_counter()
    assert startup.process_start_unix() == pytest.approx(time.time() - startup.uptime_s(), abs=0.05)
    with open("/proc/self/stat") as f:  # started before this interpreter imported anything
        assert startup.uptime_s() > 0.0 and f.read()


# ------------------------------------------------------- a container's start


@pytest.fixture()
def started(fresh_start):
    """A container built through its one seam — the ingestor first, so that
    the embedder and both indexes are built INSIDE its build — and marked
    ready as ``run_server`` marks it."""
    container = DependencyContainer(settings=fast_settings())
    container.ingestor
    container.initialize_all()
    startup.listening_from()
    done = startup.mark_ready()
    record = fresh_start.get(startup.STARTUP_ID)
    yield container, done, record
    container.cleanup()


def test_the_phases_of_a_start_sum_to_ready_s(started):
    _container, done, record = started
    assert tuple(done["phases"]) == STARTUP_PHASES
    assert sum(done["phases"].values()) == pytest.approx(done["ready_s"], abs=1e-3)
    assert record["status"] == "done" and record["ready_s"] == done["ready_s"]
    assert record["latency_ms"] == pytest.approx(done["ready_s"] * 1e3, abs=0.01)
    assert record["process_start_unix"] == round(startup.process_start_unix(), 3)
    assert done["phases"]["other"] >= 0.0 and done["phases"]["listen"] >= 0.0


@pytest.mark.parametrize("child, parent", [
    ("startup.embedder", "startup.ingestor"),
    ("startup.dense_index", "startup.ingestor"),
    ("startup.sparse_index", "startup.ingestor"),
    ("startup.web_cache_index", "startup.retriever"),
    ("startup.backend", "request"),
    ("startup.graph", "request"),
    ("startup.listen", "request"),
])
def test_a_component_built_inside_another_is_its_child(started, child, parent):
    _container, _done, record = started
    spans = {s["name"]: s for s in record["spans"]}
    assert spans[child]["parent"] == parent
    holder = spans[parent]
    assert holder["t0_s"] <= spans[child]["t0_s"] + 2e-6  # ends are kept to the microsecond
    assert spans[child]["t1_s"] <= holder["t1_s"] + 2e-6


def test_the_gauge_has_every_phase_and_only_those(started, metrics):
    _container, done, _record = started
    text = metrics.export_prometheus().decode()
    for phase in STARTUP_PHASES:
        assert f'sentio_tpu_startup_seconds{{phase="{phase}"}} {done["phases"][phase]}' in text
    assert text.count("sentio_tpu_startup_seconds{") == len(STARTUP_PHASES) <= 24
    with pytest.raises(KeyError):
        metrics.set_startup_phases({"tyop": 1.0})


def test_after_ready_a_component_is_no_part_of_the_start(started):
    container, done, record = started
    n = len(record["spans"])
    container._cache.pop("cache_manager")
    container.cache_manager  # built lazily, after the start
    with startup.phase("late"):
        pass
    startup.stamp_phase("later", 0.0, 1.0)
    assert len(startup.mark_ready()["phases"]) == len(done["phases"])
    assert startup.mark_ready() is done  # the first call decided
    assert len(get_flight_recorder().get(startup.STARTUP_ID)["spans"]) == n


def test_the_startup_record_outlives_the_requests_that_follow_it(fresh_start):
    rec = FlightRecorder(max_requests=4)
    set_flight_recorder(rec)
    startup.stamp_phase("import", startup.process_start(), startup.process_start() + 1.0)
    for i in range(32):
        rec.start_request(f"r-{i}")
    assert rec.get(startup.STARTUP_ID) is not None
    assert len(rec.records()) == 5 and rec.dropped_requests == 28


def test_uptime_and_the_log_line_read_the_startup_clock(started, caplog):
    container, _done, _record = started
    assert not hasattr(container, "started_at")
    up = container.health_handler.basic()["uptime_s"]
    assert up == pytest.approx(startup.uptime_s(), abs=0.2)
    fresh = DependencyContainer(settings=fast_settings())
    with caplog.at_level("INFO", logger="sentio_tpu.serve.dependencies"):
        fresh.initialize_all()
    fresh.cleanup()
    assert any("after the process started" in r.getMessage() for r in caplog.records)


# ------------------------------------------------- every compile, by program


@pytest.mark.parametrize("fun_name, label", [
    ("fwd", "fwd"), ("jit(fwd)", "fwd"), ("pmap(fwd)", "fwd"),
    ("add", "other"), ("jit(multiply)", "other"), ("jit(_reduce_sum)", "other"),
    ("startup_probe", "startup_probe"), ("jit(startup_probe)", "startup_probe"),
])
def test_a_program_keeps_its_name_only_if_registered(fun_name, label):
    fence.register_program("startup_probe")
    assert fence.program_label(fun_name) == label


def test_the_program_set_is_bounded(monkeypatch):
    monkeypatch.setattr(fence, "_programs", set(fence._programs))  # the process's own stays
    for i in range(3 * fence.MAX_PROGRAMS):
        fence.register_program(f"minted_{i}")
    assert len(fence._programs) == fence.MAX_PROGRAMS
    assert fence.program_label(f"jit(minted_{3 * fence.MAX_PROGRAMS - 1})") == "other"


@pytest.fixture()
def compile_cache(tmp_path):
    """JAX's persistent cache on, in a directory of the test's own, taking
    every program however small; off again afterwards (tests/conftest.py)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    tracing.install_compile_listeners()
    keys = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path / "cache"),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    fence.reset()
    yield tmp_path / "cache"
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    fence.reset()


def _probe():
    import jax.numpy as jnp

    @jit_family("test.startup_probe", register=False)
    def startup_probe(x):
        return jnp.tanh(x) * 2.0 + jnp.sum(x)

    return startup_probe


def test_a_miss_then_a_hit_are_booked_under_the_program(compile_cache, metrics, recorder):
    import jax
    import jax.numpy as jnp

    x = jnp.ones((8, 8))
    fence.drain_events()
    _probe()(x).block_until_ready()       # the cache is empty: the backend compiles
    [missed] = [e for e in fence.drain_events() if e["family"] == "test.startup_probe"]
    jax.clear_caches()                    # the jit cache goes, the directory stays
    _probe()(x).block_until_ready()       # the same program: the directory has it
    [hit] = [e for e in fence.drain_events() if e["family"] == "test.startup_probe"]
    assert missed["cache"] == "miss" and hit["cache"] == "hit"
    assert missed["seconds"] > 0.0 and hit["seconds"] > 0.0
    seconds = {k: v for k, v in _counters(metrics, "compile_seconds").items()
               if k[0] == "startup_probe"}
    assert set(seconds) == {("startup_probe", part) for part in COMPILE_PARTS}
    assert all(v > 0.0 for v in seconds.values())
    outcomes = {k: v for k, v in _counters(metrics, "compile_cache").items()
                if k[0] == "startup_probe"}
    assert outcomes == {("startup_probe", outcome): 1.0 for outcome in CACHE_OUTCOMES}
    mine = fence.compile_summary()["by_program"]["startup_probe"]
    assert mine["hits"] == mine["misses"] == 1
    assert mine["backend_miss_s"] == pytest.approx(seconds[("startup_probe", "backend_miss")], abs=1e-5)
    text = metrics.export_prometheus().decode()
    assert 'sentio_tpu_compile_cache_total{outcome="hit",program="startup_probe"} 1.0' in text
    assert 'sentio_tpu_compile_seconds_total{part="backend_hit",program="startup_probe"}' in text


def test_an_unregistered_functions_compile_lands_under_other(compile_cache, metrics, recorder):
    import jax
    import jax.numpy as jnp

    def not_a_family(x):
        return jnp.cos(x) - 1.0

    before = fence.compile_summary()["by_program"].get("other", {}).get("misses", 0)
    jax.jit(not_a_family)(jnp.ones((4, 4))).block_until_ready()
    summary = fence.compile_summary()
    assert "not_a_family" not in summary["by_program"]
    assert summary["by_program"]["other"]["misses"] > before
    programs = {k[0] for k in _counters(metrics, "compile_seconds")}
    assert "not_a_family" not in programs and "other" in programs
    # the functions a trace calls are part of it: one trace a compile, not one a function
    assert summary["trace_lower_s"] < 5.0 and summary["misses"] >= 1


def test_a_compile_names_itself_to_the_profiler_and_the_span_it_ran_in(
        compile_cache, metrics, recorder, monkeypatch):
    import jax.numpy as jnp

    seen = []
    real = tracing.annotation

    def spy(name, **fields):
        seen.append((name, fields))
        return real(name, **fields)

    monkeypatch.setattr(tracing, "annotation", spy)
    recorder.start_request("req-c")
    with tracing.span("embed", request_id="req-c"):
        _probe()(jnp.ones((2, 2))).block_until_ready()
    parts = [f["part"] for name, f in seen if name == "compile.startup_probe"]
    assert parts == ["trace", "lower", "backend"]
    embed = next(s for s in recorder.get("req-c")["spans"] if s["name"] == "embed")
    assert embed["fields"]["compile_cache"] == "miss"
    assert 0.0 < embed["fields"]["compile_ms"] <= (embed["t1_s"] - embed["t0_s"]) * 1e3 + 0.01


class TestCompileTimeOnASpan:
    def test_a_compile_waits_for_the_span_it_ran_in(self, recorder):
        recorder.start_request("r")
        t0 = recorder.origin()
        recorder.note_compile_time("r", "prefill", t0 + 2.0, 1.5, "miss")
        assert "spans" not in recorder.get("r")
        recorder.add_span("r", "prefill", t0 + 0.4, t0 + 2.5)
        [_root, prefill] = recorder.get("r")["spans"]
        assert prefill["fields"] == {"compile_ms": 1500.0, "compile_cache": "miss"}

    def test_a_closed_span_takes_it_and_compiles_sum(self, recorder):
        recorder.start_request("r")
        t0 = recorder.origin()
        recorder.add_span("r", "prefill", t0 + 1.0, t0 + 5.0)
        recorder.note_compile_time("r", "prefill", t0 + 2.0, 1.0, "hit")
        recorder.note_compile_time("r", "prefill", t0 + 4.0, 2.0, "miss")
        recorder.note_compile_time("r", "prefill", t0 + 4.5, 0.25, "hit")
        recorder.note_compile_time("r", "prefill", t0 + 4.8, 0.25, None)  # a trace: no outcome
        [_root, prefill] = recorder.get("r")["spans"]
        assert prefill["fields"] == {"compile_ms": 3500.0, "compile_cache": "miss"}

    def test_another_spans_compile_is_not_taken(self, recorder):
        recorder.start_request("r")
        t0 = recorder.origin()
        recorder.add_span("r", "embed", t0 + 1.0, t0 + 2.0)
        recorder.note_compile_time("r", "embed", t0 + 3.0, 1.0, "miss")     # after it closed
        recorder.note_compile_time("r", "rerank", t0 + 1.5, 1.0, "miss")    # another name
        recorder.note_compile_time("gone", "embed", t0 + 1.5, 1.0, "miss")  # no such record
        assert "fields" not in recorder.get("r")["spans"][1]
        recorder.finish_request("r")
        assert not recorder._compile_pending


def test_a_compile_inside_a_request_is_on_its_span_and_on_the_tick(
        compile_cache, metrics, recorder):
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.service import PagedGenerationService

    stamper = tracing.DeviceStamper()
    tracing.set_stamper(stamper)
    svc = PagedGenerationService(ContinuousBatchingEngine(
        max_slots=2, page_size=16, max_pages_per_seq=8, ignore_eos=True,
        steps_per_tick=4, max_tick_steps=8))
    try:
        svc.generate("a prompt whose programs no one has compiled yet",
                     max_new_tokens=6, request_id="cold", timeout_s=300)
    finally:
        svc.close()
        stamper.wait_idle(30)
        tracing.set_stamper(None)
    prefill = next(s for s in recorder.get("cold")["spans"] if s["name"] == "prefill")
    assert prefill["fields"]["compile_cache"] == "miss"
    assert 0.0 < prefill["fields"]["compile_ms"] <= (prefill["t1_s"] - prefill["t0_s"]) * 1e3 + 0.01
    events = [e for t in recorder.timeline() for e in t.get("compile_events", ())]
    families = {e["family"] for e in events}
    assert {"paged.prefill_scatter", "paged.step_n"} <= families
    for event in events:
        assert event["seconds"] > 0.0 and event["cache"] == "miss"
    by_program = fence.compile_summary()["by_program"]
    assert by_program["step_n"]["misses"] >= 1 and by_program["prefill_scatter"]["misses"] >= 1
    # the family counter is as it was: one count a compile, no seconds
    assert _counters(metrics, "xla_compiles")[("paged.step_n",)] >= 1.0


# ------------------------------------------------ the server: /info, upload


@pytest.fixture(scope="module")
def served():
    """One server, one start, one upload of two files and one chat: what
    ``/info``, ``/metrics``, the upload's record and the chrome export hold."""
    import aiohttp

    rec, m = FlightRecorder(), MetricsCollector()
    set_flight_recorder(rec)
    set_metrics(m)
    startup.reset()
    out: dict = {}

    async def body():
        container = DependencyContainer(settings=fast_settings())
        client = TestClient(TestServer(create_app(container=container)))
        await client.start_server()
        startup.mark_ready()  # the sites accept: what ``run_server``'s print hook marks
        try:
            form = aiohttp.FormData()
            form.add_field("file", b"plain text about ring attention", filename="a.txt")
            form.add_field("file", b"more text, about paged attention", filename="b.txt")
            resp = await client.post("/upload", data=form)
            out["upload"] = (resp.status, await resp.json())
            resp = await client.post("/chat", json={"question": "what about attention?"})
            out["chat"] = resp.status
            out["info"] = await (await client.get("/info")).json()
            out["health"] = await (await client.get("/health")).json()
            out["metrics"] = await (await client.get("/metrics")).text()
            out["chrome"] = await (await client.get("/debug/flight?format=chrome")).json()
            out["startup_record"] = await (await client.get("/debug/flight/startup")).json()
        finally:
            await client.close()

    asyncio.run(body())
    out["upload_record"] = next(r for r in rec.records() if r.get("endpoint") == "/upload")
    yield out
    set_flight_recorder(None)
    set_metrics(None)
    startup.reset()


@pytest.mark.parametrize("key", ["process_start_unix", "ready_s", "phases", "weights",
                                 "compile", "ingest"])
def test_info_startup_has_every_key(served, key):
    block = served["info"]["startup"]
    assert block[key] is not None
    assert sum(block["phases"].values()) == pytest.approx(block["ready_s"], abs=1e-3)


@pytest.mark.parametrize("path", [
    ("compile", "trace_lower_s"), ("compile", "backend_miss_s"), ("compile", "backend_hit_s"),
    ("compile", "hits"), ("compile", "misses"), ("compile", "by_program"),
    ("ingest", "seconds_total"), ("ingest", "stages"), ("ingest", "calls"),
    ("ingest", "docs"), ("ingest", "chunks"), ("ingest", "index_size"),
    ("ingest", "bm25_updates", "add"), ("ingest", "bm25_updates", "build"),
    ("weights", "read_s"), ("weights", "place_s"), ("phases", "weights"),
])
def test_info_startup_has_what_the_queued_metrics_read(served, path):
    node = served["info"]["startup"]
    for key in path:
        node = node[key]
    assert node is not None and served["upload"][0] == 200 and served["chat"] == 200


def test_info_sums_the_uploads_stages(served):
    ingest = served["info"]["startup"]["ingest"]
    assert tuple(ingest["stages"]) == INGEST_STAGES
    assert ingest["seconds_total"] == pytest.approx(sum(ingest["stages"].values()), abs=1e-5)
    assert ingest["seconds_total"] > 0.0
    assert (ingest["calls"], ingest["docs"], ingest["chunks"], ingest["index_size"]) == (2, 2, 2, 2)
    for stage in INGEST_STAGES:
        assert f'sentio_tpu_ingest_stage_seconds_total{{stage="{stage}"}}' in served["metrics"]
    assert served["metrics"].count("sentio_tpu_ingest_stage_seconds_total{") == len(INGEST_STAGES)


def test_an_uploads_files_are_added_to_the_sparse_index_not_built_again(served):
    assert served["info"]["startup"]["ingest"]["bm25_updates"] == {"add": 2, "build": 0}
    assert 'sentio_tpu_bm25_updates_total{kind="add"} 2.0' in served["metrics"]
    assert 'sentio_tpu_bm25_updates_total{kind="build"}' not in served["metrics"]
    sparse = [s["fields"] for s in served["upload_record"]["spans"] if s["name"] == "ingest.sparse_add"]
    assert [(f["path"], f["index_size"]) for f in sparse] == [("add", 1), ("add", 2)]
    assert all(f["tokens"] > 0 for f in sparse)


@pytest.mark.parametrize("stage", INGEST_STAGES)
def test_an_upload_leaves_its_stages_as_spans_on_its_own_record(served, stage):
    record = served["upload_record"]
    assert record["request_id"].startswith("upload-") and record["status"] == "done"
    mine = [s for s in record["spans"] if s["name"] == f"ingest.{stage}"]
    assert len(mine) == 2  # a span a file
    assert all(s["parent"] == "ingest" for s in mine)
    if stage == "chunk":
        assert [s["fields"] for s in mine] == [{"docs": 1, "chunks": 1}] * 2
    elif stage == "embed":
        assert all(s["fields"]["chunks"] == 1 for s in mine)
    else:  # the index as it stood once this file was in it: what a growing stage grows with
        assert [s["fields"]["index_size"] for s in mine] == [1, 2]
    summed = record["ingest"]
    assert (summed["files"], summed["docs"], summed["chunks"], summed["index_size"]) == (2, 2, 2, 2)
    assert summed["stage_ms"][stage] == pytest.approx(
        sum(s["t1_s"] - s["t0_s"] for s in mine) * 1e3, abs=0.5)


def test_the_uploads_response_is_as_it_was(served):
    status, body = served["upload"]
    assert status == 200 and body["status"] == "ok"
    assert set(body["files"][0]) == {"filename", "documents_loaded", "chunks_created",
                                     "chunks_embedded", "chunks_stored", "files_skipped",
                                     "errors", "elapsed_s"}


def test_the_chrome_export_has_the_startup_track(served):
    events = served["chrome"]["traceEvents"]
    [lane] = [e for e in events if e["ph"] == "M" and e["args"].get("name") == "startup"]
    track = [e for e in events if e["ph"] == "X" and (e["pid"], e["tid"]) == (lane["pid"], lane["tid"])]
    root = next(e for e in track if e["name"] == "startup")
    ready_s = served["info"]["startup"]["ready_s"]
    assert root["dur"] == pytest.approx(ready_s * 1e6, abs=1.0)
    assert root["args"]["ready_s"] == ready_s and "phases" in root["args"]
    names = {e["name"] for e in track}
    assert {"startup.backend", "startup.embedder", "startup.graph"} <= names
    assert all(e["ts"] >= root["ts"] - 1.0 for e in track)
    assert not any(e["name"] == "request startup" for e in events)


def test_the_startup_record_is_a_flight_record_like_any(served):
    record = served["startup_record"]
    assert record["request_id"] == "startup" and record["status"] == "done"
    assert record["spans"][0]["name"] == "request"
    names = {s["name"] for s in record["spans"]}
    assert all(s["parent"] in names for s in record["spans"][1:])
    assert served["health"]["uptime_s"] >= served["info"]["startup"]["ready_s"] - 0.1


def test_a_hand_made_startup_record_exports_as_a_track():
    record = {"request_id": "startup", "status": "done", "t_start_s": 0.0, "latency_ms": 3000.0,
              "ready_s": 3.0, "spans": [dict(sp("startup.import", 0.0, 1.0), parent=None)]}
    chat = {"request_id": "q-1", "status": "done", "t_start_s": 4.0, "latency_ms": 10.0}
    events = build_chrome_trace([], [record, chat])["traceEvents"]
    lanes = {e["tid"]: e["args"]["name"] for e in events if e["name"] == "thread_name"}
    assert lanes == {0: "pump", 1: "startup", 2: "request lane 2"}
    assert [e["name"] for e in events if e["ph"] == "X"] == [
        "startup", "startup.import", "request q-1"]
