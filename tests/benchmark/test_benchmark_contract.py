"""BENCHMARK.json against the contract's limits, and every name in it
against the files the harness finds by that name."""

import json
import re

import pytest
from bench_tree import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def cells_of(metric):
    return metric.get("workloads", sorted(CELLS))


def whole_step_share(metric):
    """A share of the chip's peak over a whole step: ``mfu`` as a part of its name."""
    return "mfu" in re.split(r"[_.\-]", metric["name"])


def assert_reader_file(folder, name):
    """A metric's file names its reader and what it reads, and repeats nothing
    that BENCHMARK.json states (unit, direction, source, layer, moves)."""
    from benchmark.readers import READERS

    spec = json.loads((REPO / "benchmark" / folder / f"{name}.json").read_text())
    assert spec["reader"] in READERS and spec["what"]
    assert not set(spec) & {"name", "unit", "better", "source", "layer", "moves", "bound"}


@pytest.mark.parametrize("folder,group", [("end_to_end", "end_to_end"), ("layer_metrics", "per_layer")])
def test_every_metric_file_is_named_by_a_metric(folder, group):
    files = {p.stem for p in (REPO / "benchmark" / folder).glob("*.json")}
    assert files == {m["name"] for m in BENCH[group]}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for word in BENCH["command"]:
        assert LINE.match(word) and not word.startswith("/") and ".." not in word
    assert any(word.startswith(p + "/") for word in BENCH["command"] for p in BENCH["paths"])
    # 2 + 14 x cells runs of run_seconds + 60 s, 180 s more per cell, 1200 spare, at 24 cells
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("why", "layer", "source"):
        if key in entry and key != "source" or (key == "source" and "file" in entry):
            assert LINE.match(entry[key]), (key, entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_entry_matches_its_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    data = json.loads((REPO / config["file"]).read_text())
    assert data["name"] == config["name"] and data["source"] == config["source"]
    assert data["reduced"] == config["reduced"] and len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key)
        # never a width
        assert not re.search(r"(hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$|head_dim|"
                             r"expansion|experts_per_tok)", key)
        assert data["published"][key] != data[key]
    assert data["assumed"], "what could not be confirmed in the sandbox is listed"
    assert (REPO / "benchmark" / "families" / f"{data['family']}.py").is_file()
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_and_reports_enough(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = json.loads((REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json").read_text())
    assert mix["name"] == cell["traffic"] and mix["loop"] in ("open", "closed")
    e2e = [m["name"] for m in BENCH["end_to_end"] if cell["name"] in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell["name"] in cells_of(m) for m in BENCH["per_layer"])


def test_four_chip_cells_are_at_most_a_quarter():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(len(BENCH["workloads"]) // 4, 1)


@pytest.mark.parametrize("metric", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert all(c in CELLS for c in cells_of(metric))
    assert_reader_file("end_to_end", metric["name"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    # stated, never implied: a metric without the list would be asked of every
    # cell a later PR adds, and that PR refused where its cell cannot read it
    assert metric.get("workloads") and all(c in CELLS for c in metric["workloads"])
    assert_reader_file("layer_metrics", metric["name"])
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    assert set(cells_of(metric)) <= set(cells_of(moved))
    # a share of a roofline or of the chip's peak is in %: a kernel's by its
    # name's end, the whole step's by ``mfu`` as a part of its name
    if metric["name"].endswith("_roofline") or whole_step_share(metric):
        assert metric["unit"] == "%" and metric["better"] == "higher"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_an_end_to_end_metric_with_a_cell_list_resolves_there_and_nowhere_else(cell):
    """``run.py::resolve_cell`` on the tree's own BENCHMARK.json: a cell's
    untraced line carries the end-to-end metrics that name it or name no cell;
    the time to a whole answer is the closed loops' and no open loop's."""
    from benchmark import run

    resolved = run.resolve_cell(cell, REPO / "BENCHMARK.json")
    got = [m["name"] for m in resolved["end_to_end"]]
    assert got == [m["name"] for m in BENCH["end_to_end"] if cell in cells_of(m)]
    closed = resolved["mix"]["loop"] == "closed"
    assert got == (["tpot_p50_ms", "answer_latency_p50_ms", "setup_s"] if closed else ["tpot_p50_ms", "setup_s"])
    # and every per-layer metric of the cell moves something the cell reports
    assert {m["moves"] for m in resolved["per_layer"]} <= set(got)


def test_a_whole_step_share_with_mfu_in_its_name_stands_beside_the_kernels_rooflines():
    """A kernel's roofline falls silent when a later PR renames the kernel or
    takes it off the path; the whole step's share of the peak, in every cell a
    kernel's roofline is read in and moving the same metric, still bounds it."""
    whole = [m for m in BENCH["per_layer"] if whole_step_share(m)]
    assert whole
    for kernel in (m for m in BENCH["per_layer"] if m["name"].endswith("_roofline")):
        assert any(w["moves"] == kernel["moves"] and set(kernel["workloads"]) <= set(w["workloads"])
                   and w["layer"] == kernel["layer"] for w in whole), kernel["name"]


@pytest.mark.parametrize("name, moves", [
    ("decode_rows_useful_share", "answer_latency_p50_ms"), ("window_out_tok_per_s", "answer_latency_p50_ms"),
    ("hbm_peak_gb", "answer_latency_p50_ms"), ("stage_queue_share", "tpot_p50_ms")])
def test_what_is_about_throughput_moves_the_time_to_a_whole_answer(name, moves):
    """Fuller rows, more tokens a second and the memory that buys slots do not
    shorten a step: they shorten what a closed loop's caller waits. The queue
    share is read in the open loop too, which reports no answer latency."""
    assert next(m for m in BENCH["per_layer"] if m["name"] == name)["moves"] == moves


def test_files_under_paths_have_contract_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", root)
        for path in (REPO / root).rglob("*"):
            rel = path.relative_to(REPO).as_posix()
            if "__pycache__" in rel or "/.work" in rel:
                continue
            assert ok.match(rel), rel


def test_layers_named_alike_are_spelled_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({name.lower() for name in layers}) == len(layers)
