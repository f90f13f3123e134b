"""``benchmark/run.py`` end to end at tiny size on the CPU: once as the
committed cell on one virtual device, once as a four-chip cell made of
scratch files only — the proof that a tp=4 cell is data, not code."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


def run_cell(cell: str, trace: int, extra: list[str], xla_flags: str, more_env: dict | None = None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": xla_flags,
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored", **(more_env or {})}
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "3", "--trace", str(trace), *extra],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:] + proc.stdout[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def check_line(line: dict, cell: str, key: str, devices: int):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in BENCH[key] if cell in m.get("workloads", [cell])}
    for name, metric in line["metrics"].items():
        assert name in want and isinstance(metric["value"], float) and metric["unit"]
    return want


def test_committed_cell_on_one_virtual_device():
    cell = "mistral7b-rag-open"
    line, out = run_cell(cell, 0, [], "--xla_force_host_platform_device_count=1")
    want = check_line(line, cell, "end_to_end", devices=1)
    assert set(line["metrics"]) == want and line["metrics"]["setup_s"]["value"] > 0
    notes = [json.loads(ln) for ln in out.splitlines()[:-1] if ln.startswith("{")]
    window = next(n for n in notes if n.get("phase") == "window")
    # the only thing wrong with a rehearsal is its platform: nothing compiled
    # inside the window, /info equalled the file, the reference agreed
    assert window["problems"] == ["platform is cpu, not tpu (rehearsal)"], window
    assert window["answer_tokens_per_request"] == 96
    # every number the reference check held to a limit, beside it, comes last
    assert list(line)[-1] == "compared" and set(line["compared"]) == {
        "prefill_rel_rms", "decode_rel_rms", "decode_over_prefill", "served_token_gap", "served_logprob_err"}
    assert all(0 <= entry["value"] <= entry["limit"] for entry in line["compared"].values())


# loaded by every Python process of the run through PYTHONPATH: when the
# program's paged engine is imported, the answers ``run_all`` hands back get
# their first token altered — where the check's served answers are produced
BREAK_THE_SERVED_TOKENS = '''
import importlib.abc, importlib.util, sys

class Hook(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path, target=None):
        if name != "sentio_tpu.runtime.paged":
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(name)
        load = spec.loader.exec_module

        def exec_module(module):
            load(module)
            engine = module.ContinuousBatchingEngine
            run_all = engine.run_all

            def altered(self, *args, **kwargs):
                results = run_all(self, *args, **kwargs)
                for res in results:
                    res.tokens[0] = (res.tokens[0] + 1) % self.cfg.vocab_size
                return results

            engine.run_all = altered

        spec.loader.exec_module = exec_module
        return spec

sys.meta_path.insert(0, Hook())
'''


def test_a_broken_timed_path_makes_the_run_incorrect(tmp_path):
    """The whole run but the look for a chip, with the engine's answers
    altered underneath: the reference check fails, the run names it among
    its problems and on the error stream, and ``correct`` is false for THAT
    reason (the unbroken rehearsal above has the platform as its only one)."""
    (tmp_path / "sitecustomize.py").write_text(BREAK_THE_SERVED_TOKENS)
    path = os.pathsep.join(filter(None, [str(tmp_path), os.environ.get("PYTHONPATH", "")]))
    line, out = run_cell("yi6b-chat-closed", 0, [], "--xla_force_host_platform_device_count=1",
                         {"PYTHONPATH": path})
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] > 0
    notes = [json.loads(ln) for ln in out.splitlines()[:-1] if ln.startswith("{")]
    check = next(n for n in notes if n.get("phase") == "reference-check")
    assert check["ok"] is False and check["served_token_gap"] > check["served_token_gap_tol"]
    # the logits part never went through ``run_all``: it still agrees
    assert max(check["prefill_rel_rms"], check["decode_rel_rms"]) <= check["tolerance"]
    window = next(n for n in notes if n.get("phase") == "window")
    problems = window["problems"]
    assert len(problems) == 2 and any(p.startswith("reference check failed") for p in problems), problems
    # a closed-loop cell's untraced line carries the time to a whole answer
    # (an open loop's does not: ``test_committed_cell_on_one_virtual_device``)
    assert set(line["metrics"]) == {"tpot_p50_ms", "answer_latency_p50_ms", "setup_s"}
    answers = window["answer_ms_sorted"]   # the note shows which group of answers the median stood on
    assert len(answers) == line["attempted"] and answers == sorted(answers)
    assert line["metrics"]["answer_latency_p50_ms"]["value"] == pytest.approx(answers[(len(answers) + 1) // 2 - 1], abs=0.06)
    stages = next(n for n in notes if n.get("phase") == "stages")
    assert stages["requests"] == line["attempted"] and {"pool_wait", "slot_wait", "decode"} <= set(stages["mean_ms"])


def test_four_chip_cell_from_scratch_files_only(tmp_path):
    """A tensor-parallel cell is a configuration file with MESH_TP in its
    serve_env and ``chips: 4`` — no line of the harness changes."""
    config = json.loads((REPO / "benchmark" / "configs" / "yi-1.5-6b-l16.json").read_text())
    config["name"] = "scratch-tp4"
    config["chips"] = 4
    config["rehearsal"].update(num_attention_heads=8, num_key_value_heads=4, head_dim=8)
    config["rehearsal"]["serve_env"].update(MESH_TP="4", MESH_DP="1")
    (tmp_path / "scratch-tp4.json").write_text(json.dumps(config))
    bench = {**BENCH,
             "configs": [{"name": "scratch-tp4", "source": config["source"],
                          "file": str(tmp_path / "scratch-tp4.json"), "reduced": [], "why": "test"}],
             "workloads": [{"name": "scratch-tp4-chat", "config": "scratch-tp4",
                            "traffic": "chat-closed", "chips": 4, "why": "test"}]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        metric.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    line, out = run_cell("scratch-tp4-chat", 1, ["--benchmark-file", str(tmp_path / "BENCHMARK.json")],
                         xla_flags="")
    check_line(line, "scratch-tp4-chat", "per_layer", devices=4)
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert line["breakdown"]["device_ops"] and "tick_host_share" in line["metrics"]
    assert '"mesh": {' in out or "tp" in out  # the plan/trace notes name the mesh run


def test_no_accelerator_is_an_error_not_a_cpu_run(monkeypatch, capsys):
    """JAX seeing only a CPU, without the caller having asked for a
    rehearsal, ends the run before anything starts: exit code 1, no result."""
    sys.path.insert(0, str(REPO))
    from benchmark import run, server

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(server, "probe_device",
                        lambda env: {"platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "mistral7b-rag-open",
                                      "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert run.main() == 1
    captured = capsys.readouterr()
    assert "no accelerator" in captured.err and '"correct"' not in captured.out


def test_fewer_chips_than_the_cell_asks_is_an_error(monkeypatch, capsys, tmp_path):
    from benchmark import run, server

    bench = {**BENCH, "workloads": [{**BENCH["workloads"][0], "chips": 4}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    monkeypatch.setattr(server, "probe_device",
                        lambda env: {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", BENCH["workloads"][0]["name"],
                                      "--benchmark-file", str(tmp_path / "BENCHMARK.json")])
    assert run.main() == 1
    assert "asks 4 chips" in capsys.readouterr().err


@pytest.mark.parametrize("reply, want", [
    ((200, {"requests": 3, "ttft_server_ms": {"mean": 900.5, "p50": 880.0}, "residual_ms_max": 0.0,
            "stages_ms": {"pool_wait": {"count": 3, "mean": 400.25, "p50": 10.0},
                          "decode": {"count": 3, "mean": 2600.0, "p50": 2590.0}}}),
     {"requests": 3, "ttft_server_ms_mean": 900.5, "mean_ms": {"pool_wait": 400.25, "decode": 2600.0}}),
    ((200, {"requests": 0, "ttft_server_ms": None, "stages_ms": {}}),
     {"requests": 0, "ttft_server_ms_mean": None, "mean_ms": {}}),
    ((404, {"error": "not found"}), {"error": "status 404"}),
    (OSError("connection refused"), {"error": "connection refused"}),
], ids=["a-table", "no-request-finished", "no-such-route", "no-server"])
def test_the_stage_table_of_a_window_is_a_note_and_never_a_failure(monkeypatch, reply, want):
    """``run.stage_table``: mean ms a request stage over the window's requests
    from ``/debug/flight?last=N``; a program that cannot give it costs the run
    a note, not its result."""
    from benchmark import run, server

    asked = []

    def http_json(port, method, path, payload=None, timeout=900.0):
        asked.append((port, method, path))
        if isinstance(reply, Exception):
            raise reply
        return reply

    monkeypatch.setattr(server, "http_json", http_json)
    assert run.stage_table(8123, 190) == want
    assert asked == [(8123, "GET", "/debug/flight?last=190")]


STAGE_SHARES = ("stage_encoders_share", "stage_queue_share", "stage_prefill_share",
                "decode_rows_useful_share")


def test_traced_rehearsal_reads_all_four_from_the_program(tmp_path):
    """(Here and not beside the readers' own tests in ``test_benchmark_stages.py``:
    every rehearsal in the tree's own ``benchmark/.work`` runs from THIS file, so
    from one worker, one at a time — a second one at once wipes the first's
    checkpoints.) ``--trace 1`` on the CPU, every per-layer metric asked of the one
    cell: the three stage shares are ratios of one denominator (so they sum
    to under 100), the row-step share is a ratio of counts, and the metrics
    the cell had before are still there beside them."""
    bench = json.loads(json.dumps(BENCH))
    for metric in bench["per_layer"]:
        metric.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    line, _out = run_cell("mistral7b-rag-open", 1, ["--seed", "2147483693", "--seconds", "4", "--benchmark-file",
                                                    str(tmp_path / "BENCHMARK.json")],
                          "--xla_force_host_platform_device_count=1")   # the later --seed and --seconds hold
    assert line["failed"] == 0 and line["attempted"] > 0
    got = {name: line["metrics"][name]["value"] for name in STAGE_SHARES}
    assert all(0.0 <= v <= 100.0 for v in got.values()), got
    assert got["stage_encoders_share"] > 0 and got["stage_prefill_share"] > 0
    assert got["decode_rows_useful_share"] > 0
    shares = sum(got[n] for n in STAGE_SHARES[:3])
    assert 0.0 < shares < 100.0, got
    assert {"tick_host_share", "graph_pre_generate_ms", "client_ttft_p50_ms"} <= set(line["metrics"])
    # the traced window's host plane is named by the program, not by frames
    gaps = [name for name, _s in line["breakdown"]["idle_gaps"]]
    assert not [g for g in gaps if g.startswith("$")], gaps
