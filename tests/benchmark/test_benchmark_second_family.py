"""A second family goes through the WHOLE contract as files, in child
processes: the family's module, its plain reference, one configuration and
the entries of ``BENCHMARK.json`` are laid into a copy of ``benchmark/`` and
NOTHING that was there is edited. Then the copy is run as the driver runs the
repo's — ``run.py`` starting ``server.py``'s child and ``check.py`` — and the
tests that glob configurations, mixes and metric files are run over it.

The family is the seam test's routed one (``scratch_moe_family.py``: the
program's ``moe`` family, no token dropped), which differs from the dense
family where a test used to assume it: more config fields than ``LlamaConfig``
has, stacks of expert matrices in its checkpoint, picks beside its logits.
The copy has its own ``.work``, so it shares no trace directory with the
rehearsals of the repo's own cells.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path[:0] = [str(REPO), str(HERE)]

from test_benchmark_family_seam import scratch_family  # noqa: E402,F401

CONFIG = json.loads((HERE / "scratch-moe-a3b-l2.json").read_text())
CELL, MIX, LIKE = "scratch-moe-chat-closed", "chat-closed", "yi6b-chat-closed"
DENSE = {"prefill_rel_rms", "decode_rel_rms", "decode_over_prefill", "served_token_gap", "served_logprob_err"}
CHOICES = {f"{part}choice_{what}" for part in ("", "served_") for what in ("disagree_share", "worst_margin")}


def lay_tree(root: Path) -> Path:
    """``root`` as a checkout: a copy of ``benchmark/``, the program beside
    it, and the second family's files ADDED — four files and three kinds of
    entry, which is all a ``model_config`` PR may bring."""
    shutil.copytree(REPO / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns(".work", "__pycache__"))
    for name in ("sentio_tpu", "prompts"):  # the system under test, not the yardstick
        (root / name).symlink_to(REPO / name, target_is_directory=True)
    before = {p.relative_to(root): p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}

    shutil.copy(HERE / "scratch_moe_family.py", root / "benchmark" / "families" / "scratch_moe.py")
    shutil.copy(HERE / "scratch_moe_reference.py", root / "benchmark" / "families" / "scratch_moe_reference.py")
    config = CONFIG
    file = f"benchmark/configs/{config['name']}.json"
    shutil.copy(HERE / "scratch-moe-a3b-l2.json", root / file)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config["name"], "source": config["source"], "file": file,
                             "reduced": config["reduced"], "why": "a second family, as files"})
    bench["workloads"].append({"name": CELL, "config": config["name"], "traffic": MIX, "chips": 1,
                               "why": "the routed family under the closed-loop mix"})
    for metric in bench["per_layer"] + bench["end_to_end"]:  # what the dense cell under this mix reports, this one reports
        if LIKE in metric.get("workloads", []):   # an end-to-end metric of every cell names none
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))

    after = {p.relative_to(root): p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    assert {k: v for k, v in after.items() if k in before} == before, "a file that was there changed"
    assert len(after) - len(before) == 3
    return root


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return lay_tree(tmp_path_factory.mktemp("second-family"))


def run_cell(tree: Path, trace: int):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored"}
    env.pop("BENCHMARK_TREE", None)
    proc = subprocess.run(
        [sys.executable, str(tree / "benchmark" / "run.py"), "--workload", CELL, "--seed", "2147483693",
         "--seconds", "3", "--trace", str(trace)],
        cwd=str(tree), env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return lines[-1], lines[:-1], proc.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_the_second_familys_cell_rehearses_in_child_processes(tree, trace):
    line, notes, stderr = run_cell(tree, trace)
    assert line["correct"] is False and line["device"] == {**line["device"], "platform": "cpu", "count": 1}
    assert line["attempted"] > 0 and line["failed"] == 0
    phase = lambda name: next(n for n in notes if n.get("phase") == name)  # noqa: E731
    assert phase("plan")["config"] == "scratch-moe-a3b-l2" and phase("plan")["rehearsal"] is True
    # /info equalled the file (every MoeConfig field, the pool's bytes), nothing
    # compiled inside the window, no error counter moved, the paged path served
    # every answer, the reference check agreed: only the platform is wrong
    assert phase("window")["problems"] == ["platform is cpu, not tpu (rehearsal)"], phase("window")
    assert phase("window")["answer_tokens_per_request"] == 256
    check = phase("reference-check")
    assert check["ok"] is True and check["rc"] == 0 and check["served_problems"] == []
    assert check["choice_pairs"] > 0 and check["served_choice_pairs"] > 0
    # the choice readings stand beside the five dense ones, each under its limit
    assert list(line)[-1] == "compared" and set(line["compared"]) == DENSE | CHOICES
    assert all(0 <= entry["value"] <= entry["limit"] for entry in line["compared"].values())
    assert all(f"compared {name} " in stderr for name in DENSE | CHOICES)
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer" if trace else "end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    if trace:  # a CPU has no peak, no kernel by that name and no memory reading: those read nothing
        assert line["device"]["busy_s"] > 0 and line["breakdown"]["device_ops"]
        assert {"tick_host_share", "stage_queue_share", "decode_rows_useful_share", "kv_pages_held_share",
                "window_out_tok_per_s"} <= set(line["metrics"]) <= want
        assert "answer_latency_p50_ms" not in want   # end to end since PR 34: the untraced run's
    else:  # a closed-loop cell's callers wait for the whole answer, and its untraced line says how long
        assert set(line["metrics"]) == want == {"tpot_p50_ms", "setup_s", "answer_latency_p50_ms"}
        assert line["metrics"]["answer_latency_p50_ms"]["value"] > 256 * line["metrics"]["tpot_p50_ms"]["value"] * 0.9
        # where the window's requests waited, stage by stage: a note of every run, read by no metric
        assert phase("stages")["requests"] == line["attempted"] and "pool_wait" in phase("stages")["mean_ms"]
    # the copy kept its work to itself
    assert (tree / "benchmark" / ".work" / "server.log").is_file()


def test_the_globbing_tests_pass_over_that_tree(tree):
    """``contract``, ``costs``, ``reference``, ``trace`` and ``traffic`` glob
    ``benchmark/configs``, ``benchmark/traffic`` and the metric folders and
    read ``BENCHMARK.json``: over the tree with the second family in it they
    pass as they are, the new configuration among their cases."""
    files = [str(HERE / f"test_benchmark_{name}.py") for name in ("contract", "costs", "reference", "trace", "traffic")]
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", *files, "-q", "-p", "no:cacheprovider", "-p", "no:randomly",
         "-p", "no:xdist", "-rA"],
        cwd=str(REPO), env={**os.environ, "BENCHMARK_TREE": str(tree), "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    out = proc.stdout
    assert proc.returncode == 0, out[-6000:] + proc.stderr[-2000:]
    passed = [ln.split()[1] for ln in out.splitlines() if ln.startswith("PASSED ")]
    assert not [ln for ln in out.splitlines() if ln.startswith(("FAILED ", "ERROR "))]
    # the second family's configuration and cell were among the cases
    for case in ("test_benchmark_costs.py::test_file_widths_are_what_the_program_config_reports[scratch-moe-a3b-l2]",
                 "test_benchmark_reference.py::test_paged_engine_agrees_with_the_reference[scratch-moe-a3b-l2]",
                 "test_benchmark_reference.py::test_the_tolerance_catches_weights_in_a_coarser_type[scratch-moe-a3b-l2]",
                 "test_benchmark_contract.py::test_configuration_entry_matches_its_file[scratch-moe-a3b-l2]",
                 f"test_benchmark_contract.py::test_cell_resolves_and_reports_enough[{CELL}]"):
        assert any(p.endswith(case) for p in passed), case


# ------------------------------------------------ the served half, by hand


def test_program_config_is_every_field_of_the_programs_config(scratch_family):
    import dataclasses

    from sentio_tpu.models.moe import MoeConfig

    fields = scratch_family.program_config(CONFIG)
    cfg = MoeConfig(**fields)
    assert dataclasses.asdict(cfg) == fields and set(fields) == {f.name for f in dataclasses.fields(MoeConfig)}
    assert cfg == scratch_family.check_config(CONFIG, 2, 4096)
    # 128 experts, 8 a token: an expert's buffer holds 16 times its even share, which is every token of a call
    assert (cfg.n_experts, cfg.experts_per_token, cfg.capacity_factor) == (128, 8, 16.0)
    assert cfg.dim == 2048 and cfg.head_dim == 128 == CONFIG["head_dim"] and cfg.mlp_dim == 768
    assert scratch_family.WIDTHS["num_local_experts"] == "n_experts"
    assert scratch_family.WIDTHS["num_experts_per_tok"] == "experts_per_token"
    assert scratch_family.REFERENCE == "scratch_moe_reference"  # beside the tests; in a tree, beside the family


def test_the_checkpoint_is_the_programs_tree_from_the_seed(scratch_family, tmp_path):
    import jax
    import numpy as np

    from sentio_tpu.models.moe import MoeConfig, init_moe
    from sentio_tpu.runtime.weights import load_model

    tiny = {**CONFIG, **CONFIG["rehearsal"]}
    scratch_family.write_checkpoint(tmp_path / "a", tiny, 2_147_483_700)
    params, cfg, _tokenizer = load_model(str(tmp_path / "a"))
    assert type(cfg) is MoeConfig and cfg == scratch_family.check_config(tiny, 2, tiny["max_position_embeddings"])
    want = jax.eval_shape(lambda key: init_moe(key, cfg), jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(want)
    for got, leaf in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(want)):
        assert got.shape == leaf.shape
        assert str(got.dtype) == ("bfloat16" if scratch_family.is_matrix(got) else "float32")
    stack = np.asarray(params["layers_1"]["moe"]["w_down"], np.float32)
    assert stack.shape == (4, 96, 64) and 0.8 < stack.std() * 96 ** 0.5 < 1.2  # fan-in of an expert's own matrix
    head = np.asarray(params["lm_head"]["kernel"], np.float32)
    assert not head[:, :261].any() and head[:, 261:].any(axis=0).all()  # no answer token is a byte or a special
    # a function of the seed alone
    again, other = scratch_family.make_params(tiny, 2_147_483_700), scratch_family.make_params(tiny, 7)
    same = lambda a, b: all(np.array_equal(x, y) for x, y in zip(  # noqa: E731
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    assert same(again, jax.device_get(params)) and not same(again, other)


def test_pool_and_costs_by_hand(scratch_family):
    m = CONFIG
    # K and V, 4 heads of 128, bf16, 2 layers: 4 KB a token; the scratch page and 32 slots x 10 pages of 128
    assert scratch_family.pool_bytes(m, m["serve_env"]) == (1 + 32 * 10) * 128 * (2 * 4 * 128 * 2 * 2) == 168_296_448
    w = scratch_family.weight_params(m)
    # wq and wo 2048x2048, wk and wv 2048x512; a router 2048x128; an expert's three matrices 2048x768
    assert w == {"attention": 2 * 2048 * 2048 + 2 * 2048 * 512, "router": 262_144,
                 "expert": 3 * 2048 * 768, "head": 151_936 * 2048}
    # one row reaches exactly its 8 experts; 32 rows reach 128 (1 - (15/16)^32) = 111.77 of 128; many rows all
    assert scratch_family.experts_read(m, 1) == pytest.approx(8.0)
    assert scratch_family.experts_read(m, 32) == pytest.approx(128 * (1 - 0.9375 ** 32)) == pytest.approx(111.77, abs=0.01)
    assert scratch_family.experts_read(m, 4096) == pytest.approx(128.0)
    cost = scratch_family.decode_substep_cost(m, rows=32, context_tokens=11 * 830)
    layer = w["attention"] + w["router"] + 128 * (1 - 0.9375 ** 32) * w["expert"]
    assert cost["bytes"] == pytest.approx(2 * (2 * layer + w["head"] + 32 * 2048) + 9130 * 4096)
    assert cost["flops"] == pytest.approx(
        2 * 32 * (2 * (w["attention"] + w["router"] + 8 * w["expert"]) + w["head"]) + 4 * 9130 * 2048 * 2)
    # the experts are the larger part: 2.1 of 2.8 GB a sub-step
    assert 2.7e9 < cost["bytes"] < 2.9e9 and 2 * 2 * 111.77 * w["expert"] > 0.7 * cost["bytes"]
    # one call of the decode kernel: one layer's K and V of the tokens held, the dense family's count
    call = scratch_family.KERNEL_COSTS["paged_attention"](m, 32, 9130)
    assert call == {"bytes": 9130 * 2 * 4 * 128 * 2, "flops": 4 * 9130 * 2048}
    step = cost["bytes"] - scratch_family.decode_substep_cost(m, 32, 0)["bytes"]
    assert step == pytest.approx(2 * call["bytes"])  # two layers' calls hold the context bytes of the step


def test_the_rooflines_read_the_second_familys_costs(scratch_family):
    """``readers.py`` asks ``load_family(obs.model)``: with this family's
    configuration as the model, both rooflines are its counts over the v5e's
    peaks, through the metric files as they are."""
    from benchmark import readers
    from benchmark.roofline import least_time_s

    rows = [[("sentio_tpu_serving_stat", {"stat": "tick_active_slots"}, 11.0)]] * 3
    step = {"count": 3, "total_ms": 60.0, "p50_ms": 20.0, "sub_steps": 48, "sub_steps_ms": 57.6,
            "kernels": {"paged_attention": {"calls": 96, "total_ms": 2.0, "p50_us": 60.0}}}
    obs = readers.Observations(
        trace={"programs": {"jit_step_n": step}}, model=CONFIG, prom_samples=rows,
        mix={"shapes": {"prompt_tokens": [658, 754]}}, device_kind="TPU v5 lite",
        server_env={"LLM_MAX_BATCH": "32", "LLM_MAX_TOKENS": "256"})
    context = 11 * (706 + 128)
    assert readers.decode_rows_and_context(obs) == (32, context)
    least = least_time_s(scratch_family.decode_substep_cost(CONFIG, 32, context), "TPU v5 lite")
    got = readers.read_metric(readers.load_metric("per_layer", "decode_step_mfu"), obs)
    assert least["bound"] == "bandwidth" and got == pytest.approx(100 * least["seconds"] * 1e3 / 1.2)
    call = least_time_s(scratch_family.KERNEL_COSTS["paged_attention"](CONFIG, 32, context), "TPU v5 lite")
    got = readers.read_metric(readers.load_metric("per_layer", "paged_attn_roofline"), obs)
    assert got == pytest.approx(100 * call["seconds"] * 1e6 / 60.0) and 0 < got < 100
