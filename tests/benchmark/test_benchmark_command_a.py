"""The configuration ``command-a-plus-ep8-l4`` and its family
(``benchmark/families/cohere2_moe.py``): the cut and the counts by hand, the
seeded tree under a tied head, the checkpoint through ``load_decoder``, and
the cell ``commanda-ep8-chat-closed-16`` rehearsed on the CPU through ``run.py``
→ ``server.py`` → ``check.py`` in a copy of ``benchmark/`` (its own ``.work``:
no trace directory shared with the other rehearsals)."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from bench_tree import REPO, load_dir

from benchmark.families import cohere2_moe as family
from benchmark.families import llama as dense
from benchmark.roofline import least_time_s

MODEL = load_dir("configs")["command-a-plus-ep8-l4"]
TINY = {**MODEL, **MODEL["rehearsal"]}
CELL = "commanda-ep8-chat-closed-16"


def test_the_cut_by_hand():
    """ISSUE 33's arithmetic: a chip's layer of 1,149.8 M parameters, the
    table 134.2 M, a pool of 16,384 B a token."""
    w = family.weight_params(MODEL)
    assert w["attention"] == 2 * 4096 * 16384 + 2 * 4096 * 1024 == 142_606_336
    assert w["expert"] == 3 * 4096 * 4096 == 50_331_648 and w["router"] == 4096 * 128
    layer = w["attention"] + w["router"] + (4 + 16) * w["expert"]
    assert layer == 1_149_763_584 and w["table"] == 32768 * 4096 == 134_217_728
    assert 2 * (4 * layer + w["table"]) == 9_466_544_128          # 9.47 GB of bf16 weights
    assert family.kv_bytes_per_token(MODEL) == 4 * 2 * 8 * 128 * 2 == 16_384
    assert family.pool_bytes(MODEL, MODEL["serve_env"]) == (1 + 32 * 10) * 128 * 16_384
    assert MODEL["published"] == {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    assert len(MODEL["assumed"]) == 5 and MODEL["num_experts_router"] == 128


def test_costs_by_hand():
    """17 rows of 834 tokens: at least 14,178 / 1,280 = 11.08 rows advance,
    which touch 16 (1 - (15/16)^11.08) = 8.17 of the 16 held experts."""
    context = 17 * 834
    n = family.rows_advancing(MODEL, context)
    assert n == pytest.approx(11.077, abs=1e-3) and n <= 17
    touched = family.experts_touched(MODEL, n)
    assert touched == pytest.approx(16 * (1 - (15 / 16) ** n)) and 8.1 < touched < 8.2
    assert family.experts_touched(MODEL, 17) == pytest.approx(10.66, abs=0.01)   # the issue's 10.7
    assert family.keys_seen(MODEL, context) == context        # no table outgrows the window here
    one = family.KERNEL_COSTS["expert_mlp"](MODEL, 32, context)
    assert one["bytes"] == pytest.approx(2 * touched * 4096 * 4096)
    assert one["flops"] == pytest.approx(2 * n * 8 * 16 / 128 * 4096 * 4096)
    assert least_time_s(one, "TPU v5 lite")["bound"] == "bandwidth"
    step = family.decode_substep_cost(MODEL, 32, context)
    w = family.weight_params(MODEL)
    layer = w["attention"] + w["router"] + (4 + touched) * w["expert"]
    assert step["bytes"] == pytest.approx(2 * (4 * layer + w["table"] + 32 * 4096) + context * 16_384)
    assert 0.0078 < least_time_s(step, "TPU v5 lite")["seconds"] < 0.0082
    # the four attention calls of a sub-step hold the context bytes the whole step counts
    attn = family.KERNEL_COSTS["paged_attention"](MODEL, 32, context)
    assert 4 * attn["bytes"] == context * 16_384 and attn["flops"] == 4 * context * 16384
    # a table that outgrows the window: a sliding layer reads a window's worth at least
    long = {**MODEL, "serve_env": {**MODEL["serve_env"], "KV_MAX_PAGES_PER_SEQ": "64"}}
    assert family.keys_seen(long, 20_000) == (3 * 4096 + 20_000) / 4


def test_depth_keeps_every_kind_of_layer():
    from sentio_tpu.models.cohere2_moe import FULL, SLIDING

    assert family.check_config(MODEL, 2, 4352).kinds == (SLIDING, FULL)
    assert family.check_config(MODEL, 4, 4352).kinds == (SLIDING, SLIDING, SLIDING, FULL)
    assert family.layer_kinds(MODEL, 8) == [SLIDING, SLIDING, SLIDING, FULL] * 2
    served = family.check_config(MODEL, 4, 200_000)
    assert dataclasses.asdict(served) == family.program_config(MODEL)
    assert (served.n_heads * served.head_dim, served.dim, served.experts_held, served.n_experts,
            served.expert_offset, served.vocab_size) == (16384, 4096, 16, 128, 0, 32768)


def test_reference_kwargs_give_the_reference_the_same_share():
    kw = family.reference_kwargs(MODEL)
    assert kw["layer_types"] == ("sliding_attention", "full_attention")      # the check's two layers
    assert (kw["experts_held"], kw["expert_offset"], kw["experts_per_token"]) == (16, 0, 8)
    assert kw["sliding_window"] == 4096 and family.CHOICES == {"experts": "experts_per_token"}
    assert max(MODEL["check"]["prompt_tokens"]) >= 4096 + 128    # a page past the published window


@pytest.mark.parametrize("seed", [0, 7, 2147483659])
def test_seeded_tree_ties_the_head_and_no_answer_holds_a_text_id(seed):
    """The head IS the embedding, so the text ids' rows are scaled, not
    zeroed: every greedy answer token lies outside the tokenizer's 261 ids
    (3 bytes of text each, no EOS), whatever the seed."""
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    tree = family.make_params(TINY, seed)
    assert "lm_head" not in tree and tree["layers_0"]["moe"]["w_gate"].shape == (4, 64, 32)
    from sentio_tpu.models.cohere2_moe import EMBED_STD, WO_SCALE, WQ_SCALE

    table = np.asarray(tree["embed_tokens"]["embedding"], np.float32)
    assert table[: dense.TEXT_IDS].std() == pytest.approx(EMBED_STD * family.TEXT_ROW_SCALE, rel=0.05)
    assert table[dense.TEXT_IDS:].std() == pytest.approx(EMBED_STD, rel=0.05)
    # the program's seeded distributions: the query projection larger, the output projection smaller
    wq, wo = (np.asarray(tree["layers_0"]["attn"][k]["kernel"], np.float32) for k in ("wq", "wo"))
    assert wq.shape == (64, 8 * 16) and wq.std() == pytest.approx(WQ_SCALE * 64 ** -0.5, rel=0.05)
    assert wo.shape == (8 * 16, 64) and wo.std() == pytest.approx(WO_SCALE * 128 ** -0.5, rel=0.05)
    again = family.make_params(TINY, seed)
    assert np.array_equal(np.asarray(again["layers_1"]["moe"]["shared"]["w_down"], np.float32),
                          np.asarray(tree["layers_1"]["moe"]["shared"]["w_down"], np.float32))
    engine = ContinuousBatchingEngine(
        model_config=family.check_config(TINY, 2, 4096), params=tree, max_slots=2, page_size=16,
        max_pages_per_seq=8)
    for res in engine.run_all(["what does the passage say?", "summarise file d00012.txt"], max_new_tokens=32):
        assert res.finish_reason == "length" and len(res.tokens) == 32
        assert min(res.tokens) >= dense.TEXT_IDS
        assert len(res.text.encode()) == 3 * 32


def test_checkpoint_goes_through_load_decoder(tmp_path):
    """``LLM_CHECKPOINT`` is the surface a user has: the family in the
    checkpoint's meta picks the config class, every field comes back, the
    tree is the serving tree and holds no second copy of the table."""
    from sentio_tpu.config import GeneratorConfig
    from sentio_tpu.models.cohere2_moe import Cohere2MoeConfig
    from sentio_tpu.runtime.weights import load_decoder

    family.write_checkpoint(tmp_path / "llm", TINY, 5)
    decoder = load_decoder(GeneratorConfig(checkpoint_path=str(tmp_path / "llm")))
    assert isinstance(decoder.model_config, Cohere2MoeConfig)
    assert json.loads(json.dumps(dataclasses.asdict(decoder.model_config))) == family.program_config(TINY)
    assert "wq_t" in decoder.params["layers_0"]["attn"] and "lm_head" not in decoder.params
    assert decoder.params["layers_0"]["attn"]["wq_t"]["kernel"].shape == (8 * 16, 64)


# ------------------------------------------------------------ the rehearsal


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("command-a")
    shutil.copytree(REPO / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for name in ("sentio_tpu", "prompts"):
        (root / name).symlink_to(REPO / name, target_is_directory=True)
    return root


def test_the_cell_rehearses_with_its_choices_and_its_metrics(tree):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored"}
    env.pop("BENCHMARK_TREE", None)
    proc = subprocess.run(
        [sys.executable, str(tree / "benchmark" / "run.py"), "--workload", CELL, "--seed", "2147483659",
         "--seconds", "3", "--trace", "1"], cwd=str(tree), env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    line, notes = lines[-1], lines[:-1]
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] > 0
    window = next(n for n in notes if n.get("phase") == "window")
    # /info equalled the file field for field, nothing compiled in the window, the reference agreed
    assert window["problems"] == ["platform is cpu, not tpu (rehearsal)"], window
    assert window["answer_tokens_per_request"] == 256
    dense_keys = {"prefill_rel_rms", "decode_rel_rms", "decode_over_prefill", "served_token_gap", "served_logprob_err"}
    choices = {f"{part}choice_{what}" for part in ("", "served_") for what in ("disagree_share", "worst_margin")}
    assert set(line["compared"]) == dense_keys | choices
    assert all(0 <= entry["value"] <= entry["limit"] for entry in line["compared"].values())
    check = next(n for n in notes if n.get("phase") == "reference-check")
    # the served answers' picks came from ``run_all`` itself, all but the radix-served head
    assert check["served_choices_from_engine_share"] > 0.5 and check["choice_pairs"] > 0
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(line["metrics"]) <= want
    assert {"moe_pairs_held_share", "moe_experts_touched_share", "kv_pages_held_share",
            "decode_rows_useful_share", "tick_host_share"} <= set(line["metrics"])
    # 4 of 16 experts held: a quarter of the pairs, within what 4 experts' luck allows
    assert 10.0 < line["metrics"]["moe_pairs_held_share"]["value"] < 45.0
    assert 0.0 < line["metrics"]["moe_experts_touched_share"]["value"] <= 100.0
