"""The configuration ``mellum2-12b-a2.5b-l12`` and its family
(``benchmark/families/mellum.py``): the arithmetic of the file by hand, every
published value against the catalog's, the costs under a window that bites, the
seeded tree under an untied head, the checkpoint through ``load_decoder``, and
the cell ``mellum2-rag-long`` rehearsed on the CPU through ``run.py`` →
``server.py`` → ``check.py`` in a copy of ``benchmark/`` (its own ``.work``, as
the other rehearsals have). Nothing here counts configurations, cells or
metrics, or asks for a last entry: the next cell does not break it."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest
from bench_tree import BENCH, REPO, load_dir

from benchmark.families import llama as dense
from benchmark.families import mellum as family
from benchmark.roofline import least_time_s

MODEL = load_dir("configs")["mellum2-12b-a2.5b-l12"]
TINY = {**MODEL, **MODEL["rehearsal"]}
MIX = load_dir("traffic")["rag-long"]
CELL = "mellum2-rag-long"
CATALOG_SOURCE = "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json"
PERIOD = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]


def test_the_arithmetic_by_hand():
    """ISSUE 51's arithmetic: a layer 417,747,456 parameters, the stage
    5,465,956,608 = 10.93 GB, 24,576 B of K and V a token, the pool to the byte."""
    w = family.weight_params(MODEL)
    assert w["attention"] == 2 * 2304 * 4096 + 2 * 2304 * 512 == 21_233_664
    assert w["router"] == 2304 * 64 == 147_456 and w["expert"] == 3 * 2304 * 896 == 6_193_152
    assert w["norms"] == 4_608 and w["table"] == 98_304 * 2304 == 226_492_416
    layer = w["attention"] + w["router"] + 64 * w["expert"] + w["norms"]
    assert layer == 417_747_456 and 64 * w["expert"] == 396_361_728
    assert family.model_weights(MODEL) == 12 * layer + 2 * w["table"] + 2304 == 5_465_956_608
    assert 2 * family.model_weights(MODEL) == 10_931_913_216                      # 10.93 GB of bf16
    assert family.model_weights({**MODEL, "num_hidden_layers": 28}) == 12_149_915_904  # the whole model: 24.3 GB
    assert family.kv_bytes_per_token(MODEL) == 12 * 2 * 4 * 128 * 2 == 24_576
    env = {**MODEL["serve_env"], **MIX["serve_env"]}
    assert family.pool_bytes(MODEL, env) == 321 * 128 * 24_576 == 1_009_778_688
    # the configuration states the slots and pages the mix runs, and its text the same numbers
    assert {k: MODEL["serve_env"][k] for k in ("LLM_MAX_BATCH", "KV_MAX_PAGES_PER_SEQ", "KV_PAGE_SIZE")} \
        == {k: MIX["serve_env"][k] for k in ("LLM_MAX_BATCH", "KV_MAX_PAGES_PER_SEQ", "KV_PAGE_SIZE")}
    for figure in ("21,233,664", "6,193,152", "417,747,456", "5,465,956,608", "10,931,913,216", "12,149,915,904",
                   "1,009,778,688"):
        assert figure in MODEL["reduced_why"], figure


def test_every_published_value_is_the_catalogs_and_the_cut_is_depth_alone():
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                               "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                               "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}}
    assert {k: MODEL[k] for k in published} == published
    assert MODEL["source"] == CATALOG_SOURCE and MODEL["family"] == "mellum" and MODEL["chips"] == 1
    assert MODEL["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types"]
    assert MODEL["published"] == {"num_hidden_layers": 28, "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28}
    # three whole periods, all 64 experts, the whole vocabulary
    assert MODEL["num_hidden_layers"] == 12 and MODEL["layer_types"] == PERIOD * 3
    assert MODEL["mlp_layer_types"] == ["sparse"] * 12
    entry = next(c for c in BENCH["configs"] if c["name"] == MODEL["name"])
    assert entry["source"] == CATALOG_SOURCE and entry["reduced"] == MODEL["reduced"] and len(entry["why"]) <= 200
    assert len(MODEL["assumed"]) >= 7 and "FIRST of three pipeline stages" in MODEL["deployment"]
    assert MODEL["trace"] == {"substep_kernel": "^paged_attention",
                              "kernels": {"paged_attention": "^paged_attention", "expert_mlp": "^gmm"}}
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (MODEL["name"], "rag-long", 1)
    assert len(cell["why"]) <= 200 and [w["config"] for w in BENCH["workloads"]].count(MODEL["name"]) == 1
    # the cell STANDS in the lists of the dense control of its mix (but the one a test holds to two cells) and in
    # the two lists of the routed cells that read something here; with every expert held the pairs' share reads
    # 100 whatever happens, so it is not on that list
    mine = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"] if CELL in m.get("workloads", [])}
    control = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]
               if "mistral7b-rag-long" in m.get("workloads", [])}
    assert control - {"prefill_turn_wait_share"} <= mine
    assert mine - control <= {"expert_mlp_roofline", "moe_experts_touched_share"}
    assert "moe_experts_touched_share" in mine and "moe_pairs_held_share" not in mine


def test_depth_keeps_both_kinds_and_the_program_holds_every_field():
    from sentio_tpu.models.mellum import FULL, SLIDING, MellumConfig

    assert family.check_config(MODEL, 2, 4352).kinds == (SLIDING, FULL)
    assert family.check_config(MODEL, 4, 4352).kinds == tuple(PERIOD)
    assert family.layer_kinds(MODEL, 8) == PERIOD * 2
    served = family.check_config(MODEL, 12, 131_072)
    assert isinstance(served, MellumConfig) and dataclasses.asdict(served) == family.program_config(MODEL)
    assert (served.n_heads * served.head_dim, served.dim, served.mlp_dim, served.experts_held, served.n_experts,
            served.experts_per_token, served.vocab_size) == (4096, 2304, 896, 64, 64, 8, 98304)
    assert [served.window(i) for i in range(12)] == [1024, 1024, 1024, None] * 3
    assert (served.rope_factor, served.rope_original_max, served.rope_beta_fast, served.rope_beta_slow,
            served.rope_attention_factor, served.rope_theta) == (16.0, 8192, 32.0, 1.0, 1.2772588722239782, 5e5)
    assert TINY["check"]["layers"] == 2 and family.check_config(TINY, 2, 512).kinds == (SLIDING, FULL)
    # what the layer has no switch for is held by the family
    for wrong in ({"hidden_act": "gelu"}, {"attention_bias": True}, {"mlp_layer_types": ["dense"] * 12}):
        with pytest.raises(AssertionError):
            family.program_config({**MODEL, **wrong})


def test_reference_kwargs_and_the_checks_prompts():
    kw = family.reference_kwargs(MODEL)
    assert kw["layer_types"] == ("sliding_attention", "full_attention")         # the check's two layers
    assert (kw["experts_held"], kw["expert_offset"], kw["experts_per_token"], kw["norm_topk_prob"]) == (64, 0, 8, True)
    assert (kw["sliding_window"], kw["rope_factor"], kw["rope_attention_factor"]) == (1024, 16.0, 1.2772588722239782)
    assert family.CHOICES == {"experts": "experts_per_token"}
    source = (REPO / "benchmark" / "mellum_reference.py").read_text()
    assert "sentio_tpu" not in source.split('"""')[2] and 'default_matmul_precision("highest")' in source
    check = MODEL["check"]
    # sliding layers drop keys four windows deep, and the served part is chunked over a cached head
    assert max(check["prompt_tokens"]) >= 4 * MODEL["sliding_window"] + 128 and check["layers"] == 2
    assert check["served"]["prefill_chunk"] == 128 and check["served"]["shared_head_chars"] >= 128
    assert "layers_why" in check and "tolerances_why" in check


@pytest.mark.parametrize("seed", [0, 2147483659])
def test_seeded_answers_neither_collapse_nor_end_early(seed):
    """An untied head whose columns for the text ids are a quarter as large, so
    no greedy answer holds one (3 bytes a token, no EOS), whatever the seed; the matrices
    follow the program's seeded distributions; and an answer does not collapse
    to one token."""
    from sentio_tpu.models.mellum import WO_SCALE, WQ_SCALE
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    tree = family.make_params(TINY, seed)
    head = np.asarray(tree["lm_head"]["kernel"], np.float32)
    assert head.shape == (64, 8192)
    assert head[:, : dense.TEXT_IDS].std() == pytest.approx(family.TEXT_COL_SCALE * 64 ** -0.5, rel=0.05)
    assert head[:, dense.TEXT_IDS:].std() == pytest.approx(64 ** -0.5, rel=0.05)
    assert np.asarray(tree["embed_tokens"]["embedding"], np.float32).std() == pytest.approx(0.02, rel=0.05)
    moe = tree["layers_0"]["moe"]
    assert moe["w_gate"].shape == moe["w_up"].shape == (16, 64, 32) and moe["w_down"].shape == (16, 32, 64)
    assert moe["router"]["kernel"].shape == (64, 16) and "shared" not in moe
    wq, wo = (np.asarray(tree["layers_0"]["attn"][k]["kernel"], np.float32) for k in ("wq", "wo"))
    assert wq.shape == (64, 8 * 16) and wq.std() == pytest.approx(WQ_SCALE * 64 ** -0.5, rel=0.05)
    assert wo.shape == (8 * 16, 64) and wo.std() == pytest.approx(WO_SCALE * 128 ** -0.5, rel=0.05)
    again = family.make_params(TINY, seed)
    assert np.array_equal(np.asarray(again["layers_1"]["moe"]["w_down"], np.float32),
                          np.asarray(tree["layers_1"]["moe"]["w_down"], np.float32))
    engine = ContinuousBatchingEngine(
        model_config=family.check_config(TINY, 2, 4096), params=tree, max_slots=2, page_size=16,
        max_pages_per_seq=8)
    for res in engine.run_all(["what does the passage say?", "summarise file d00012.txt"], max_new_tokens=32):
        assert res.finish_reason == "length" and len(res.tokens) == 32
        assert min(res.tokens) >= dense.TEXT_IDS and len(res.text.encode()) == 3 * 32
        assert len(set(res.tokens)) >= 12, res.tokens


def test_checkpoint_goes_through_load_decoder(tmp_path):
    """``LLM_CHECKPOINT`` is the surface a user has: the family in the
    checkpoint's meta picks the config class, every field comes back, the tree
    is the serving tree with a head of its own."""
    from sentio_tpu.config import GeneratorConfig
    from sentio_tpu.models.mellum import MellumConfig
    from sentio_tpu.runtime.weights import load_decoder

    family.write_checkpoint(tmp_path / "llm", TINY, 5)
    decoder = load_decoder(GeneratorConfig(checkpoint_path=str(tmp_path / "llm")))
    assert isinstance(decoder.model_config, MellumConfig)
    assert json.loads(json.dumps(dataclasses.asdict(decoder.model_config))) == family.program_config(TINY)
    attn = decoder.params["layers_0"]["attn"]
    assert set(attn) == {"wq_t", "wk_t", "wv_t", "wo"} and attn["wq_t"]["kernel"].shape == (8 * 16, 64)
    assert decoder.params["lm_head"]["kernel"].shape == (64, 8192)


def test_costs_by_hand():
    """Three rows of 4,763 tokens: at least 14,289 / 5,120 = 2.79 rows advance,
    which touch 64 (1 - (7/8)^2.79) = 19.9 of the 64 experts (a sub-step of 4.06 GB: 4.96 ms); a sliding layer
    sees at least a fifth of the context (1,024 of a table's 5,120)."""
    context = 3 * 4763
    n = family.rows_advancing(MODEL, context)
    assert n == pytest.approx(2.791, abs=1e-3) and n <= 8
    touched = family.experts_touched(MODEL, n)
    assert touched == pytest.approx(64 * (1 - (7 / 8) ** n)) and 19.8 < touched < 20.0
    assert family.experts_touched(MODEL, 3) == pytest.approx(21.125)
    sliding = family.sliding_keys(MODEL, context)
    assert sliding == pytest.approx(context * 1024 / 5120) and sliding <= 3 * 1024
    assert family.keys_seen(MODEL, context) == pytest.approx((9 * sliding + 3 * context) / 12)
    w = family.weight_params(MODEL)
    step = family.decode_substep_cost(MODEL, 8, context)
    layer = w["attention"] + w["router"] + touched * w["expert"]
    assert step["bytes"] == pytest.approx(2 * (12 * layer + w["table"] + 8 * 2304)
                                          + family.keys_seen(MODEL, context) * 24_576)
    row = 12 * (w["attention"] + w["router"] + 8 * w["expert"]) + w["table"]
    assert step["flops"] == pytest.approx(2 * n * row + 4 * family.keys_seen(MODEL, context) * 4096 * 12)
    assert 0.0048 < least_time_s(step, "TPU v5 lite")["seconds"] < 0.0051   # 4.06 GB at 819 GB/s
    assert set(family.KERNEL_COSTS) == set(MODEL["trace"]["kernels"]) == {"paged_attention", "expert_mlp"}
    # ONE attention call is a SLIDING layer's: under the mean over the kinds, which a 9-page walk would be held to
    walk = family.KERNEL_COSTS["paged_attention"](MODEL, 8, context)
    assert walk["bytes"] == pytest.approx(sliding * 2048) and walk["flops"] == pytest.approx(4 * sliding * 4096)
    mean = family.keys_seen(MODEL, context) * 2048
    assert walk["bytes"] < 0.51 * mean and 12 * walk["bytes"] < family.keys_seen(MODEL, context) * 24_576
    # and under what the walk reads: 9 pages of 128 a row that advances
    assert walk["bytes"] < 3 * 9 * 128 * 2048
    one = family.KERNEL_COSTS["expert_mlp"](MODEL, 8, context)
    assert one["bytes"] == pytest.approx(2 * touched * 2304 * 896)
    assert one["flops"] == pytest.approx(2 * n * 8 * 2304 * 896)
    assert least_time_s(one, "TPU v5 lite")["bound"] == "bandwidth"
    # a table the window covers: a sliding layer sees all the context
    short = {**MODEL, "serve_env": {**MODEL["serve_env"], "KV_MAX_PAGES_PER_SEQ": "8"}}
    assert family.sliding_keys(short, 2000) == family.keys_seen(short, 2000) == 2000


# ------------------------------------------------------------ the rehearsal


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("mellum")
    shutil.copytree(REPO / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for name in ("sentio_tpu", "prompts"):
        (root / name).symlink_to(REPO / name, target_is_directory=True)
    return root


def test_the_cell_rehearses_with_its_window_biting(tree):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "JAX_ENABLE_COMPILATION_CACHE": "false", "BENCH_RUN": "ignored"}
    env.pop("BENCHMARK_TREE", None)
    proc = subprocess.run(
        [sys.executable, str(tree / "benchmark" / "run.py"), "--workload", CELL, "--seed", "2147483659",
         "--seconds", "3", "--trace", "1"], cwd=str(tree), env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    line, notes = lines[-1], lines[:-1]
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] > 0
    window = next(n for n in notes if n.get("phase") == "window")
    # /info equalled the file field for field (the pool's bytes among them), nothing compiled in the window,
    # the reference agreed
    assert window["problems"] == ["platform is cpu, not tpu (rehearsal)"], window
    assert window["answer_tokens_per_request"] == 96
    dense_keys = {"prefill_rel_rms", "decode_rel_rms", "decode_over_prefill", "served_token_gap", "served_logprob_err"}
    choices = {f"{part}choice_{what}" for part in ("", "served_") for what in ("disagree_share", "worst_margin")}
    assert set(line["compared"]) == dense_keys | choices
    assert all(0 <= entry["value"] <= entry["limit"] for entry in line["compared"].values())
    check = next(n for n in notes if n.get("phase") == "reference-check")
    assert check["served_choices_from_engine_share"] > 0.5 and check["choice_pairs"] > 0
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(line["metrics"]) <= want
    assert {"moe_experts_touched_share", "kv_pages_held_share", "decode_rows_useful_share", "tick_host_share",
            "device_decode_share", "device_prefill_share", "stage_prefill_share", "answer_decode_share"} \
        <= set(line["metrics"])
    assert 0.0 < line["metrics"]["moe_experts_touched_share"]["value"] <= 100.0
    # THE WINDOW BITES. A row that advances holds 37 to 38 pages; the full layer's walk takes them all, the sliding
    # layer's (a window of 32 tokens in pages of 128) one or two, so the mean over the two layers is near 19.5 where
    # no window gives 37.5. ``held`` counts that mean for a row that advances and one block for any other, so from
    # the two shares of the same ticks: held / tabled = (u x walk + (1 - u)) / 40, u the useful share of row-steps.
    # What lies between the two walks is what the engine books as ``behind_window``
    useful = line["metrics"]["decode_rows_useful_share"]["value"] / 100.0
    held = line["metrics"]["kv_pages_held_share"]["value"] / 100.0
    pages = int(MIX["serve_env"]["KV_MAX_PAGES_PER_SEQ"])
    walk = (held * pages - (1.0 - useful)) / useful
    assert 17.0 < walk < 23.0, (walk, useful, held)
