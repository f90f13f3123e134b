"""The configuration ``lfm2-24b-a2b-l10`` and its family
(``benchmark/families/lfm2_moe.py``): the cut and the counts by hand, the
pool's bytes with the convolution state per slot and per page, the seeded tree
under a tied head with a non-zero expert bias, the checkpoint through
``load_decoder``, the costs, and the cell ``lfm2moe-chat-closed-16`` rehearsed
on the CPU through ``run.py`` → ``server.py`` → ``check.py`` in a copy of
``benchmark/`` (its own ``.work``, as the other rehearsals have)."""

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
from benchmark.families import lfm2_moe as family
from benchmark.roofline import least_time_s

MODEL = load_dir("configs")["lfm2-24b-a2b-l10"]
TINY = {**MODEL, **MODEL["rehearsal"]}
MIX = load_dir("traffic")["chat-closed-16"]
CELL = "lfm2moe-chat-closed-16"
CATALOG_SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
PERIOD = ["conv", "conv", "full_attention", "conv"]


def test_the_cut_by_hand():
    """ISSUE 42's arithmetic: an expert 9.44 M, a routed layer's experts 1.208
    GB, the two operators, 10.53 GB of weights; 4,096 B of K and V a token and
    64 KB of state a page; the pool to the byte."""
    w = family.weight_params(MODEL)
    assert w["expert"] == 3 * 2048 * 1536 == 9_437_184 and 64 * w["expert"] == 603_979_776
    assert 2 * 64 * w["expert"] == 1_207_959_552                          # 1.208 GB a routed layer
    assert w["router"] == 131_072 + 64
    assert w["conv"] == 12_582_912 + 4_194_304 + 6_144 == 16_783_360
    assert w["attention"] == 4_194_304 + 2 * 1_048_576 + 4_194_304 == 10_485_760
    assert w["dense_mlp"] == 72_351_744 and w["table"] == 65536 * 2048 == 134_217_728
    assert family.layer_counts(MODEL) == (2, 8)
    weights = family.model_weights(MODEL)
    assert weights == 8 * w["conv"] + 2 * w["attention"] + 2 * w["dense_mlp"] + 8 * (w["router"] + 64 * w["expert"]) + w["table"]
    assert 10.52e9 < 2 * weights < 10.54e9                                 # 10.53 GB of bf16
    whole = {**MODEL, "num_hidden_layers": 40, "layer_types": MODEL["published"]["layer_types"]}
    assert 47.5e9 < 2 * family.model_weights(whole) < 47.9e9              # the published model: 47.7 GB
    assert family.kv_bytes_per_token(MODEL) == 4096 and family.conv_state_bytes(MODEL) == 65536
    env = {**MODEL["serve_env"], **MIX["serve_env"]}
    assert family.pool_bytes(MODEL, env) == 161 * 128 * 4096 + 161 * 65536 + 16 * 65536 == 96_010_240
    assert MODEL["published"] == {"num_hidden_layers": 40, "layer_types": PERIOD * 10}
    assert MODEL["reduced"] == ["num_hidden_layers", "layer_types"]
    # the floors of a model_config PR: whole periods, >= 4 layers behind the dense ones, every expert, the vocabulary
    assert MODEL["layer_types"] == (PERIOD * 10)[:10] and MODEL["layer_types"][2:] == ["full_attention", "conv", "conv", "conv"] * 2
    assert MODEL["num_hidden_layers"] - MODEL["num_dense_layers"] == 8 >= 4
    assert MODEL["num_experts"] == 64 and MODEL["vocab_size"] == 65536


def test_every_published_value_is_the_catalogs_and_no_width_is_cut():
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 64, "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    assert {k: MODEL[k] for k in published} == published
    assert MODEL["source"] == CATALOG_SOURCE and MODEL["family"] == "lfm2_moe" and MODEL["chips"] == 1
    entry = next(c for c in BENCH["configs"] if c["name"] == MODEL["name"])
    assert entry["source"] == CATALOG_SOURCE and entry["reduced"] == MODEL["reduced"]
    widths = ("hidden_size", "intermediate_size", "_dim", "_rank", "per_tok", "heads", "L_cache")
    assert not [k for k in MODEL["reduced"] if k.endswith(widths)]
    assert len(MODEL["assumed"]) == 5 and "4 pipeline stages" in MODEL["deployment"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (MODEL["name"], "chat-closed-16", 1)
    assert len(cell["why"]) <= 200
    lists = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"] if CELL in m.get("workloads", [])}
    commanda = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]
                if "commanda-ep8-chat-closed-16" in m.get("workloads", [])}
    assert lists == commanda - {"paged_attn_roofline", "moe_pairs_held_share"}
    # no per-layer metric of its own: ``test_benchmark_device_time.py`` holds PR 40's six to the END of
    # ``per_layer``, and a new entry may only be appended (PERF.md section 7, Q17)
    assert [m["name"] for m in BENCH["per_layer"]][-1] == "answer_decode_share"


def test_depth_keeps_every_kind_of_block():
    """``check_config`` at 4 layers: conv and dense twice, attention and
    routed, conv and routed; at the file's depth it is what ``/info`` reports."""
    cfg = family.check_config(MODEL, 4, 1536)
    assert cfg.kinds == ["conv", "conv", "full_attention", "conv"] and cfg.n_routed_layers == 2
    assert [cfg.routed_layer(i) for i in range(4)] == [False, False, True, True] and MODEL["check"]["layers"] == 4
    # fewer layers than hold every kind from the start (the yardstick's CPU tests hold a rehearsal to two):
    # a convolution under a dense layer, attention under a routed one
    two = family.check_config(TINY, 2, 512)
    assert two.kinds == ["conv", "full_attention"] and (two.num_dense_layers, two.n_routed_layers) == (1, 1)
    assert TINY["check"]["layers"] == 2 and len(TINY["check"]["served"]["prompt_chars"]) == 3
    three = family.check_config(MODEL, 3, 512)
    assert three.kinds == ["conv", "full_attention", "conv"] and three.num_dense_layers == 1
    served = family.check_config(MODEL, 10, 128_000)
    assert dataclasses.asdict(served) == family.program_config(MODEL)
    assert (served.dim, served.head_dim, served.n_heads, served.n_kv_heads, served.mlp_dim, served.moe_mlp_dim,
            served.n_experts, served.experts_held, served.experts_per_token, served.conv_l_cache, served.vocab_size) \
        == (2048, 64, 32, 8, 11776, 1536, 64, 64, 4, 3, 65536)
    assert served.attn_layers == (2, 6) and len(served.conv_layers) == 8 and served.norm_topk_eps == 1e-6


def test_reference_kwargs_and_the_checks_prompts():
    kw = family.reference_kwargs(MODEL)
    assert (kw["experts_held"], kw["expert_offset"], kw["experts_per_token"], kw["norm_topk_eps"]) == (64, 0, 4, 1e-6)
    assert family.CHOICES == {"experts": "experts_per_token"} and kw["norm_topk_prob"] and kw["rope_theta"] == 1e6
    source = (REPO / "benchmark" / "lfm2_moe_reference.py").read_text()
    assert "sentio_tpu" not in source and 'default_matmul_precision("highest")' in source
    check, served = MODEL["check"], MODEL["check"]["served"]
    assert check["prompt_tokens"] == [1100, 131] and check["decode_steps"] == 16
    assert served["new_tokens"] == 96 and served["steps_per_tick"] == 16 and served["shared_head_chars"] == 256
    # every request is chunked (check.py asks it) and every later one starts behind the cached head:
    # a restored page tail and a carried state are both in the served comparison
    page, chunk = check["page_size"], served["prefill_chunk"]
    hit = (served["shared_head_chars"] + 1) // page * page
    assert hit == 256 and served["prompt_chars"][0] + 1 > chunk
    assert all(n + 1 - hit > chunk for n in served["prompt_chars"][1:])


@pytest.mark.parametrize("seed", [0, 7, 2147483659])
def test_seeded_tree_ties_the_head_and_answers_do_not_collapse(seed):
    """The head IS the embedding; the text ids' rows are a quarter as large, so
    no greedy answer holds one (3 bytes a token, no EOS), whatever the seed;
    the matrices follow the program's seeded distributions; the taps and the
    bias are float32; and an answer does not collapse to one token."""
    from sentio_tpu.models.lfm2_moe import EMBED_STD, EXPERT_BIAS_STD, WO_SCALE, WQ_SCALE
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    tree = family.make_params(TINY, seed)
    table = np.asarray(tree["embed_tokens"]["embedding"], np.float32)
    assert "lm_head" not in tree and table.shape == (8192, 64)
    assert table[: dense.TEXT_IDS].std() == pytest.approx(EMBED_STD * family.TEXT_ROW_SCALE, rel=0.06)
    assert table[dense.TEXT_IDS:].std() == pytest.approx(EMBED_STD, rel=0.02)
    assert set(tree["layers_0"]) == {"op_norm", "ffn_norm", "conv", "mlp"}
    assert set(tree["layers_2"]) == {"op_norm", "ffn_norm", "attn", "moe"} and "conv" in tree["layers_3"]
    moe = tree["layers_2"]["moe"]
    assert moe["w_gate"].shape == (8, 64, 32) and moe["bias"].shape == (8,) and moe["bias"].dtype == np.float32
    conv = tree["layers_0"]["conv"]
    assert conv["kernel"].shape == (64, 3) and conv["kernel"].dtype == np.float32 and conv["w_in"]["kernel"].shape == (64, 192)
    std = lambda a: float(np.asarray(a, np.float32).std())  # noqa: E731
    assert std(tree["layers_2"]["attn"]["wq"]["kernel"]) == pytest.approx(WQ_SCALE * 64 ** -0.5, rel=0.06)
    assert std(conv["w_out"]["kernel"]) == pytest.approx(WO_SCALE * 64 ** -0.5, rel=0.06)
    assert std(conv["kernel"]) == pytest.approx(3 ** -0.5, rel=0.15)
    assert 0 < np.abs(moe["bias"]).max() < 5 * EXPERT_BIAS_STD
    again = family.make_params(TINY, seed)
    assert np.array_equal(np.asarray(again["layers_5"]["moe"]["w_down"], np.float32),
                          np.asarray(tree["layers_5"]["moe"]["w_down"], np.float32))
    engine = ContinuousBatchingEngine(
        model_config=family.check_config(TINY, 6, 4096), params=tree, max_slots=2, page_size=16,
        max_pages_per_seq=8)
    for res in engine.run_all(["what does the passage say?", "summarise file d00012.txt"], max_new_tokens=32):
        assert res.finish_reason == "length" and len(res.tokens) == 32
        assert min(res.tokens) >= dense.TEXT_IDS and len(res.text.encode()) == 3 * 32
        assert len(set(res.tokens)) >= 12, res.tokens


@pytest.mark.parametrize("seed", [1, 2147483659])
def test_sixteen_rows_touch_what_even_routing_gives_and_the_bias_changes_a_stated_share(seed):
    """The published router geometry (64 experts, 4 a token) at hidden 256:
    over greedy answers of 16 rows a decode step touches about 64 (1 -
    (60/64)^16) = 41 of 64 experts a layer (33 to 45), and ``top4(s + b)``
    differs from ``top4(s)`` for 30 to 65 % of tokens."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.lfm2_moe import lfm2_forward
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    model = {**TINY, "hidden_size": 256, "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 4,
             "layer_types": PERIOD}
    cfg, tree = family.check_config(model, 4, 4096), family.make_params(model, seed)
    engine = ContinuousBatchingEngine(model_config=cfg, params=tree, max_slots=16, page_size=16, max_pages_per_seq=8)
    prompts = [f"question {i}: " + "".join(chr(97 + (i * 7 + j * 3) % 26) for j in range(40)) for i in range(16)]
    results = engine.run_all(prompts, max_new_tokens=48)
    stats = engine.stats()
    touched = 64 * stats["moe_experts_touched"] / stats["moe_experts_held"]
    assert 33 < touched < 45, touched
    assert family.experts_touched(model, 16) == pytest.approx(41.2, abs=0.1)
    assert all(len(set(r.tokens)) >= 16 for r in results), [len(set(r.tokens)) for r in results]
    # the share of tokens whose picks the bias changed, read off the forward's own scores
    ids = jnp.asarray([engine.tokenizer.encode(p, add_bos=True)[:48] for p in prompts])
    picks = np.asarray(lfm2_forward(jax.device_put(tree), cfg, ids)[2]["experts"])
    unbiased = jax.tree.map(lambda a: a, tree)
    for i in (2, 3):
        unbiased[f"layers_{i}"] = {**tree[f"layers_{i}"], "moe": {**tree[f"layers_{i}"]["moe"],
                                                                 "bias": np.zeros(64, np.float32)}}
    plain = np.asarray(lfm2_forward(jax.device_put(unbiased), cfg, ids)[2]["experts"])
    changed = (np.sort(picks[0], -1) != np.sort(plain[0], -1)).any(-1).mean()    # the first routed layer: same input
    assert 0.30 < changed < 0.65, changed


def test_checkpoint_goes_through_load_decoder(tmp_path):
    """``LLM_CHECKPOINT`` is the surface a user has: the family in the
    checkpoint's meta picks the config class and every field comes back."""
    from sentio_tpu.config import GeneratorConfig
    from sentio_tpu.models.lfm2_moe import Lfm2MoeConfig
    from sentio_tpu.runtime.weights import load_decoder

    family.write_checkpoint(tmp_path / "llm", TINY, 5)
    decoder = load_decoder(GeneratorConfig(checkpoint_path=str(tmp_path / "llm")))
    assert isinstance(decoder.model_config, Lfm2MoeConfig)
    assert json.loads(json.dumps(dataclasses.asdict(decoder.model_config))) == family.program_config(TINY)
    assert decoder.model_config.kinds == TINY["layer_types"]
    attn = decoder.params["layers_2"]["attn"]
    # the serving tree: q, k and v [out, in] (``models/llama.py::serving_layout``)
    assert set(attn) == {"wq_t", "wk_t", "wv_t", "wo", "q_norm", "k_norm"}
    assert attn["wq_t"]["kernel"].shape == (64, 64) and attn["wk_t"]["kernel"].shape == (32, 64)
    assert "lm_head" not in decoder.params and decoder.params["layers_0"]["conv"]["kernel"].shape == (64, 3)


def test_costs_by_hand():
    """16 rows of 834 tokens (the mix's mean prompt and half an answer): the
    rows that advance are bounded from below by 13,344 / 1,280 = 10.4, which
    touch 64 (1 - (60/64)^10.4) = 31.3 experts a layer; a sub-step then reads
    5.6 GB and ONE gmm call 31.3 matrices of 6.29 MB."""
    context = 16 * 834
    n = family.rows_advancing(MODEL, context)
    assert n == pytest.approx(10.425) and n <= 16
    touched = family.experts_touched(MODEL, n)
    assert touched == pytest.approx(64 * (1 - (60 / 64) ** n)) and 31 < touched < 32
    one = family.KERNEL_COSTS["expert_mlp"](MODEL, 16, context)
    assert one["bytes"] == pytest.approx(2 * touched * 2048 * 1536) and 2 * 2048 * 1536 == 6_291_456
    assert one["flops"] == pytest.approx(2 * n * 4 * 2048 * 1536)
    assert set(family.KERNEL_COSTS) == set(MODEL["trace"]["kernels"]) == {"expert_mlp"}
    assert MODEL["trace"]["calls_per_substep"] == 3 * 8 and MODEL["trace"]["substep_kernel"] == "^gmm"
    step = family.decode_substep_cost(MODEL, 16, context)
    w = family.weight_params(MODEL)
    weights = (8 * w["conv"] + 2 * w["attention"] + 2 * w["dense_mlp"]
               + 8 * (w["router"] + touched * w["expert"]) + w["table"])
    assert step["bytes"] == pytest.approx(2 * (weights + 16 * 2048) + context * 4096 + 2 * n * 65536)
    # the 24 calls of a sub-step hold the expert bytes the whole step counts
    assert 24 * one["bytes"] == pytest.approx(2 * 8 * touched * w["expert"])
    assert step["bytes"] > 24 * one["bytes"] and step["flops"] > 24 * one["flops"]
    assert 0.0060 < least_time_s(step, "TPU v5 lite")["seconds"] < 0.0075
    # every row advancing (the issue's table): 41.2 experts a layer, 7.1 GB, 8.7 ms
    full = family.decode_substep_cost(MODEL, 16, 16 * 1280)
    assert 7.0e9 < full["bytes"] < 7.3e9 and 0.0085 < least_time_s(full, "TPU v5 lite")["seconds"] < 0.0090
    # no share can read over 100 at the rehearsal's sizes either: the lower bound on rows holds there too
    tiny_step = family.decode_substep_cost(TINY, 4, 4 * 834)
    assert tiny_step["bytes"] < family.decode_substep_cost(TINY, 4, 4 * 1280)["bytes"]


# ------------------------------------------------------------ the rehearsal


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("lfm2-moe")
    shutil.copytree(REPO / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for name in ("sentio_tpu", "prompts"):
        (root / name).symlink_to(REPO / name, target_is_directory=True)
    return root


def test_the_cell_rehearses_with_its_state_counters_and_its_metrics(tree):
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
    # /info equalled the file field for field (the pool's bytes, state and tails among them),
    # nothing compiled in the window, the reference agreed
    assert window["problems"] == ["platform is cpu, not tpu (rehearsal)"], window
    assert window["answer_tokens_per_request"] == 256
    dense_keys = {"prefill_rel_rms", "decode_rel_rms", "decode_over_prefill", "served_token_gap", "served_logprob_err"}
    choices = {f"{part}choice_{what}" for part in ("", "served_") for what in ("disagree_share", "worst_margin")}
    assert set(line["compared"]) == dense_keys | choices
    assert all(0 <= entry["value"] <= entry["limit"] for entry in line["compared"].values())
    check = next(n for n in notes if n.get("phase") == "reference-check")
    # a restored tail and a carried state were in the served comparison
    assert check["served_prefix_hit_tokens"] == [0, 32, 32] and check["served_problems"] == []
    assert check["served_choices_from_engine_share"] > 0.5 and check["choice_pairs"] > 0
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(line["metrics"]) <= want
    assert {"moe_experts_touched_share", "kv_pages_held_share", "decode_rows_useful_share", "tick_host_share",
            "device_decode_share", "answer_decode_share"} <= set(line["metrics"])
    assert 0 < line["metrics"]["moe_experts_touched_share"]["value"] <= 100

