"""The configuration ``deepseek-v2-ep8-l8`` and its family
(``benchmark/families/deepseek_v2.py``): the cut and the counts by hand, the
latent pool's bytes, the seeded tree under an untied head and its routing over
the eight groups, the checkpoint through ``load_decoder``, the mix ``rag-long``
as the scratch mix it was, and the cell ``dsv2-ep8-rag-long`` rehearsed on the
CPU through ``run.py`` → ``server.py`` → ``check.py`` in a copy of
``benchmark/`` (its own ``.work``: no trace directory shared with the other
rehearsals, ROADMAP D8)."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest
from bench_tree import BENCH, REPO, load_dir

from benchmark.families import deepseek_v2 as family
from benchmark.families import llama as dense
from benchmark.roofline import least_time_s

MODEL = load_dir("configs")["deepseek-v2-ep8-l8"]
TINY = {**MODEL, **MODEL["rehearsal"]}
MIX = load_dir("traffic")["rag-long"]
CELL = "dsv2-ep8-rag-long"
CATALOG_SOURCE = "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json"


def test_the_cut_by_hand():
    """ISSUE 38's arithmetic: attention 149.2 M, a routed layer here 669.1 M,
    the dense layer 338.0 M, 10.30 GB of weights, 1,152 B a token a layer."""
    w = family.weight_params(MODEL)
    assert w["attention"] == (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
                              + 128 * 128 * 5120) == 149_225_472
    assert w["expert"] == 3 * 5120 * 1536 == 23_592_960 and w["router"] == 5120 * 160
    assert w["dense_mlp"] == 3 * 5120 * 12288 == 188_743_680 and w["table"] == 12_800 * 5120
    routed = w["attention"] + w["router"] + (2 + 20) * w["expert"]
    assert routed == 669_089_792 and w["attention"] + w["dense_mlp"] == 337_969_152
    weights = 2 * (w["attention"] + w["dense_mlp"] + 7 * routed + 2 * w["table"])
    assert weights == 10_305_339_392                                   # 10.30 GB of bf16
    assert 2 * (routed - 20 * w["expert"] + 160 * w["expert"]) > 7.9e9  # an uncut routed layer: no chip holds two
    assert family.kv_bytes_per_token(MODEL) == 8 * 1152 and family.latent_dim(MODEL) == 576
    assert family.pool_bytes(MODEL, {**MODEL["serve_env"], **MIX["serve_env"]}) == (1 + 8 * 40) * 128 * 8 * 1152
    assert MODEL["published"] == {"num_hidden_layers": 60, "n_routed_experts": 160, "vocab_size": 102400}
    assert MODEL["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    # the floors of a model_config PR: >= 4 layers behind the dense one, >= 8 experts, >= 1/8 of the vocabulary
    assert MODEL["num_hidden_layers"] - MODEL["first_k_dense_replace"] == 7 >= 4 and MODEL["n_routed_experts"] == 20 >= 8
    assert MODEL["vocab_size"] * 8 == MODEL["published"]["vocab_size"] and MODEL["vocab_size"] > dense.TEXT_IDS
    # one whole published group: the deployment is the model's own grouping
    assert MODEL["n_routed_experts"] == MODEL["n_routed_experts_router"] // MODEL["n_group"] and MODEL["expert_offset"] == 0


def test_every_published_value_is_the_catalogs_and_no_width_is_cut():
    """The file against the values ISSUE 38 quotes from the catalog entry:
    every key but the three in ``reduced`` as published, no width among them."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 12288, "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v2", "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
        "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 128, "num_experts_per_tok": 6,
        "num_key_value_heads": 128, "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000, "routed_scaling_factor": 16, "scoring_func": "softmax",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 3, "topk_method": "group_limited_greedy",
        "v_head_dim": 128,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096, "type": "yarn"}}
    assert {k: MODEL[k] for k in published} == published
    assert MODEL["source"] == CATALOG_SOURCE and MODEL["family"] == "deepseek_v2" and MODEL["chips"] == 1
    entry = next(c for c in BENCH["configs"] if c["name"] == MODEL["name"])
    assert entry["source"] == CATALOG_SOURCE and entry["reduced"] == MODEL["reduced"]
    widths = ("hidden_size", "intermediate_size", "_dim", "_rank", "per_tok", "heads")
    assert not [k for k in MODEL["reduced"] if k.endswith(widths)]
    assert len(MODEL["assumed"]) == 6 and "8 chips" in MODEL["deployment"]


def test_costs_by_hand():
    """3 rows of 4.8k tokens: at least 14,400 / 5,120 = 2.81 rows advance,
    which touch 20 (1 - (154/160)^2.81) = 2.04 of the 20 held experts; ONE call
    of the latent kernel moves 1,152 B a held token and sits at the chip's ridge."""
    context = 3 * 4800
    n = family.rows_advancing(MODEL, context)
    assert n == pytest.approx(2.8125) and n <= 8
    touched = family.experts_touched(MODEL, n)
    assert touched == pytest.approx(20 * (1 - (154 / 160) ** n)) and 2.0 < touched < 2.1
    one = family.KERNEL_COSTS["latent_attention"](MODEL, 8, context)
    assert one["flops"] == context * 128 * (576 + 512) * 2
    assert one["bytes"] == pytest.approx(2 * (context * 576 + n * 128 * (576 + 512)))
    assert 230 < one["flops"] / one["bytes"] < 241                     # the ridge of a v5e is 240
    least = least_time_s(one, "TPU v5 lite")
    assert 20e-6 < least["seconds"] < 22e-6                            # 16.6 MB at 819 GB/s
    # the kernel the trace names and the kernel that has a cost are the same one
    assert set(family.KERNEL_COSTS) == set(MODEL["trace"]["kernels"]) == {"latent_attention"}
    step = family.decode_substep_cost(MODEL, 8, context)
    w = family.weight_params(MODEL)
    weights = (8 * w["attention"] + w["dense_mlp"] + 7 * (w["router"] + (2 + touched) * w["expert"]) + w["table"])
    assert step["bytes"] == pytest.approx(2 * (weights + 8 * 5120) + context * 8 * 1152)
    # the eight kernel calls of a sub-step hold the latent bytes and the attention operations the whole step counts
    assert 8 * (one["bytes"] - 2 * n * 128 * 1088) == pytest.approx(context * 8 * 1152)
    assert step["flops"] > 8 * one["flops"]
    assert 0.0045 < least_time_s(step, "TPU v5 lite")["seconds"] < 0.0055   # 4.1 GB a sub-step


def test_depth_keeps_the_dense_layer_and_one_routed_layer():
    cfg = family.check_config(MODEL, 2, 4992)
    assert (cfg.n_layers, cfg.first_k_dense_replace, cfg.n_routed_layers) == (2, 1, 1)
    assert [cfg.routed_layer(i) for i in range(2)] == [False, True]
    served = family.check_config(MODEL, 8, 163_840)
    assert dataclasses.asdict(served) == family.program_config(MODEL)
    assert (served.dim, served.q_lora_rank, served.kv_lora_rank, served.qk_rope_head_dim, served.qk_nope_head_dim,
            served.v_head_dim, served.n_heads, served.mlp_dim, served.moe_mlp_dim) == \
        (5120, 1536, 512, 64, 128, 128, 128, 12288, 1536)
    assert (served.experts_held, served.n_experts, served.expert_offset, served.n_group, served.topk_group,
            served.experts_per_token, served.routed_scaling_factor, served.vocab_size) == (20, 160, 0, 8, 3, 6, 16.0, 12800)
    assert served.latent_dim * 2 == 1152


def test_reference_kwargs_give_the_reference_the_same_share_and_both_choices():
    kw = family.reference_kwargs(MODEL)
    assert (kw["experts_held"], kw["expert_offset"], kw["experts_per_token"], kw["topk_group"], kw["n_group"]) == \
        (20, 0, 6, 3, 8)
    assert family.CHOICES == {"groups": "topk_group", "experts": "experts_per_token"}
    assert set(family.CHOICES.values()) <= set(kw) and kw["routed_scaling_factor"] == 16.0 and not kw["norm_topk_prob"]
    assert family.REFERENCE == "benchmark.deepseek_v2_reference"
    source = (REPO / "benchmark" / "deepseek_v2_reference.py").read_text()
    assert "sentio_tpu" not in source and 'default_matmul_precision("highest")' in source
    # the check: one prompt of the mix's length, one of 131; 16 decode steps; two layers
    assert MODEL["check"]["prompt_tokens"] == [4800, 131] and MODEL["check"]["decode_steps"] == 16
    assert MIX["shapes"]["prompt_tokens"][0] <= 4800 <= MIX["shapes"]["prompt_tokens"][1]


@pytest.mark.parametrize("seed", [0, 7, 2147483659])
def test_seeded_tree_keeps_the_head_untied_and_no_answer_holds_a_text_id(seed):
    """The head's columns for the tokenizer's 261 ids are zero (the dense
    family's rule, whole): every greedy answer token lies outside them (3
    bytes of text each, no EOS), whatever the seed; the matrices follow the
    program's seeded distributions."""
    from sentio_tpu.models.deepseek_v2 import WO_SCALE, WQ_SCALE
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    tree = family.make_params(TINY, seed)
    head = np.asarray(tree["lm_head"]["kernel"], np.float32)
    assert head.shape == (64, 8192) and not head[:, : dense.TEXT_IDS].any() and head[:, dense.TEXT_IDS:].std() > 0.1
    assert "mlp" in tree["layers_0"] and "moe" not in tree["layers_0"] and "moe" in tree["layers_1"]
    assert tree["layers_1"]["moe"]["w_gate"].shape == (4, 64, 32)
    assert tree["layers_1"]["moe"]["shared"]["w_down"].shape == (2, 32, 64)
    attn = tree["layers_0"]["attn"]
    assert attn["w_uk"].shape == (4, 16, 32) and attn["w_uv"].shape == (4, 16, 32)
    std = lambda a: float(np.asarray(a, np.float32).std())  # noqa: E731
    assert std(attn["wq_b"]["kernel"]) == pytest.approx(WQ_SCALE * 48 ** -0.5, rel=0.05)
    assert std(attn["wo"]["kernel"]) == pytest.approx(WO_SCALE * 64 ** -0.5, rel=0.06)
    assert std(tree["layers_1"]["moe"]["router"]["kernel"]) == pytest.approx(64 ** -0.5, rel=0.06)
    again = family.make_params(TINY, seed)
    assert np.array_equal(np.asarray(again["layers_2"]["moe"]["shared"]["w_down"], np.float32),
                          np.asarray(tree["layers_2"]["moe"]["shared"]["w_down"], np.float32))
    engine = ContinuousBatchingEngine(
        model_config=family.check_config(TINY, 3, 4096), params=tree, max_slots=2, page_size=16,
        max_pages_per_seq=8)
    for res in engine.run_all(["what does the passage say?", "summarise file d00012.txt"], max_new_tokens=32):
        assert res.finish_reason == "length" and len(res.tokens) == 32
        assert min(res.tokens) >= dense.TEXT_IDS
        assert len(res.text.encode()) == 3 * 32


@pytest.mark.parametrize("seed", [1, 5, 2147483659])
def test_seeded_routing_is_near_even_over_the_eight_groups(seed):
    """The published router geometry (160 experts, 8 groups, the best 3, 6 a
    token, group 0 held) at hidden 256: over a run of prompts and greedy
    answers the share of pairs this chip's group holds stays near an eighth
    (9 to 16 %), and an answer does not collapse to one token."""
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    model = {**TINY, "hidden_size": 256, "n_routed_experts": 20, "n_routed_experts_router": 160, "n_group": 8,
             "topk_group": 3, "num_experts_per_tok": 6, "num_hidden_layers": 3}
    engine = ContinuousBatchingEngine(
        model_config=family.check_config(model, 3, 4096), params=family.make_params(model, seed),
        max_slots=4, page_size=16, max_pages_per_seq=12)
    prompts = [f"question {i}: " + "".join(chr(97 + (i * 7 + j * 3) % 26) for j in range(60)) for i in range(8)]
    results = engine.run_all(prompts, max_new_tokens=64)
    stats = engine.stats()
    assert 0.09 < stats["moe_pairs_held"] / stats["moe_pairs_routed"] < 0.16
    assert all(len(set(r.tokens)) >= 16 for r in results), [len(set(r.tokens)) for r in results]


def test_checkpoint_goes_through_load_decoder(tmp_path):
    """``LLM_CHECKPOINT`` is the surface a user has: the family in the
    checkpoint's meta picks the config class, every field comes back, and the
    tree holds ``kv_b_proj`` as its two halves."""
    from sentio_tpu.config import GeneratorConfig
    from sentio_tpu.models.deepseek_v2 import DeepseekV2Config
    from sentio_tpu.runtime.weights import load_decoder

    family.write_checkpoint(tmp_path / "llm", TINY, 5)
    decoder = load_decoder(GeneratorConfig(checkpoint_path=str(tmp_path / "llm")))
    assert isinstance(decoder.model_config, DeepseekV2Config)
    assert json.loads(json.dumps(dataclasses.asdict(decoder.model_config))) == family.program_config(TINY)
    attn = decoder.params["layers_0"]["attn"]
    # the serving tree: the query up-projection [out, in] (``models/llama.py::serving_layout``)
    assert set(attn) == {"wq_a", "q_norm", "wq_b_t", "wkv_a", "kv_norm", "w_uk", "w_uv", "wo"}
    assert attn["wq_b_t"]["kernel"].shape == (4 * 24, 48)
    assert attn["w_uk"].shape == (4, 16, 32) and decoder.params["lm_head"]["kernel"].shape == (64, 8192)


def test_the_mix_is_the_scratch_mix_with_a_name():
    """``rag-long`` IS ``test_benchmark_traffic.py::LONG`` (PERF.md section 7,
    Q6b) with a name, a why, 8 slots, the tick policy, ``drain_s`` and a
    rehearsal that cuts callers and corpus and not the prompt band; both cells
    of the mix are on one chip."""
    from test_benchmark_traffic import LONG

    for key, value in LONG.items():
        if key in ("name", "serve_env", "warm_programs"):
            continue
        got = MIX[key] if key != "shapes" else {"prompt_tokens": MIX["shapes"]["prompt_tokens"]}
        assert got == value, key
    # the warm-up's count held tighter than the scratch mix held it, never looser, and it says why
    assert MIX["warm_programs"] == {**LONG["warm_programs"], "paged.merge_admitted": 2} and "warm_note" in MIX
    assert {k: MIX["serve_env"][k] for k in LONG["serve_env"]} == LONG["serve_env"]
    extra = {k: v for k, v in MIX["serve_env"].items() if k not in LONG["serve_env"]}
    assert extra == {"EMBED_COALESCE": "0", "LLM_MAX_BATCH": "8", "DECODE_STEPS_PER_TICK": "8",
                     "DECODE_MAX_TICK_STEPS": "8"}
    assert MIX["drain_s"] == 60 and MIX["name"] == "rag-long" and len(MIX["why"]) > 100
    assert set(MIX["rehearsal"]) == {"clients", "corpus", "warmup_bursts"}
    assert MIX["rehearsal"]["corpus"]["file_chars"] == MIX["corpus"]["file_chars"]
    # 1,024 files are 128 questions: 27 for three rounds of warm-up at worst, 101 for a window
    assert MIX["corpus"]["files"] // 8 - 3 * sum(MIX["warmup_bursts"]) == 101
    cells = {w["name"]: w for w in BENCH["workloads"] if w["traffic"] == "rag-long"}
    assert set(cells) == {CELL, "mistral7b-rag-long"} and all(w["chips"] == 1 for w in cells.values())
    assert cells["mistral7b-rag-long"]["config"] == "mistral-7b-v0.3-l16"
    new = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in ("latent_attn_roofline", "prefill_prior_expand_ratio")}
    assert all(m["workloads"] == [CELL] for m in new.values()) and len(new) == 2
    roofline = next(m for m in BENCH["per_layer"] if m["name"] == "paged_attn_roofline")
    assert "mistral7b-rag-long" in roofline["workloads"] and CELL not in roofline["workloads"]


# ------------------------------------------------------------ the rehearsal


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("deepseek-v2")
    shutil.copytree(REPO / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for name in ("sentio_tpu", "prompts"):
        (root / name).symlink_to(REPO / name, target_is_directory=True)
    return root


def test_the_cell_rehearses_with_both_choices_and_its_metrics(tree):
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
    # /info equalled the file field for field (the latent pool's bytes among them),
    # nothing compiled in the window, the reference agreed
    assert window["problems"] == ["platform is cpu, not tpu (rehearsal)"], window
    assert window["answer_tokens_per_request"] == 96
    dense_keys = {"prefill_rel_rms", "decode_rel_rms", "decode_over_prefill", "served_token_gap", "served_logprob_err"}
    choices = {f"{part}choice_{what}" for part in ("", "served_") for what in ("disagree_share", "worst_margin")}
    assert set(line["compared"]) == dense_keys | choices
    assert all(0 <= entry["value"] <= entry["limit"] for entry in line["compared"].values())
    check = next(n for n in notes if n.get("phase") == "reference-check")
    # groups and experts, both tallied; the served answers' picks came from ``run_all`` itself
    assert check["served_choices_from_engine_share"] > 0.5 and check["choice_pairs"] > 0
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(line["metrics"]) <= want
    assert {"moe_pairs_held_share", "moe_experts_touched_share", "kv_pages_held_share", "decode_rows_useful_share",
            "tick_host_share", "prefill_prior_expand_ratio"} <= set(line["metrics"])
    # 4 of 16 experts held: a quarter of the pairs, within what 4 experts' luck allows
    assert 10.0 < line["metrics"]["moe_pairs_held_share"]["value"] < 45.0
    # nine segments over a 256-token cached head: (9 x 256 + 512 x 36) / (prompt - 256) of about 4.5
    assert 4.0 < line["metrics"]["prefill_prior_expand_ratio"]["value"] < 5.0
