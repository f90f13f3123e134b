"""The configuration ``nemotron-3-nano-30b-a3b-ep2-l14`` and its family
(``benchmark/families/nemotron_h.py``): the cut and the counts by hand, the
pool's bytes with the Mamba state per slot and per snapshot, the seeded tree
under the published ``A`` / ``dt`` initialisation and an untied head, the
checkpoint through ``load_decoder``, the costs, and the cell
``nemotron3-ep2-chat-closed-16`` rehearsed on the CPU through ``run.py`` →
``server.py`` → ``check.py`` in a copy of ``benchmark/`` (its own ``.work``, as
the other rehearsals have)."""

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
from benchmark.families import nemotron_h as family
from benchmark.roofline import least_time_s

MODEL = load_dir("configs")["nemotron-3-nano-30b-a3b-ep2-l14"]
TINY = {**MODEL, **MODEL["rehearsal"]}
MIX = load_dir("traffic")["chat-closed-16"]
CELL = "nemotron3-ep2-chat-closed-16"
CATALOG_SOURCE = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def test_the_cut_by_hand():
    """ISSUE 44's arithmetic: a Mamba block 38.74 M, an attention block 23.40
    M, an expert 9,977,856, a routed block whole 2.595 GB, the model 31.58 B;
    here 9.17 GB of weights; 2,048 B of K and V a token and 12.8 MB of state a
    sequence; the pool to the byte."""
    w = family.weight_params(MODEL)
    assert w["mamba"] == 2688 * 10304 + 4096 * 2688 + 6144 * 5 + 3 * 64 + 4096 == 38_742_208
    assert w["attention"] == 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 == 23_396_352
    assert w["expert"] == 2 * 2688 * 1856 == 9_977_856 and w["shared"] == 2 * 2688 * 3712 == 19_955_712
    assert w["router"] == 2688 * 128 + 128 and w["table"] == 65536 * 2688
    whole_block = 128 * w["expert"] + w["shared"] + w["router"]
    assert 2.594e9 < 2 * whole_block < 2.596e9                              # a routed block whole: 2.595 GB
    assert family.block_counts(MODEL) == {"M": 6, "E": 6, "*": 2}
    weights = family.model_weights(MODEL)
    assert weights == 6 * w["mamba"] + 2 * w["attention"] + 6 * (w["router"] + 64 * w["expert"] + w["shared"]) + 2 * w["table"]
    assert 9.16e9 < 2 * weights < 9.18e9                                    # 9.17 GB of bf16
    whole = {**MODEL, **MODEL["published"]}
    assert family.block_counts(whole) == {"M": 23, "E": 23, "*": 6}
    assert 31.5e9 < family.model_weights(whole) < 31.7e9                    # the published model: 31.58 B
    assert family.kv_bytes_per_token(MODEL) == 2048
    assert family.state_bytes(MODEL) == 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) == 12_804_096
    env = {**MODEL["serve_env"], **MIX["serve_env"]}
    assert family.pool_bytes(MODEL, env) == 161 * 128 * 2048 + (16 + 64) * 12_804_096 == 1_066_532_864
    # a tail a page, as lfm2's state has, would be fifty times the K and V of the page it ends
    assert 161 * family.state_bytes(MODEL) > 2.0e9 and family.state_bytes(MODEL) / (128 * 2048) > 48
    assert MODEL["published"] == {"num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED_PATTERN,
                                  "n_routed_experts": 128, "vocab_size": 131072}
    assert MODEL["reduced"] == ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    # the floors of a model_config PR: whole turns of the 7-block run, >= 8 experts, >= an eighth of the vocabulary
    assert MODEL["hybrid_override_pattern"] == PUBLISHED_PATTERN[:14] == "MEMEM*E" * 2
    assert MODEL["n_routed_experts"] == 64 >= 8 and MODEL["n_routed_experts_router"] == 128
    assert MODEL["vocab_size"] * 8 >= 131072 and MODEL["num_hidden_layers"] == 14 >= 9


def test_every_published_value_is_the_catalogs_and_no_width_is_cut():
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True}
    assert {k: MODEL[k] for k in published} == published
    assert MODEL["source"] == CATALOG_SOURCE and MODEL["family"] == "nemotron_h" and MODEL["chips"] == 1
    entry = next(c for c in BENCH["configs"] if c["name"] == MODEL["name"])
    assert entry["source"] == CATALOG_SOURCE and entry["reduced"] == MODEL["reduced"]
    widths = ("hidden_size", "intermediate_size", "_dim", "_rank", "per_tok", "heads", "state_size", "kernel")
    assert not [k for k in MODEL["reduced"] if k.endswith(widths)]
    assert len(MODEL["assumed"]) == 5 and "2 chips that share each layer" in MODEL["deployment"]
    assert "4 pipeline stages" in MODEL["deployment"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (MODEL["name"], "chat-closed-16", 1)
    assert len(cell["why"]) <= 200 and BENCH["workloads"][-1] is cell and BENCH["configs"][-1] is entry
    lists = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"] if CELL in m.get("workloads", [])}
    lfm2 = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]
            if "lfm2moe-chat-closed-16" in m.get("workloads", [])}
    assert lists == lfm2 | {"moe_pairs_held_share", "paged_attn_roofline"}
    # no per-layer metric of its own (PERF.md section 7, Q17): the cell joined the lists that exist, at their ends
    assert [m["name"] for m in BENCH["per_layer"]][-1] == "answer_decode_share" and len(BENCH["per_layer"]) == 25
    assert all(m["workloads"][-1] == CELL for m in BENCH["per_layer"] + BENCH["end_to_end"]
               if CELL in m.get("workloads", []))


def test_depth_keeps_every_kind_of_block():
    """``check_config`` at 7 blocks: one whole turn, every kind; at the file's
    depth it is what ``/info`` reports; at two (the yardstick's CPU tests
    hold a rehearsal to it) a layer is a mixer and the experts behind it."""
    cfg = family.check_config(MODEL, 7, 1536)
    assert cfg.pattern == "MEMEM*E" and MODEL["check"]["layers"] == 7
    assert (len(cfg.ssm_layers), cfg.n_routed_layers, cfg.attn_layers) == (3, 3, (5,))
    two = family.check_config(TINY, 2, 512)
    assert two.pattern == "ME*E" and two.n_layers == 4 and TINY["check"]["layers"] == 2
    assert family.check_config(MODEL, 3, 512).pattern == "ME*EME"
    assert len(TINY["check"]["served"]["prompt_chars"]) == 3
    served = family.check_config(MODEL, 14, 262_144)
    assert dataclasses.asdict(served) == family.program_config(MODEL)
    assert (served.dim, served.head_dim, served.n_heads, served.n_kv_heads, served.mlp_dim, served.shared_mlp_dim,
            served.n_experts, served.experts_held, served.experts_per_token, served.vocab_size) \
        == (2688, 128, 32, 2, 1856, 3712, 128, 64, 6, 65536)
    assert (served.inner, served.conv_dim, served.conv_taps, served.ssm_state, served.n_groups, served.chunk_size) \
        == (4096, 6144, 3, 128, 8, 128)
    assert served.attn_layers == (5, 12) and len(served.ssm_layers) == 6 and served.norm_topk_eps == 1e-20
    assert served.state_shapes(16)["ssm"][0] == (6, 16, 64, 64, 128)
    # what the block has no switch for is held by the family: a gated expert is another model
    with pytest.raises(AssertionError):
        family.program_config({**MODEL, "mlp_hidden_act": "silu"})


def test_reference_kwargs_and_the_checks_prompts():
    kw = family.reference_kwargs(MODEL)
    assert (kw["experts_held"], kw["expert_offset"], kw["experts_per_token"], kw["norm_topk_eps"]) == (64, 0, 6, 1e-20)
    assert family.CHOICES == {"experts": "experts_per_token"} and kw["routed_scaling_factor"] == 2.5
    assert (kw["mamba_heads"], kw["mamba_head_dim"], kw["n_groups"], kw["ssm_state"]) == (64, 64, 8, 128)
    source = (REPO / "benchmark" / "nemotron_h_reference.py").read_text()
    assert "sentio_tpu" not in source.split('"""')[2] and 'default_matmul_precision("highest")' in source
    assert "jax.lax.scan(step" in source                    # the recurrence a token at a time: no chunks
    check, served = MODEL["check"], MODEL["check"]["served"]
    assert check["prompt_tokens"] == [1100, 131] and check["decode_steps"] == 16
    assert served["new_tokens"] == 96 and served["steps_per_tick"] == 16 and served["shared_head_chars"] == 256
    # every request is chunked (check.py asks it), the cold prompt's first segment ends where the head does
    # (its end leaves the snapshot the later ones start from), and every later one starts behind it
    page, chunk = check["page_size"], served["prefill_chunk"]
    hit = (served["shared_head_chars"] + 1) // page * page
    assert hit == chunk == 256 and served["prompt_chars"][0] + 1 > chunk
    assert all(n + 1 - hit > chunk for n in served["prompt_chars"][1:])


@pytest.mark.parametrize("seed", [0, 2147483659])
def test_seeded_tree_has_the_published_init_and_answers_do_not_collapse(seed):
    """An untied head whose text ids' columns are a quarter as large, so no
    greedy answer holds one (3 bytes a token, no EOS), whatever the seed; the
    matrices follow the program's seeded distributions; ``A`` lies in 1..16
    and ``softplus(dt_bias)`` in ``time_step_min..max``; the taps and the bias
    are float32; and an answer does not collapse to one token."""
    from sentio_tpu.models.nemotron_h import EXPERT_BIAS_STD, HEAD_SCALE, WO_SCALE, WQ_SCALE
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    tree = family.make_params(TINY, seed)
    head = np.asarray(tree["lm_head"]["kernel"], np.float32)
    assert head.shape == (64, 8192) and tree["embed_tokens"]["embedding"].shape == (8192, 64)
    assert head[:, : dense.TEXT_IDS].std() == pytest.approx(HEAD_SCALE * family.TEXT_COL_SCALE * 64 ** -0.5, rel=0.06)
    assert head[:, dense.TEXT_IDS:].std() == pytest.approx(HEAD_SCALE * 64 ** -0.5, rel=0.02)
    assert [set(tree[f"layers_{i}"]) - {"norm"} for i in (0, 1, 5)] == [{"mamba"}, {"moe"}, {"attn"}]
    moe, mamba = tree["layers_1"]["moe"], tree["layers_0"]["mamba"]
    assert set(moe) == {"router", "bias", "w_up", "w_down", "shared"}             # ungated: two matrices
    assert moe["w_up"].shape == (4, 64, 48) and moe["shared"]["w_down"].shape == (1, 64, 64)
    assert moe["bias"].shape == (8,) and moe["bias"].dtype == np.float32
    assert 0 < np.abs(moe["bias"]).max() < 5 * EXPERT_BIAS_STD
    assert mamba["w_in"]["kernel"].shape == (64, 64 + 128 + 8) and mamba["conv_kernel"].shape == (128, 4)
    assert mamba["conv_kernel"].dtype == np.float32 and not mamba["conv_bias"].any()
    a, dt = np.exp(mamba["a_log"]), np.log1p(np.exp(mamba["dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and 0.001 <= dt.min() and dt.max() <= 0.1001
    std = lambda a: float(np.asarray(a, np.float32).std())  # noqa: E731
    assert std(tree["layers_5"]["attn"]["wq"]["kernel"]) == pytest.approx(WQ_SCALE * 64 ** -0.5, rel=0.06)
    assert std(mamba["w_out"]["kernel"]) == pytest.approx(WO_SCALE * 64 ** -0.5, rel=0.06)
    again = family.make_params(TINY, seed)
    assert np.array_equal(np.asarray(again["layers_6"]["moe"]["w_down"], np.float32),
                          np.asarray(tree["layers_6"]["moe"]["w_down"], np.float32))
    assert np.array_equal(again["layers_4"]["mamba"]["a_log"], tree["layers_4"]["mamba"]["a_log"])
    engine = ContinuousBatchingEngine(
        model_config=family.check_config(TINY, 7, 4096), params=tree, max_slots=2, page_size=16,
        max_pages_per_seq=8, ssm_snapshots=4)
    for res in engine.run_all(["what does the passage say?", "summarise file d00012.txt"], max_new_tokens=32):
        assert res.finish_reason == "length" and len(res.tokens) == 32
        assert min(res.tokens) >= dense.TEXT_IDS and len(res.text.encode()) == 3 * 32
        assert len(set(res.tokens)) >= 12, res.tokens


def test_checkpoint_goes_through_load_decoder(tmp_path):
    """``LLM_CHECKPOINT`` is the surface a user has: the family in the
    checkpoint's meta picks the config class and every field comes back; the
    tree is the serving tree."""
    from sentio_tpu.config import GeneratorConfig
    from sentio_tpu.models.nemotron_h import NemotronHConfig
    from sentio_tpu.runtime.weights import load_decoder

    family.write_checkpoint(tmp_path / "llm", TINY, 5)
    decoder = load_decoder(GeneratorConfig(checkpoint_path=str(tmp_path / "llm")))
    assert isinstance(decoder.model_config, NemotronHConfig)
    assert json.loads(json.dumps(dataclasses.asdict(decoder.model_config))) == family.program_config(TINY)
    assert decoder.model_config.pattern == TINY["hybrid_override_pattern"]
    attn, mamba = decoder.params["layers_5"]["attn"], decoder.params["layers_0"]["mamba"]
    # the serving tree: q, k, v and the Mamba's input projection [out, in] (``models/llama.py::serving_layout``)
    assert set(attn) == {"wq_t", "wk_t", "wv_t", "wo"} and attn["wk_t"]["kernel"].shape == (32, 64)
    assert "w_in" not in mamba and mamba["w_in_t"]["kernel"].shape == (200, 64)
    assert decoder.params["lm_head"]["kernel"].shape == (64, 8192)


def test_costs_by_hand():
    """16 rows of 834 tokens (the mix's mean prompt and half an answer): the
    rows that advance are bounded from below by 13,344 / 1,280 = 10.4, which
    touch 64 (1 - (122/128)^10.4) = 25.2 of the 64 held experts a block; a
    sub-step then reads 4.47 GB and ONE gmm call 25.2 matrices of 9.98 MB."""
    context = 16 * 834
    n = family.rows_advancing(MODEL, context)
    assert n == pytest.approx(10.425) and n <= 16
    touched = family.experts_touched(MODEL, n)
    assert touched == pytest.approx(64 * (1 - (122 / 128) ** n)) and 25 < touched < 25.5
    assert family.pairs_held(MODEL) == 3.0                                 # of a token's six picks, those held here
    one = family.KERNEL_COSTS["expert_mlp"](MODEL, 16, context)
    assert one["bytes"] == pytest.approx(2 * touched * 2688 * 1856) and 2 * 2688 * 1856 == 9_977_856
    assert one["flops"] == pytest.approx(2 * n * 3 * 2688 * 1856)
    assert set(family.KERNEL_COSTS) == set(MODEL["trace"]["kernels"]) == {"expert_mlp", "paged_attention"}
    assert MODEL["trace"]["calls_per_substep"] == 2 * 6 and MODEL["trace"]["substep_kernel"] == "^gmm"
    walk = family.KERNEL_COSTS["paged_attention"](MODEL, 16, context)
    assert walk["bytes"] == context * 1024 and walk["flops"] == 4 * context * 4096
    step = family.decode_substep_cost(MODEL, 16, context)
    w = family.weight_params(MODEL)
    weights = (6 * w["mamba"] + 2 * w["attention"] + 6 * (w["router"] + w["shared"] + touched * w["expert"]) + w["table"])
    assert step["bytes"] == pytest.approx(2 * (weights + 16 * 2688) + context * 2048 + 2 * n * 12_804_096)
    # the 12 calls of a sub-step hold the expert bytes the whole step counts, the 2 walks its K and V
    assert 12 * one["bytes"] == pytest.approx(2 * 6 * touched * w["expert"]) and 2 * walk["bytes"] == context * 2048
    assert step["bytes"] > 12 * one["bytes"] + 2 * walk["bytes"] and step["flops"] > 12 * one["flops"] + 2 * walk["flops"]
    assert 4.4e9 < step["bytes"] < 4.55e9 and 0.0053 < least_time_s(step, "TPU v5 lite")["seconds"] < 0.0056
    # every row advancing: 34.3 experts a block, 5.7 GB
    full = family.decode_substep_cost(MODEL, 16, 16 * 1280)
    assert family.experts_touched(MODEL, 16) == pytest.approx(34.3, abs=0.1) and 5.6e9 < full["bytes"] < 5.8e9
    # no share can read over 100 at the rehearsal's sizes either: the lower bound on rows holds there too
    tiny_step = family.decode_substep_cost(TINY, 4, 4 * 834)
    assert tiny_step["bytes"] < family.decode_substep_cost(TINY, 4, 4 * 1280)["bytes"]


# ------------------------------------------------------------ the rehearsal


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("nemotron-h")
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
    # /info equalled the file field for field (the pool's bytes, state and snapshots among them),
    # nothing compiled in the window, the reference agreed
    assert window["problems"] == ["platform is cpu, not tpu (rehearsal)"], window
    assert window["answer_tokens_per_request"] == 256
    dense_keys = {"prefill_rel_rms", "decode_rel_rms", "decode_over_prefill", "served_token_gap", "served_logprob_err"}
    choices = {f"{part}choice_{what}" for part in ("", "served_") for what in ("disagree_share", "worst_margin")}
    assert set(line["compared"]) == dense_keys | choices
    assert all(0 <= entry["value"] <= entry["limit"] for entry in line["compared"].values())
    check = next(n for n in notes if n.get("phase") == "reference-check")
    # a restored snapshot and a carried state were in the served comparison
    assert check["served_prefix_hit_tokens"] == [0, 32, 32] and check["served_problems"] == []
    assert check["served_choices_from_engine_share"] > 0.5 and check["choice_pairs"] > 0
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(line["metrics"]) <= want
    assert {"moe_experts_touched_share", "moe_pairs_held_share", "kv_pages_held_share", "decode_rows_useful_share",
            "tick_host_share", "device_decode_share", "answer_decode_share"} <= set(line["metrics"])
    assert 0 < line["metrics"]["moe_experts_touched_share"]["value"] <= 100
    assert 30 < line["metrics"]["moe_pairs_held_share"]["value"] < 70         # 4 of the router's 8 held
