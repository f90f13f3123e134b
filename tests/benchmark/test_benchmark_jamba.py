"""The configuration ``ai21-jamba2-3b`` and its family
(``benchmark/families/jamba.py``): the arithmetic of the file by hand — nothing
is cut —, every published value against the catalog's, the pool's bytes with
the Mamba-1 state per slot and per snapshot, the seeded tree under the
published ``A`` / ``dt`` initialisation and a TIED head, the checkpoint through
``load_decoder``, the costs, and the cell ``jamba2-3b-rag-long`` rehearsed on
the CPU through ``run.py`` → ``server.py`` → ``check.py`` in a copy of
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

from benchmark.families import jamba as family
from benchmark.families import llama as dense
from benchmark.roofline import least_time_s

MODEL = load_dir("configs")["ai21-jamba2-3b"]
TINY = {**MODEL, **MODEL["rehearsal"]}
MIX = load_dir("traffic")["rag-long"]
CELL = "jamba2-3b-rag-long"
CATALOG_SOURCE = "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"


def test_the_arithmetic_by_hand():
    """ISSUE 48's arithmetic: a Mamba mixer 41,241,792, a Mamba layer
    104,161,472, an attention layer 76,682,240, the model 3,029,337,472 =
    6.06 GB; 1,024 B of K and V a token and 9,318,400 B of state a sequence;
    the pool to the byte."""
    w = family.weight_params(MODEL)
    assert w["mamba"] == (2560 * 10240 + 5120 * 5 + 5120 * 192 + (160 * 5120 + 5120) + 5120 * 16 + 5120 + 192
                          + 5120 * 2560) == 41_241_792
    assert w["attention"] == 2560 * 2560 + 2 * 2560 * 128 + 2560 * 2560 == 13_762_560
    assert w["mlp"] == 3 * 2560 * 8192 == 62_914_560 and w["norms"] == 5120 and w["table"] == 65536 * 2560
    assert w["mamba"] + w["mlp"] + w["norms"] == 104_161_472 and w["attention"] + w["mlp"] + w["norms"] == 76_682_240
    assert family.layer_counts(MODEL) == (2, 26)
    weights = family.model_weights(MODEL)
    assert weights == 26 * 104_161_472 + 2 * 76_682_240 + 65536 * 2560 + 2560 == 3_029_337_472
    assert 2 * weights == 6_058_674_944                                      # 6.06 GB of bf16, the table once: tied
    assert family.kv_bytes_per_token(MODEL) == 2 * 2 * 1 * 128 * 2 == 1024
    assert family.state_bytes(MODEL) == 26 * (5120 * 16 * 4 + 3 * 5120 * 2) == 9_318_400
    assert 71 < family.state_bytes(MODEL) / (128 * 1024) < 72               # the K and V of 71 pages
    env = {**MODEL["serve_env"], **MIX["serve_env"]}
    assert family.pool_bytes(MODEL, env) == 321 * 128 * 1024 + (8 + 64) * 9_318_400 == 712_998_912
    # the configuration states the slots and pages the mix runs, and its text the same numbers
    assert {k: MODEL["serve_env"][k] for k in ("LLM_MAX_BATCH", "KV_MAX_PAGES_PER_SEQ", "KV_PAGE_SIZE")} \
        == {k: MIX["serve_env"][k] for k in ("LLM_MAX_BATCH", "KV_MAX_PAGES_PER_SEQ", "KV_PAGE_SIZE")}
    for figure in ("41,241,792", "104,161,472", "76,682,240", "3,029,337,472", "9,318,400", "712,998,912"):
        assert figure in MODEL["reduced_why"], figure


def test_every_published_value_is_the_catalogs_and_nothing_is_cut():
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert {k: MODEL[k] for k in published} == published
    assert MODEL["source"] == CATALOG_SOURCE and MODEL["family"] == "jamba" and MODEL["chips"] == 1
    assert MODEL["reduced"] == [] and MODEL["published"] == {"num_hidden_layers": 28, "vocab_size": 65536}
    assert all(MODEL[k] == v for k, v in MODEL["published"].items())
    entry = next(c for c in BENCH["configs"] if c["name"] == MODEL["name"])
    assert entry["source"] == CATALOG_SOURCE and entry["reduced"] == [] and len(entry["why"]) <= 200
    assert len(MODEL["assumed"]) == 8 and "WHOLE model" in MODEL["deployment"]
    assert MODEL["trace"] == {"substep_kernel": "^paged_attention", "calls_per_substep": 2,
                              "kernels": {"paged_attention": "^paged_attention"}}
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (MODEL["name"], "rag-long", 1)
    assert len(cell["why"]) <= 200 and [w["config"] for w in BENCH["workloads"]].count(MODEL["name"]) == 1
    # the cell joined the lists of the dense control of its mix, at their ends, and no list is new; one list is
    # held to its two cells by a test only a benchmark PR may edit (PERF.md section 7, Q17)
    mine = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"] if CELL in m.get("workloads", [])}
    control = {m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]
               if "mistral7b-rag-long" in m.get("workloads", [])}
    assert mine == control - {"prefill_turn_wait_share"} and len(mine) == 20
    assert all(m["workloads"][-1] == CELL for m in BENCH["per_layer"] + BENCH["end_to_end"]
               if CELL in m.get("workloads", []))
    assert len(BENCH["per_layer"]) == 25 and len(BENCH["configs"]) == 7 and len(BENCH["workloads"]) == 9


def test_depth_keeps_both_mixers():
    """``check_config`` at 8 layers: the model's own first eight, seven Mamba
    and the attention layer; at the file's depth it is what ``/info`` reports;
    at two (the yardstick's CPU tests hold a rehearsal to it) a Mamba layer
    and an attention layer."""
    cfg = family.check_config(MODEL, 8, 5120)
    assert MODEL["check"]["layers"] == 8 and cfg.attn_layers == (7,) and cfg.ssm_layers == (0, 1, 2, 3, 4, 5, 6)
    assert (cfg.attn_layer_period, cfg.attn_layer_offset) == (14, 7)
    two = family.check_config(TINY, 2, 512)
    assert TINY["check"]["layers"] == 2 and two.attn_layers == (1,) and two.ssm_layers == (0,)
    assert family.check_config(MODEL, 2, 512).attn_layers == (1,)          # a depth the offset does not reach
    assert len(TINY["check"]["served"]["prompt_chars"]) == 3
    served = family.check_config(MODEL, 28, 262_144)
    assert dataclasses.asdict(served) == family.program_config(MODEL)
    assert (served.dim, served.head_dim, served.n_heads, served.n_kv_heads, served.mlp_dim, served.vocab_size) \
        == (2560, 128, 20, 1, 8192, 65536)
    assert (served.inner, served.conv_taps, served.mamba_d_state, served.mamba_dt_rank) == (5120, 3, 16, 160)
    assert served.attn_layers == (7, 21) and len(served.ssm_layers) == 26 and served.norm_eps == 1e-6
    assert served.state_shapes(8) == {"conv": ((26, 8, 3, 5120), served.jdtype), "ssm": ((26, 8, 16, 5120), np.float32)}
    # what the layer has no switch for is held by the family, and routed Jamba models by the program
    with pytest.raises(AssertionError):
        family.program_config({**MODEL, "hidden_act": "gelu"})
    with pytest.raises(ValueError, match="num_experts=16"):
        family.check_config({**MODEL, "num_experts": 16}, 28, 512)


def test_reference_kwargs_and_the_checks_prompts():
    kw = family.reference_kwargs(MODEL)
    assert kw == {"n_heads": 20, "n_kv_heads": 1, "norm_eps": 1e-6, "d_state": 16, "dt_rank": 160}
    assert not hasattr(family, "CHOICES")                   # nothing here picks
    source = (REPO / "benchmark" / "jamba_reference.py").read_text()
    assert "sentio_tpu" not in source.split('"""')[2] and 'default_matmul_precision("highest")' in source
    assert "jax.lax.scan(step" in source                    # the recurrence a token at a time: no blocks
    check, served = MODEL["check"], MODEL["check"]["served"]
    assert check["prompt_tokens"] == [1100, 131] and check["decode_steps"] == 16
    # the served part is the cell's own band: prompts of 4.6 to 4.8k in segments of 512 over priors to 40 pages
    lo, hi = MIX["shapes"]["prompt_tokens"]
    assert all(lo <= n + 1 <= hi for n in served["prompt_chars"])
    assert served["new_tokens"] == int(MIX["serve_env"]["LLM_MAX_TOKENS"]) == 96
    assert served["prefill_chunk"] == int(MIX["serve_env"]["PREFILL_CHUNK"]) == 512
    assert served["steps_per_tick"] == int(MIX["serve_env"]["DECODE_STEPS_PER_TICK"])
    page = check["page_size"]
    assert (max(served["prompt_chars"]) + 1 + 2 * served["new_tokens"] + 4) // page + 1 == 40
    # a match needs a snapshot at its boundary and a cold prompt leaves one where a segment ends: the head is a segment
    hit = (served["shared_head_chars"] + 1) // page * page
    assert hit == served["prefill_chunk"] and all(n + 1 - hit > served["prefill_chunk"] for n in served["prompt_chars"])
    # the limits as the chip's six seeds set them (``tolerances_why`` has the readings and the int8 variant's)
    assert (check["rel_rms_tol"], check["decode_over_prefill_max"], served["token_gap_tol"], served["logprob_tol"]) \
        == (0.03, 1.2, 0.03, 0.02) and "0.02261" in check["tolerances_why"] and "0.0783" in check["tolerances_why"]


@pytest.mark.parametrize("seed", [0, 2147483659])
def test_seeded_tree_has_the_published_init_and_answers_do_not_collapse(seed):
    """A TIED head whose text ids' rows are a quarter as large, so no greedy
    answer holds one (3 bytes a token, no EOS), whatever the seed; the
    matrices follow the program's seeded distributions; ``A = 1..N`` along
    the state's columns and ``softplus(b_dt)`` in 0.001..0.1; the taps, their
    bias and the Mamba's vectors are float32; and an answer does not collapse
    to one token."""
    from sentio_tpu.models.jamba import CONV_BIAS_STD, EMBED_STD, WO_SCALE, WQ_SCALE
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    tree = family.make_params(TINY, seed)
    assert "lm_head" not in tree
    table = np.asarray(tree["embed_tokens"]["embedding"], np.float32)
    assert table.shape == (8192, 64)
    assert table[: dense.TEXT_IDS].std() == pytest.approx(EMBED_STD * family.TEXT_ROW_SCALE, rel=0.06)
    assert table[dense.TEXT_IDS:].std() == pytest.approx(EMBED_STD, rel=0.02)
    assert [set(tree[f"layers_{i}"]) - {"norm", "mlp_norm", "mlp"} for i in (0, 1)] == [{"mamba"}, {"attn"}]
    mamba = tree["layers_0"]["mamba"]
    assert set(mamba) == {"w_in", "conv_kernel", "conv_bias", "w_x", "dt_norm", "b_norm", "c_norm", "w_dt", "dt_bias",
                          "a_log", "d", "w_out"}
    assert mamba["w_in"]["kernel"].shape == (64, 256) and mamba["w_x"]["kernel"].shape == (128, 24 + 2 * 8)
    assert mamba["w_dt"]["kernel"].shape == (24, 128) and mamba["conv_kernel"].shape == (128, 4)
    assert all(mamba[k].dtype == np.float32 for k in ("conv_kernel", "conv_bias", "dt_bias", "a_log", "d"))
    assert 0 < np.abs(mamba["conv_bias"]).max() < 5 * CONV_BIAS_STD
    a, dt = np.exp(mamba["a_log"]), np.log1p(np.exp(mamba["dt_bias"]))
    assert a.shape == (8, 128) and np.allclose(a, np.arange(1, 9)[:, None])      # [N, inner]: 1..N down the columns
    assert 0.001 <= dt.min() and dt.max() <= 0.1001 and dt.max() > 10 * dt.min()
    std = lambda a: float(np.asarray(a, np.float32).std())  # noqa: E731
    assert std(tree["layers_1"]["attn"]["wq"]["kernel"]) == pytest.approx(WQ_SCALE * 64 ** -0.5, rel=0.06)
    assert std(mamba["w_out"]["kernel"]) == pytest.approx(WO_SCALE * 128 ** -0.5, rel=0.06)
    assert tree["layers_1"]["attn"]["wk"]["kernel"].shape == (64, 16)               # ONE kv head
    again = family.make_params(TINY, seed)
    assert np.array_equal(np.asarray(again["layers_0"]["mamba"]["w_x"]["kernel"], np.float32),
                          np.asarray(mamba["w_x"]["kernel"], np.float32))
    assert np.array_equal(again["layers_0"]["mamba"]["dt_bias"], mamba["dt_bias"])
    engine = ContinuousBatchingEngine(
        model_config=family.check_config(TINY, 2, 4096), params=tree, max_slots=2, page_size=16,
        max_pages_per_seq=8, ssm_snapshots=4)
    for res in engine.run_all(["what does the passage say?", "summarise file d00012.txt"], max_new_tokens=32):
        assert res.finish_reason == "length" and len(res.tokens) == 32
        assert min(res.tokens) >= dense.TEXT_IDS and len(res.text.encode()) == 3 * 32
        assert len(set(res.tokens)) >= 12, res.tokens


def test_checkpoint_goes_through_load_decoder(tmp_path):
    """``LLM_CHECKPOINT`` is the surface a user has: the family in the
    checkpoint's meta picks the config class and every field comes back; the
    tree is the serving tree, and it has no head of its own."""
    from sentio_tpu.config import GeneratorConfig
    from sentio_tpu.models.jamba import JambaConfig
    from sentio_tpu.runtime.weights import load_decoder

    family.write_checkpoint(tmp_path / "llm", TINY, 5)
    decoder = load_decoder(GeneratorConfig(checkpoint_path=str(tmp_path / "llm")))
    assert isinstance(decoder.model_config, JambaConfig)
    assert json.loads(json.dumps(dataclasses.asdict(decoder.model_config))) == family.program_config(TINY)
    attn, mamba = decoder.params["layers_1"]["attn"], decoder.params["layers_0"]["mamba"]
    # the serving tree: q, k, v and the Mamba's input projection [out, in] (``models/llama.py::serving_layout``)
    assert set(attn) == {"wq_t", "wk_t", "wv_t", "wo"} and attn["wk_t"]["kernel"].shape == (16, 64)
    assert "w_in" not in mamba and mamba["w_in_t"]["kernel"].shape == (256, 64)
    assert "lm_head" not in decoder.params and decoder.params["embed_tokens"]["embedding"].shape == (8192, 64)


def test_costs_by_hand():
    """8 slots of which three rows hold 4,700 tokens: the rows that advance
    are bounded from below by 14,100 / 5,120 = 2.75; a sub-step reads every
    weight once (6.06 GB), 14.4 MB of K and V and 2.75 states twice (51 MB):
    7.5 ms at the chip's bandwidth, the weights 99 % of it."""
    context = 3 * 4700
    n = family.rows_advancing(MODEL, context)
    assert n == pytest.approx(2.754, abs=1e-3) and n <= 8
    step = family.decode_substep_cost(MODEL, 8, context)
    assert step["bytes"] == pytest.approx(2 * (3_029_337_472 + 8 * 2560) + context * 1024 + 2 * n * 9_318_400)
    w = family.weight_params(MODEL)
    matmuls = 2 * 8 * (26 * w["mamba"] + 2 * w["attention"] + 28 * w["mlp"] + w["table"])
    assert step["flops"] == pytest.approx(matmuls + 2 * n * 2 * 26 * 5120 * 16 + 4 * context * 20 * 128 * 2)
    assert 6.1e9 < step["bytes"] < 6.15e9 and 0.0074 < least_time_s(step, "TPU v5 lite")["seconds"] < 0.0076
    assert set(family.KERNEL_COSTS) == set(MODEL["trace"]["kernels"]) == {"paged_attention"}
    walk = family.KERNEL_COSTS["paged_attention"](MODEL, 8, context)
    assert walk["bytes"] == context * 512 and walk["flops"] == 4 * context * 2560
    # the 2 walks of a sub-step hold the K and V the whole step counts
    assert 2 * walk["bytes"] == context * family.kv_bytes_per_token(MODEL) == MODEL["trace"]["calls_per_substep"] * walk["bytes"]
    assert step["bytes"] > 2 * walk["bytes"] and step["flops"] > 2 * walk["flops"]
    # every row full: the state is still 2.4 % of the bytes; no share can read over 100 at the rehearsal's sizes
    full = family.decode_substep_cost(MODEL, 8, 8 * 5120)
    assert 2 * 8 * 9_318_400 / full["bytes"] < 0.025
    tiny_step = family.decode_substep_cost(TINY, 3, 3 * 834)
    assert tiny_step["bytes"] < family.decode_substep_cost(TINY, 3, 3 * 5120)["bytes"]


# ------------------------------------------------------------ the rehearsal


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("jamba")
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
    assert window["answer_tokens_per_request"] == 96
    assert set(line["compared"]) == {"prefill_rel_rms", "decode_rel_rms", "decode_over_prefill", "served_token_gap",
                                     "served_logprob_err"}
    assert all(0 <= entry["value"] <= entry["limit"] for entry in line["compared"].values())
    check = next(n for n in notes if n.get("phase") == "reference-check")
    # a restored snapshot and a carried state were in the served comparison
    assert check["served_prefix_hit_tokens"] == [0, 32, 32] and check["served_problems"] == []
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(line["metrics"]) <= want
    assert {"prefill_ms", "kv_pages_held_share", "decode_rows_useful_share", "tick_host_share", "device_decode_share",
            "device_prefill_share", "stage_prefill_share", "answer_decode_share"} <= set(line["metrics"])
