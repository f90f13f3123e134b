"""Checkpoint family ``deepseek_v2``: DeepSeek-V2 as the program runs it
through ``DeepseekV2Config`` (``sentio_tpu/models/deepseek_v2.py``) —
sequential pre-norm blocks, multi-head LATENT attention (a 576-wide latent a
token and layer in place of keys and values; prefill expands it to 128 heads'
keys and values, decode absorbs the expansion into query and output), a dense
SwiGLU in the leading layer and, in every later one, softmax-gated routed
experts picked inside the best 3 of 8 groups, unnormalised gates times 16,
two shared experts added whole; YaRN rotary on 64 dimensions; an untied head.
The whole contract of ``families/llama.py``'s docstring is here; what a reader
of this family needs beyond it:

THE DEPLOYMENT. One routed layer of the published model is 7.9 GB of bf16: no
chip holds two. The published grouping IS the deployment: ``n_group`` 8
devices share each layer, a device holds one group of 20 experts, and
``topk_group`` 3 bounds the devices a token is sent to. ONE of those chips is
what a cell runs: attention, norms, router and the shared experts whole (a
latent has no heads to split: data-parallel attention), group 0 of the routed
experts (the file's ``n_routed_experts`` is the experts HELD here, 20, listed
in ``reduced`` beside the depth and the vocabulary; ``n_routed_experts_router``
the router's published width, 160; ``expert_offset`` which slice), an eighth
of the vocabulary. The router keeps its 160 outputs, its 8 groups, its 3 and
its 6; the program computes the part of the routed sum its own experts give,
and the reference is given the same share. Nothing stands in for the seven
absent chips or their exchange.

ASSUMED (the configuration file lists the same): every value is the catalog's
copy of the published ``config.json``; ``torch_dtype`` bfloat16,
``n_routed_experts_router`` and ``expert_offset`` are the file's own keys;
weights are random from ``--seed``; the tokenizer is the program's
ByteTokenizer. The two shared experts are ONE SwiGLU of width 3072 in the
published code and two stacked experts of 1536, summed, here: the same
function. ``kv_b_proj`` is held split by head into ``w_uk`` and ``w_uv``
``[heads, 128, 512]``: the same matrix, row for row.

THE SEEDED TREE. The head is untied, so the dense family's rule carries over
whole: the head's columns for the tokenizer's 261 text ids are zero, no answer
ends early and every answer token is 3 bytes (``families/llama.py::
seeded_tree`` says why). The program's own seeded distributions are followed
(``models/deepseek_v2.py``: the query up-projection ``WQ_SCALE`` times as
large so that attention is peaked, ``WO_SCALE``; the router drawn over its
fan-in like any matrix — gates are ``s x 16`` with ``s`` a softmax over 160
and sum to 3 to 4 a token, the reasoning stands there).

THE CHECK'S DEPTH. ``check_config`` at 2 layers keeps layer 0 dense and ONE
routed layer (``first_k_dense_replace`` stays 1): both kinds of block, the
latent pool, both choices.

COSTS. A decode sub-step reads the held experts its advancing rows touch, not
all it holds; the advancing rows are bounded from below by ``context_tokens /
(pages x page)`` (``families/cohere2_moe.py`` says why a lower bound: a share
reads low, never high). ``latent_attention`` is ONE call of the latent
kernel (one layer of one sub-step): the latents of the tokens the rows hold,
1,152 B each, with the absorbed queries in and the latent outputs out, against
``heads x (576 + 512) x 2`` operations a token — the absorb and un-absorb
matmuls are NOT the kernel's and stand in ``decode_substep_cost``.
"""

from __future__ import annotations

import concurrent.futures
import os
from pathlib import Path

import numpy as np

from benchmark.families import llama as dense

BYTES_BF16 = dense.BYTES_BF16
REFERENCE = "benchmark.deepseek_v2_reference"
# what the forward decides by rank → the reference's keyword for how many it takes
CHOICES = {"groups": "topk_group", "experts": "experts_per_token"}

# published key → field of the program's config object (``DeepseekV2Config``)
WIDTHS = {
    "hidden_size": "dim", "intermediate_size": "mlp_dim", "moe_intermediate_size": "moe_mlp_dim",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps", "max_position_embeddings": "max_len",
    "first_k_dense_replace": "first_k_dense_replace", "moe_layer_freq": "moe_layer_freq",
    "n_routed_experts": "experts_held", "n_routed_experts_router": "n_experts",
    "expert_offset": "expert_offset", "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "n_shared_experts", "n_group": "n_group", "topk_group": "topk_group",
    "routed_scaling_factor": "routed_scaling_factor", "norm_topk_prob": "norm_topk_prob",
    "scoring_func": "gate_fn", "topk_method": "topk_method", "tie_word_embeddings": "tie_embeddings",
}


def program_config(model: dict) -> dict:
    """Published keys → ``DeepseekV2Config`` fields, every one."""
    rope = model["rope_scaling"]
    assert rope["type"] == "yarn", rope
    return dict(
        vocab_size=int(model["vocab_size"]), dim=int(model["hidden_size"]),
        n_layers=int(model["num_hidden_layers"]), n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]), mlp_dim=int(model["intermediate_size"]),
        max_len=int(model["max_position_embeddings"]), rope_theta=float(model["rope_theta"]),
        dtype=str(model.get("torch_dtype", "bfloat16")), norm_eps=float(model["rms_norm_eps"]),
        q_lora_rank=int(model["q_lora_rank"]), kv_lora_rank=int(model["kv_lora_rank"]),
        qk_nope_head_dim=int(model["qk_nope_head_dim"]), qk_rope_head_dim=int(model["qk_rope_head_dim"]),
        v_head_dim=int(model["v_head_dim"]), rope_kind=str(rope["type"]), rope_factor=float(rope["factor"]),
        rope_original_max_len=int(rope["original_max_position_embeddings"]),
        rope_beta_fast=float(rope["beta_fast"]), rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]), rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        first_k_dense_replace=int(model["first_k_dense_replace"]), moe_layer_freq=int(model["moe_layer_freq"]),
        moe_mlp_dim=int(model["moe_intermediate_size"]), n_experts=int(model["n_routed_experts_router"]),
        experts_per_token=int(model["num_experts_per_tok"]), n_shared_experts=int(model["n_shared_experts"]),
        gate_fn=str(model["scoring_func"]), n_group=int(model["n_group"]), topk_group=int(model["topk_group"]),
        topk_method=str(model["topk_method"]), norm_topk_prob=bool(model["norm_topk_prob"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]), shared_combine="sum",
        tie_embeddings=bool(model["tie_word_embeddings"]),
        experts_held=int(model["n_routed_experts"]), expert_offset=int(model["expert_offset"]),
    )


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.deepseek_v2 import DeepseekV2Config

    return DeepseekV2Config(**{**program_config(model), "n_layers": layers, "max_len": max_len})


# ------------------------------------------------------------ seeded weights


def leaf_shapes(cfg: dict) -> dict:
    """``{path: (shape, std)}`` of every matrix of the program's
    ``init_deepseek_v2`` tree; a stack of experts is listed expert by expert
    (``(..., index)``), so that the largest leaves fill in parallel."""
    from sentio_tpu.models.deepseek_v2 import WO_SCALE, WQ_SCALE

    d, h, r = cfg["dim"], cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, vd, ql = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"]
    out = {("embed_tokens", "embedding"): ((cfg["vocab_size"], d), 0.02),
           ("lm_head", "kernel"): ((d, cfg["vocab_size"]), d ** -0.5)}
    for i in range(cfg["n_layers"]):
        layer = f"layers_{i}"
        out[(layer, "attn", "wq_a", "kernel")] = ((d, ql), d ** -0.5)
        out[(layer, "attn", "wq_b", "kernel")] = ((ql, h * (nope + rope)), WQ_SCALE * ql ** -0.5)
        out[(layer, "attn", "wkv_a", "kernel")] = ((d, r + rope), d ** -0.5)
        out[(layer, "attn", "w_uk")] = ((h, nope, r), r ** -0.5)
        out[(layer, "attn", "w_uv")] = ((h, vd, r), r ** -0.5)
        out[(layer, "attn", "wo", "kernel")] = ((h * vd, d), WO_SCALE * (h * vd) ** -0.5)
        if i < cfg["first_k_dense_replace"]:
            f = cfg["mlp_dim"]
            for name, (n_in, n_out) in {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}.items():
                out[(layer, "mlp", name, "kernel")] = ((n_in, n_out), n_in ** -0.5)
            continue
        f = cfg["moe_mlp_dim"]
        out[(layer, "moe", "router", "kernel")] = ((d, cfg["n_experts"]), d ** -0.5)
        for where, count in ((("moe",), cfg["experts_held"]), (("moe", "shared"), cfg["n_shared_experts"])):
            for name, (n_in, n_out) in {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}.items():
                for e in range(count):
                    out[(layer, *where, name, e)] = ((n_in, n_out), n_in ** -0.5)
    return out


def make_params(model: dict, seed: int) -> dict:
    """The tree of the program's ``init_deepseek_v2`` in bf16 from ``seed``:
    one generator a matrix (an expert of a stack is one), all filled in
    parallel, so the tree depends on the seed alone. The head's columns for
    the tokenizer's text ids are zero (``families/llama.py::seeded_tree``)."""
    cfg = program_config(model)
    shapes = leaf_shapes(cfg)
    seeds = np.random.SeedSequence(seed).spawn(len(shapes))
    with concurrent.futures.ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as pool:
        filled = list(pool.map(
            lambda job: dense.normal_bf16(np.random.default_rng(job[0]), *job[1]), zip(seeds, shapes.values())))
    tree: dict = {}
    stacks: dict = {}

    def put(path, leaf):
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf

    for path, leaf in zip(shapes, filled):
        if isinstance(path[-1], int):
            stacks.setdefault(path[:-1], []).append(leaf)
        else:
            put(path, leaf)
    for path, leaves in stacks.items():
        put(path, np.stack(leaves))
    ones = lambda n: {"scale": np.ones((n,), np.float32)}  # noqa: E731
    tree["final_norm"] = ones(cfg["dim"])
    for i in range(cfg["n_layers"]):
        layer = tree[f"layers_{i}"]
        layer["attn_norm"], layer["mlp_norm"] = ones(cfg["dim"]), ones(cfg["dim"])
        layer["attn"]["q_norm"], layer["attn"]["kv_norm"] = ones(cfg["q_lora_rank"]), ones(cfg["kv_lora_rank"])
    tree["lm_head"]["kernel"][:, : dense.TEXT_IDS] = 0
    return tree


def write_checkpoint(path: Path, model: dict, seed: int) -> None:
    from sentio_tpu.runtime.checkpoint import save_pytree

    save_pytree(path, make_params(model, seed),
                meta={"family": "deepseek_v2", "config": program_config(model)})


# ------------------------------------------------ bytes and operations


def latent_dim(model: dict) -> int:
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def kv_bytes_per_token(model: dict) -> int:
    """What one token leaves in the pool over all layers: ONE latent a layer
    (``c_kv | k_pe``, 576 numbers, 1,152 B of bf16) and nothing per head."""
    return latent_dim(model) * BYTES_BF16 * model["num_hidden_layers"]


def pool_bytes(model: dict, env: dict) -> int:
    """The latent pool the server's environment asks for: the scratch page
    and ``slots x pages`` more, ``page`` tokens each, one latent a layer."""
    pages = 1 + int(env["LLM_MAX_BATCH"]) * int(env["KV_MAX_PAGES_PER_SEQ"])
    return pages * int(env["KV_PAGE_SIZE"]) * kv_bytes_per_token(model)


def weight_params(model: dict) -> dict:
    """Parameters: a layer's attention, the dense layer's MLP, the router, ONE
    expert, one table (embedding or head: untied, two of them)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope, vd = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    ql, r = model["q_lora_rank"], model["kv_lora_rank"]
    attention = d * ql + ql * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) + h * vd * d
    return {"attention": attention, "dense_mlp": 3 * d * model["intermediate_size"],
            "router": d * model["n_routed_experts_router"], "expert": 3 * d * model["moe_intermediate_size"],
            "table": model["vocab_size"] * d}


def rows_advancing(model: dict, context_tokens: float) -> float:
    """At least this many rows hold ``context_tokens``: none holds more than
    its page table (``families/cohere2_moe.py`` says why a lower bound)."""
    env = model["serve_env"]
    return context_tokens / (int(env["KV_MAX_PAGES_PER_SEQ"]) * int(env["KV_PAGE_SIZE"]))


def experts_touched(model: dict, rows: float) -> float:
    """Of the experts held, those ``rows`` tokens reach, each picking
    ``num_experts_per_tok`` of the router's experts evenly: held x (1 - (1 -
    k/E)^rows). (Group-limited picks are not independent; over many tokens
    the share of pairs a group sees is the same.)"""
    share = model["num_experts_per_tok"] / model["n_routed_experts_router"]
    return model["n_routed_experts"] * (1.0 - (1.0 - share) ** rows)


def attention_ops_per_token(model: dict) -> int:
    """Operations the ABSORBED attention spends on one pooled token in one
    layer: every head's score over latent and rotated key, and its output
    over the latent — heads x (576 + 512) x 2."""
    return 2 * model["num_attention_heads"] * (latent_dim(model) + model["kv_lora_rank"])


def decode_substep_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """One decode sub-step: every layer's attention matrices (the absorb and
    un-absorb halves of ``kv_b_proj`` among them), the dense layers' MLP, the
    routed layers' router, shared experts and the held experts the advancing
    rows touch, the head once (the embedding is a gather of ``rows`` rows),
    and the latents the attention reads. 2 operations per multiply-add of
    every matmul an advancing row goes through — of its picks the share held
    here — plus the absorbed attention over the context."""
    w, n_layers, n_dense = weight_params(model), model["num_hidden_layers"], model["first_k_dense_replace"]
    n = rows_advancing(model, context_tokens)
    shared = model["n_shared_experts"]
    routed_layer = w["router"] + (shared + experts_touched(model, n)) * w["expert"]
    weights = (n_layers * w["attention"] + n_dense * w["dense_mlp"] + (n_layers - n_dense) * routed_layer
               + w["table"])
    bytes_ = (BYTES_BF16 * (weights + rows * model["hidden_size"])
              + context_tokens * kv_bytes_per_token(model))
    held = model["num_experts_per_tok"] * model["n_routed_experts"] / model["n_routed_experts_router"]
    row = (n_layers * w["attention"] + n_dense * w["dense_mlp"]
           + (n_layers - n_dense) * (w["router"] + (shared + held) * w["expert"]) + w["table"])
    attn = context_tokens * attention_ops_per_token(model) * n_layers
    return {"bytes": float(bytes_), "flops": float(2 * n * row + attn)}


def latent_attention_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the latent decode kernel (one layer of one sub-step): the
    latents of the tokens the rows HOLD, the advancing rows' absorbed queries
    in (heads x 576) and latent outputs out (heads x 512); scores and outputs
    of every head over every held token."""
    n = rows_advancing(model, context_tokens)
    io = n * model["num_attention_heads"] * (latent_dim(model) + model["kv_lora_rank"])
    return {"bytes": float(BYTES_BF16 * (context_tokens * latent_dim(model) + io)),
            "flops": float(context_tokens * attention_ops_per_token(model))}


# the grouped expert matmul has no entry: half of this cell's decode calls route
# nothing, and a share of ONE call's roofline over the MEDIAN call has nothing
# to hold it against (PERF.md, Open questions: the stat needs totals)
KERNEL_COSTS = {"latent_attention": latent_attention_cost}


# ------------------------------------------------------ the reference check


def init_params(key, cfg) -> dict:
    """The tree of the program's ``init_deepseek_v2`` (its shapes are asked of
    it) with the program's distributions, every leaf drawn in ONE call (the
    program draws a stack expert by expert). The head's text columns are NOT
    zeroed here, as in the dense family's check: the check draws its token ids
    from the whole vocabulary, and ``check.py``'s int8 variant scales a matrix
    by its columns' largest values — a zero column would make it 0 / 0."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.deepseek_v2 import WO_SCALE, WQ_SCALE, init_deepseek_v2

    paths, tree = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda k: init_deepseek_v2(k, cfg), key))
    scales = {"wq_b": WQ_SCALE, "wo": WO_SCALE}
    leaves = []
    for (path, leaf), k in zip(paths, jax.random.split(key, len(paths))):
        names = [p.key for p in path]
        if names[-1] == "scale":
            leaves.append(jnp.ones(leaf.shape, leaf.dtype))
        elif names[-1] == "embedding":
            leaves.append(jax.random.normal(k, leaf.shape, leaf.dtype) * 0.02)
        else:  # a matrix or a stack of them: truncated normal over the fan-in
            fan_in = leaf.shape[-1] if names[-1] in ("w_uk", "w_uv") else leaf.shape[-2]
            leaves.append(jax.random.truncated_normal(k, -2.0, 2.0, leaf.shape, leaf.dtype)
                          * scales.get(names[-2], 1.0) * fan_in ** -0.5)
    return jax.tree_util.tree_unflatten(tree, leaves)


def is_matrix(leaf) -> bool:
    """Matrices and stacks of them: all bf16 in a checkpoint; norm scales float32."""
    return leaf.ndim >= 2


def reference_kwargs(model: dict) -> dict:
    """What the reference takes from the configuration, the share of the
    experts the program holds among it."""
    cfg = program_config(model)
    keys = ("n_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
            "rope_factor", "rope_original_max_len", "rope_beta_fast", "rope_beta_slow", "rope_mscale",
            "rope_mscale_all_dim", "norm_eps", "n_group", "topk_group", "experts_per_token",
            "routed_scaling_factor", "norm_topk_prob", "experts_held", "expert_offset")
    return {key: cfg[key] for key in keys}


def reference_params(tree: dict, n_layers: int) -> dict:
    """The program's tree under the reference's flat names, every matrix in
    the checkpoint's own bf16: the reference widens one where it uses it."""
    out = {"embed": np.asarray(tree["embed_tokens"]["embedding"]), "head": np.asarray(tree["lm_head"]["kernel"]),
           "final_norm": np.asarray(tree["final_norm"]["scale"], np.float32), "layers": []}
    for i in range(n_layers):
        lp = tree[f"layers_{i}"]
        attn = lp["attn"]
        layer = {
            "attn_norm": np.asarray(lp["attn_norm"]["scale"], np.float32),
            "mlp_norm": np.asarray(lp["mlp_norm"]["scale"], np.float32),
            "q_norm": np.asarray(attn["q_norm"]["scale"], np.float32),
            "kv_norm": np.asarray(attn["kv_norm"]["scale"], np.float32),
            **{k: np.asarray(attn[k]["kernel"]) for k in ("wq_a", "wq_b", "wkv_a", "wo")},
            "w_uk": np.asarray(attn["w_uk"]), "w_uv": np.asarray(attn["w_uv"])}
        if "moe" in lp:
            moe = lp["moe"]
            layer.update({"router": np.asarray(moe["router"]["kernel"]),
                          **{k: np.asarray(moe[k]) for k in ("w_gate", "w_up", "w_down")},
                          **{f"shared_{k[2:]}": np.asarray(moe["shared"][k]) for k in ("w_gate", "w_up", "w_down")}})
        else:
            layer.update({k: np.asarray(lp["mlp"][k]["kernel"]) for k in ("w_gate", "w_up", "w_down")})
        out["layers"].append(layer)
    return out


def _chosen(routed: dict) -> dict:
    return {name: routed[name] for name in CHOICES}


def paged_pieces(engine, cfg, rows: int, width: int):
    """→ ``(state, prefill, decode)`` as ``families/llama.py`` has them. The
    state is the engine's LATENT pool (one array; there is no V). Each piece
    returns ``(logits, state, {"groups": ..., "experts": ...})`` — the picks
    the program's own expert layers hand back (prefill ``[routed layers, rows,
    width, k]``, decode ``[routed layers, rows, k]``). Prefill is the
    admission forward (expanded attention) into a fresh latent cache and
    ``scatter_prefill`` of it; decode is ``paged_decode_forward`` (absorbed
    attention) over the pool with the engine's own kernel selection."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.deepseek_v2 import init_latent_cache
    from sentio_tpu.runtime.paged import paged_decode_forward, scatter_prefill

    forward_fn, attn_impl = engine.forward_fn, engine._attn_impl

    @jax.jit
    def prefill(params, ids, positions, lens, blocks, pages):
        pad = jnp.arange(width)[None, :] < lens[:, None]
        logits, cache, routed = forward_fn(params, cfg, ids, positions=positions,
                                           cache=init_latent_cache(cfg, rows, width), cache_index=0, pad_mask=pad)
        return logits, scatter_prefill(pages, None, cache["k"], None, blocks)[0], _chosen(routed)

    @jax.jit
    def decode(params, tok, lens, table, pages):
        logits, pages, _none, routed = paged_decode_forward(
            params, cfg, tok, lens, table, pages, None, attn_impl=attn_impl, return_routed=True)
        return logits, pages, _chosen(routed)

    return engine.pool.k, prefill, decode


def served(engine, prompts, max_new_tokens):
    """The requests through ``engine.run_all`` → (results, each request's own
    picks ``{"groups": [routed layers, prompt + answer tokens - 1, 3],
    "experts": [..., 6]}``, negative where the radix cache served the
    position)."""
    results = engine.run_all(prompts, max_new_tokens=max_new_tokens, return_choices=True)
    return results, [r.choices for r in results]
