"""Checkpoint family ``cohere2_moe``: Command A+ as the program runs it
through ``Cohere2MoeConfig`` (``sentio_tpu/models/cohere2_moe.py``) — a
parallel block under one mean-centred LayerNorm, attention wider than the
hidden size, sliding-window layers with interleaved rotary beside
rotation-free full-attention layers, sigmoid-gated routed experts, averaged
shared experts, a head tied to the embedding. The whole contract of
``families/llama.py``'s docstring is here; what a reader of this family needs
beyond it:

THE DEPLOYMENT. One layer of the published model is 13.6 GB of bf16: no chip
holds one. The configuration states eight chips that share each layer:
attention, router, norm and the shared experts on every chip (data-parallel
attention), the 128 routed experts split eight ways (expert parallelism), the
vocabulary split eight ways. ONE of those chips is what a cell runs: the
file's ``num_experts`` is the experts HELD here (16, listed in ``reduced``
beside the depth and the vocabulary), ``num_experts_router`` the router's
published width (128), ``expert_offset`` which slice. The router keeps its
128 outputs and its 8 a token; the program computes the part of the routed
sum its own experts give and leaves the rest out, and the reference is given
the same share (``experts_held`` / ``expert_offset`` among its keywords).
Nothing stands in for the seven absent chips or their exchange.

ASSUMED (the configuration file and the reference's docstring list them):
(1) ``shared_expert_combination_strategy: "average"`` — the shared experts'
outputs are averaged and the average is ADDED to the routed sum; (2) no
selection bias and no routed scaling factor, since the config names none;
(3) ``intermediate_size`` is the width of one routed and of one shared expert.

THE SEEDED TREE AND A TIED HEAD. No answer may end early and every answer
token must render as 3 bytes, for every seed (``families/llama.py::
seeded_tree`` says why: the mix's prompt lengths, hence its compiled
programs). The dense family zeroes the head's columns for the tokenizer's 261
text ids; here the head IS the embedding, and zero rows would be zero prompt
embeddings. So the text ids' ROWS are scaled by ``TEXT_ROW_SCALE`` (a
quarter). As an input such a row meets the LayerNorm first, which restores
part of its size; as a head row its logit is a quarter of another row's —
N(0, 0.08²) where the largest of the other 32,507 logits is near 1.3 — so a
greedy answer never holds a text id (``tests/benchmark/
test_benchmark_command_a.py`` holds it over seeds; the reference check reads
the same rows). AND ANSWERS THAT DO NOT COLLAPSE: with a tied head and random
weights at the usual sizes a greedy answer repeats one token, every row the
same one, and the routed experts see one token (a chip run read 1.3 of 16
held experts touched a step where even routing gives 10.7). The program's
seeded distributions, which this family follows, draw the embedding a quarter
as large and the query projection four times as large, so that attention is
peaked and not the context's average (``models/cohere2_moe.py::EMBED_STD``,
``WQ_SCALE``, with the arithmetic).

THE CHECK'S DEPTH. ``check_config`` at fewer layers than a period keeps the
period's LAST layers: two layers are one sliding and one full.

COSTS. A decode sub-step reads the experts its advancing rows touch, not all
it holds. The rows that advance are not among a cost function's arguments
(``rows`` is every slot), so they are bounded from below by ``context_tokens
/ (pages x page)`` — no row holds more than its table — and the expected
experts touched under even routing taken at that count: the bytes the
mathematics NEEDS are then a little under the truth and a share reads low,
never high. The Pallas grouped matmul is the kernel ``expert_mlp`` names: ONE
call is one of a layer's three expert matmuls.
"""

from __future__ import annotations

import concurrent.futures
import os
from pathlib import Path

import numpy as np

from benchmark.families import llama as dense

BYTES_BF16 = dense.BYTES_BF16
REFERENCE = "benchmark.command_a_reference"
# what the forward decides by rank → the reference's keyword for how many it takes
CHOICES = {"experts": "experts_per_token"}
TEXT_ROW_SCALE = 0.25

# published key → field of the program's config object (``Cohere2MoeConfig``)
WIDTHS = {
    "hidden_size": "dim", "intermediate_size": "mlp_dim", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim", "vocab_size": "vocab_size",
    "num_hidden_layers": "n_layers", "rope_theta": "rope_theta", "layer_norm_eps": "norm_eps",
    "max_position_embeddings": "max_len", "sliding_window": "sliding_window",
    "logit_scale": "logit_scale", "num_experts": "experts_held",
    "num_experts_router": "n_experts", "expert_offset": "expert_offset",
    "num_experts_per_tok": "experts_per_token", "num_shared_experts": "n_shared_experts",
}
ROPE_KINDS = {"rope_gptj": "interleaved"}


def layer_kinds(model: dict, layers: int) -> list[str]:
    """The kinds of ``layers`` layers: the published pattern from its start,
    or — fewer than a period — the period's LAST layers, so that every kind
    is there (two layers: one sliding, one full)."""
    kinds, period = list(model["layer_types"]), int(model["layer_switch"])
    return kinds[:period][-layers:] if layers < period else kinds[:layers]


def program_config(model: dict) -> dict:
    """Published keys → ``Cohere2MoeConfig`` fields, every one."""
    layers = int(model["num_hidden_layers"])
    return dict(
        vocab_size=int(model["vocab_size"]), dim=int(model["hidden_size"]), n_layers=layers,
        n_heads=int(model["num_attention_heads"]), n_kv_heads=int(model["num_key_value_heads"]),
        mlp_dim=int(model["intermediate_size"]), max_len=int(model["max_position_embeddings"]),
        rope_theta=float(model["rope_theta"]), dtype=str(model.get("torch_dtype", "bfloat16")),
        norm_eps=float(model["layer_norm_eps"]), head_dim=int(model["head_dim"]),
        norm_kind="layernorm", parallel_block=bool(model["use_parallel_block"]),
        layer_kinds=",".join(layer_kinds(model, layers)),
        sliding_window=int(model["sliding_window"]),
        rope_kind=ROPE_KINDS[model["position_embedding_type"]],
        logit_scale=float(model["logit_scale"]), tie_embeddings=bool(model["tie_word_embeddings"]),
        n_experts=int(model["num_experts_router"]),
        experts_per_token=int(model["num_experts_per_tok"]),
        n_shared_experts=int(model["num_shared_experts"]), gate_fn=str(model["expert_selection_fn"]),
        experts_held=int(model["num_experts"]), expert_offset=int(model["expert_offset"]),
    )


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.cohere2_moe import Cohere2MoeConfig

    return Cohere2MoeConfig(**{**program_config({**model, "num_hidden_layers": layers}),
                               "max_len": max_len})


# ------------------------------------------------------------ seeded weights


def leaf_shapes(cfg: dict) -> dict:
    """``{path: (shape, std)}`` of every matrix of the program's
    ``init_cohere2_moe`` tree; a stack of experts is listed expert by expert
    (``(..., index)``), so that the largest leaves fill in parallel."""
    from sentio_tpu.models.cohere2_moe import EMBED_STD, WO_SCALE, WQ_SCALE

    dim, mlp = cfg["dim"], cfg["mlp_dim"]
    q, kv = cfg["n_heads"] * cfg["head_dim"], cfg["n_kv_heads"] * cfg["head_dim"]
    out = {("embed_tokens", "embedding"): ((cfg["vocab_size"], dim), EMBED_STD)}
    for i in range(cfg["n_layers"]):
        layer = f"layers_{i}"
        for name, (n_in, n_out) in {"wk": (dim, kv), "wv": (dim, kv)}.items():
            out[(layer, "attn", name, "kernel")] = ((n_in, n_out), n_in ** -0.5)
        out[(layer, "attn", "wq", "kernel")] = ((dim, q), WQ_SCALE * dim ** -0.5)
        out[(layer, "attn", "wo", "kernel")] = ((q, dim), WO_SCALE * q ** -0.5)
        out[(layer, "moe", "router", "kernel")] = ((dim, cfg["n_experts"]), dim ** -0.5)
        for where, count in ((("moe",), cfg["experts_held"]), (("moe", "shared"), cfg["n_shared_experts"])):
            for name, (n_in, n_out) in {"w_gate": (dim, mlp), "w_up": (dim, mlp), "w_down": (mlp, dim)}.items():
                for e in range(count):
                    out[(layer, *where, name, e)] = ((n_in, n_out), n_in ** -0.5)
    return out


def make_params(model: dict, seed: int) -> dict:
    """The tree of the program's ``init_cohere2_moe`` in bf16 from ``seed``:
    one generator a matrix (an expert of a stack is one), all filled in
    parallel, so the tree depends on the seed alone. The text ids' rows of
    the embedding — which is the head too — are scaled by ``TEXT_ROW_SCALE``."""
    cfg = program_config(model)
    shapes = leaf_shapes(cfg)
    seeds = np.random.SeedSequence(seed).spawn(len(shapes))
    with concurrent.futures.ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as pool:
        filled = list(pool.map(
            lambda job: dense.normal_bf16(np.random.default_rng(job[0]), *job[1]), zip(seeds, shapes.values())))
    ones = lambda: {"scale": np.ones((cfg["dim"],), np.float32)}  # noqa: E731
    tree: dict = {"final_norm": ones()}
    stacks: dict = {}
    for path, leaf in zip(shapes, filled):
        if isinstance(path[-1], int):
            stacks.setdefault(path[:-1], []).append(leaf)
            continue
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    for path, leaves in stacks.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = np.stack(leaves)
    for i in range(cfg["n_layers"]):
        tree[f"layers_{i}"]["norm"] = ones()
    table = tree["embed_tokens"]["embedding"]
    table[: dense.TEXT_IDS] = (table[: dense.TEXT_IDS].astype(np.float32) * TEXT_ROW_SCALE).astype(table.dtype)
    return tree


def write_checkpoint(path: Path, model: dict, seed: int) -> None:
    from sentio_tpu.runtime.checkpoint import save_pytree

    save_pytree(path, make_params(model, seed),
                meta={"family": "cohere2_moe", "config": program_config(model)})


# ------------------------------------------------ bytes and operations

pool_bytes = dense.pool_bytes              # K and V of every layer, the dense family's pool
kv_bytes_per_token = dense.kv_bytes_per_token


def weight_params(model: dict) -> dict:
    """Parameters: a layer's attention and router, ONE expert, the table."""
    d, f = model["hidden_size"], model["intermediate_size"]
    q, kv = (model[k] * model["head_dim"] for k in ("num_attention_heads", "num_key_value_heads"))
    return {"attention": d * q + 2 * d * kv + q * d, "router": d * model["num_experts_router"],
            "expert": 3 * d * f, "table": model["vocab_size"] * d}


def rows_advancing(model: dict, context_tokens: float) -> float:
    """At least this many rows hold ``context_tokens``: none holds more than
    its page table (the module docstring says why a lower bound)."""
    env = model["serve_env"]
    return context_tokens / (int(env["KV_MAX_PAGES_PER_SEQ"]) * int(env["KV_PAGE_SIZE"]))


def experts_touched(model: dict, rows: float) -> float:
    """Of the experts held, those ``rows`` tokens reach, each picking
    ``num_experts_per_tok`` of the router's ``num_experts_router`` evenly:
    held x (1 - (1 - k/E)^rows)."""
    share = model["num_experts_per_tok"] / model["num_experts_router"]
    return model["num_experts"] * (1.0 - (1.0 - share) ** rows)


def keys_seen(model: dict, context_tokens: float) -> float:
    """Tokens of K and V a layer's decode attention reads, the mean over the
    layer kinds: all the context in a full layer; in a sliding layer all of
    it where no table outgrows the window, else at least one window's worth."""
    kinds = layer_kinds(model, model["num_hidden_layers"])
    env, window = model["serve_env"], model["sliding_window"]
    fits = int(env["KV_MAX_PAGES_PER_SEQ"]) * int(env["KV_PAGE_SIZE"]) <= window
    sliding = context_tokens if fits else min(context_tokens, window)
    n_sliding = sum(kind == "sliding_attention" for kind in kinds)
    return (n_sliding * sliding + (len(kinds) - n_sliding) * context_tokens) / len(kinds)


def decode_substep_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """One decode sub-step: every layer's attention matrices, router and
    shared experts, the held experts the advancing rows touch, the table
    once (as the head; as the embedding it is a gather of ``rows`` rows), and
    the K and V the attention reads. 2 operations per multiply-add of every
    matmul an advancing row goes through — of its ``num_experts_per_tok``
    picks the share held here — plus QK and PV over the keys seen."""
    w, n_layers = weight_params(model), model["num_hidden_layers"]
    n = rows_advancing(model, context_tokens)
    layer = (w["attention"] + w["router"]
             + (model["num_shared_experts"] + experts_touched(model, n)) * w["expert"])
    seen = keys_seen(model, context_tokens)
    bytes_ = (BYTES_BF16 * (n_layers * layer + w["table"] + rows * model["hidden_size"])
              + seen * kv_bytes_per_token(model))
    held = model["num_experts_per_tok"] * model["num_experts"] / model["num_experts_router"]
    row = n_layers * (w["attention"] + w["router"]
                      + (model["num_shared_experts"] + held) * w["expert"]) + w["table"]
    attn = 4 * seen * model["num_attention_heads"] * model["head_dim"] * n_layers
    return {"bytes": float(bytes_), "flops": float(2 * n * row + attn)}


def paged_attention_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the decode attention kernel (one layer of one sub-step),
    the mean over the layer kinds: K and V of the keys seen, QK and PV."""
    seen = keys_seen(model, context_tokens)
    q = model["num_attention_heads"] * model["head_dim"]
    return {"bytes": float(seen * kv_bytes_per_token(model) / model["num_hidden_layers"]),
            "flops": float(4 * seen * q)}


def expert_mlp_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the grouped expert matmul in a decode sub-step — one of a
    layer's three (gate, up, down: ``hidden x expert width`` each): the
    matrices of the held experts the advancing rows touch, 2 operations per
    multiply-add of the pairs routed here. The pairs' rows in and out (a few
    hundred vectors) are left out: the share reads a little low."""
    n = rows_advancing(model, context_tokens)
    matrix = model["hidden_size"] * model["intermediate_size"]
    pairs = n * model["num_experts_per_tok"] * model["num_experts"] / model["num_experts_router"]
    return {"bytes": float(BYTES_BF16 * experts_touched(model, n) * matrix),
            "flops": float(2 * pairs * matrix)}


KERNEL_COSTS = {"paged_attention": paged_attention_cost, "expert_mlp": expert_mlp_cost}


# ------------------------------------------------------ the reference check


def init_params(key, cfg) -> dict:
    """The tree of the program's ``init_cohere2_moe`` (its shapes are asked of
    it) with the program's distributions, every leaf drawn in ONE call (the
    program draws a stack expert by expert), and the text ids' rows scaled as
    ``make_params`` scales them: the check reads what a cell serves."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.cohere2_moe import EMBED_STD, WO_SCALE, WQ_SCALE, init_cohere2_moe

    paths, tree = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda k: init_cohere2_moe(k, cfg), key))
    leaves = []
    for (path, leaf), k in zip(paths, jax.random.split(key, len(paths))):
        if path[-1].key == "scale":
            leaves.append(jnp.ones(leaf.shape, leaf.dtype))
        elif path[-1].key == "embedding":
            rows = jnp.where(jnp.arange(leaf.shape[0]) < dense.TEXT_IDS, TEXT_ROW_SCALE, 1.0)
            leaves.append(jax.random.normal(k, leaf.shape, leaf.dtype) * EMBED_STD * rows[:, None])
        else:  # a matrix or a stack of them: truncated normal over the fan-in
            scale = {"wq": WQ_SCALE, "wo": WO_SCALE}.get(path[-2].key, 1.0)
            leaves.append(jax.random.truncated_normal(k, -2.0, 2.0, leaf.shape, leaf.dtype)
                          * scale * leaf.shape[-2] ** -0.5)
    return jax.tree_util.tree_unflatten(tree, leaves)


def is_matrix(leaf) -> bool:
    """Matrices and stacks of them: all bf16 in a checkpoint; norm scales float32."""
    return leaf.ndim >= 2


def reference_kwargs(model: dict) -> dict:
    """What the reference takes from the configuration — the layer kinds at
    the CHECK's depth, and the share of the experts the program holds."""
    return dict(
        n_heads=int(model["num_attention_heads"]), n_kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]), rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["layer_norm_eps"]),
        layer_types=tuple(layer_kinds(model, int(model["check"]["layers"]))),
        sliding_window=int(model["sliding_window"]),
        experts_per_token=int(model["num_experts_per_tok"]), experts_held=int(model["num_experts"]),
        expert_offset=int(model["expert_offset"]), logit_scale=float(model["logit_scale"]))


def reference_params(tree: dict, n_layers: int) -> dict:
    """The program's tree under the reference's flat names, every matrix in
    the checkpoint's own bf16: the reference widens one where it uses it (two
    layers are 2.3 B parameters — 9.2 GB as float32 beside the program's)."""
    out = {"embed": np.asarray(tree["embed_tokens"]["embedding"]),
           "final_norm": np.asarray(tree["final_norm"]["scale"], np.float32), "layers": []}
    for i in range(n_layers):
        lp = tree[f"layers_{i}"]
        moe = lp["moe"]
        out["layers"].append({
            "norm": np.asarray(lp["norm"]["scale"], np.float32),
            **{k: np.asarray(lp["attn"][k]["kernel"]) for k in ("wq", "wk", "wv", "wo")},
            "router": np.asarray(moe["router"]["kernel"]),
            **{k: np.asarray(moe[k]) for k in ("w_gate", "w_up", "w_down")},
            **{f"shared_{k[2:]}": np.asarray(moe["shared"][k]) for k in ("w_gate", "w_up", "w_down")}})
    return out


def paged_pieces(engine, cfg, rows: int, width: int):
    """→ ``(state, prefill, decode)`` as ``families/llama.py`` has them, each
    piece returning ``(logits, state, {"experts": picks})`` — the picks the
    program's own expert layers hand back (prefill ``[layers, rows, width,
    k]``, decode ``[layers, rows, k]``), not a routing computed beside them."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.llama import init_cache
    from sentio_tpu.runtime.paged import paged_decode_forward, scatter_prefill

    forward_fn, attn_impl = engine.forward_fn, engine._attn_impl

    @jax.jit
    def prefill(params, ids, positions, lens, blocks, state):
        k_pages, v_pages = state
        pad = jnp.arange(width)[None, :] < lens[:, None]
        logits, cache, routed = forward_fn(params, cfg, ids, positions=positions,
                                           cache=init_cache(cfg, rows, width), cache_index=0, pad_mask=pad)
        return (logits, scatter_prefill(k_pages, v_pages, cache["k"], cache["v"], blocks),
                {"experts": routed["experts"]})

    @jax.jit
    def decode(params, tok, lens, table, state):
        k_pages, v_pages = state
        logits, k_pages, v_pages, routed = paged_decode_forward(
            params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=attn_impl, return_routed=True)
        return logits, (k_pages, v_pages), {"experts": routed["experts"]}

    return (engine.pool.k, engine.pool.v), prefill, decode


def served(engine, prompts, max_new_tokens):
    """The requests through ``engine.run_all`` → (results, each request's own
    picks ``{"experts": [layers, prompt + answer tokens - 1, k]}``, negative
    where the radix cache served the position)."""
    results = engine.run_all(prompts, max_new_tokens=max_new_tokens, return_choices=True)
    return results, [r.choices for r in results]
