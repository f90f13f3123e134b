"""Checkpoint family ``mellum``: Mellum2-12B-A2.5B-Instruct as the program runs
it through ``MellumConfig`` (``sentio_tpu/models/mellum.py``) — a sequential
pre-norm block under RMSNorm, attention wider than the hidden size (32 query /
4 KV heads of 128 at hidden 2,304), three sliding-window layers of 1,024 keys
to one full layer, rotate-half rotary on every layer with YaRN's frequencies
and attention factor on the FULL layers alone, 64 routed experts 896 wide
picked eight a token by a softmax renormalised over the picks, no shared
expert, a head untied from the embedding. The whole contract of
``families/llama.py``'s docstring is here; what a reader of this family needs
beyond it:

THE DEPLOYMENT. A layer is 0.835 GB of bf16, so a chip holds every expert of a
layer and no layer is shared between chips; the whole model (24.3 GB) is a
PIPELINE of three v5e chips, stages of 12, 12 and 4 layers. The cell runs the
first stage — three whole periods of the pattern, nine sliding layers and three
full ones — with the table, the final norm and the head, so that it serves
whole answers. ``num_experts`` is all 64 and the program's ``experts_held``
equals it: the expert layer IS the model's.

ASSUMED (the configuration file and the reference's docstring list them): no
RMSNorm on q or k and no MTP head (the config has a key for neither); no router
bias and no routed scaling factor; ``intermediate_size`` sizes nothing (every
``mlp_layer_types`` entry is ``sparse``); ``rope_parameters.full_attention`` is
transformers' ``yarn`` with cos and sin times ``attention_factor``.

THE SEEDED TREE. The head is untied, so its COLUMNS for the tokenizer's 261
text ids are a quarter as large (``TEXT_COL_SCALE``): a text id's logit is
N(0, 0.25^2) where the largest of the other 98k is over 4, so no answer ends
early on EOS and every answer token renders as 3 bytes, for every seed
(``families/llama.py::seeded_tree`` says why that matters; the dense family
zeroes the columns, and ``check.py --variant weights_int8`` then has no column
maximum to scale by: it read NaN here on the chip). The embedding keeps its
usual size. Attention is drawn peaked (``models/mellum.py::WQ_SCALE``,
``WO_SCALE``) so that the window and the rotary of a layer's kind are visible
to the reference check — and bf16 then reads 3.5 % against the float32
reference where a flat attention reads 1 (the configuration's
``tolerances_why``).

THE CHECK'S DEPTH. ``check_config`` at fewer layers than a period keeps the
period's LAST layers: two layers are one sliding and one full.

COSTS, each a LOWER bound of what the timed call does (a share over 100 would
say the count is too high). A decode sub-step reads the experts its advancing
rows touch, not all 64; the rows are bounded from below by ``context_tokens /
(pages x page)`` as in ``families/cohere2_moe.py``. Here the window BITES: a
row holds up to 5,120 tokens and a sliding layer reads 1,024 of them, so the
keys a sliding layer's call sees are bounded from below by ``context x window
/ capacity`` (no row holds more than its table, so ``sum min(len, window) >=
context x window / capacity``). ``paged_attention`` is ONE call, and the
kernel's MEDIAN call is a sliding layer's (nine of twelve): its cost is the
sliding layer's, not the mean over the kinds, which would hold a 9-page walk
to a 16-page cost. ``expert_mlp`` is ONE ``gmm`` call, one of a layer's three.
"""

from __future__ import annotations

import concurrent.futures
import os
from pathlib import Path

import numpy as np

from benchmark.families import cohere2_moe as routed
from benchmark.families import llama as dense

BYTES_BF16 = dense.BYTES_BF16
REFERENCE = "benchmark.mellum_reference"
# what the forward decides by rank → the reference's keyword for how many it takes
CHOICES = {"experts": "experts_per_token"}
SLIDING, FULL = "sliding_attention", "full_attention"
TEXT_COL_SCALE = 0.25

# published key → field (or property) of the program's config object (``MellumConfig``)
WIDTHS = {
    "hidden_size": "dim", "moe_intermediate_size": "mlp_dim", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim", "vocab_size": "vocab_size",
    "num_hidden_layers": "n_layers", "rms_norm_eps": "norm_eps", "max_position_embeddings": "max_len",
    "sliding_window": "sliding_window", "num_experts": "n_experts", "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob", "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype",
    "layer_types": "layer_types", "mlp_layer_types": "mlp_layer_types",
}


def layer_kinds(model: dict, layers: int) -> list[str]:
    """The kinds of ``layers`` layers: the file's pattern from its start, or —
    fewer than a period (the first full layer ends one) — the period's LAST
    layers, so that both kinds are there (two layers: one sliding, one full)."""
    kinds = list(model["layer_types"])
    period = kinds.index(FULL) + 1
    return kinds[:period][-layers:] if layers < period else kinds[:layers]


def program_config(model: dict) -> dict:
    """Published keys → ``MellumConfig`` fields, every one. What the layer has
    no switch for is held here: a bias, another activation, a dense layer or
    two thetas would be another program."""
    rope = model["rope_parameters"]
    full, sliding = rope[FULL], rope[SLIDING]
    assert (full["rope_type"], sliding["rope_type"]) == ("yarn", "default"), rope
    assert full["rope_theta"] == sliding["rope_theta"], rope
    assert model["hidden_act"] == "silu" and not model["attention_bias"], model
    layers = int(model["num_hidden_layers"])
    assert set(model["mlp_layer_types"][:layers]) == {"sparse"}, model["mlp_layer_types"]
    return dict(
        vocab_size=int(model["vocab_size"]), dim=int(model["hidden_size"]), n_layers=layers,
        n_heads=int(model["num_attention_heads"]), n_kv_heads=int(model["num_key_value_heads"]),
        mlp_dim=int(model["moe_intermediate_size"]), max_len=int(model["max_position_embeddings"]),
        rope_theta=float(sliding["rope_theta"]), dtype=str(model.get("torch_dtype", "bfloat16")),
        norm_eps=float(model["rms_norm_eps"]), head_dim=int(model["head_dim"]),
        norm_kind="rmsnorm", parallel_block=False, layer_kinds=",".join(layer_kinds(model, layers)),
        sliding_window=int(model["sliding_window"]), rope_kind="rotate_half",
        rope_factor=float(full["factor"]), rope_original_max=int(full["original_max_position_embeddings"]),
        rope_beta_fast=float(full["beta_fast"]), rope_beta_slow=float(full["beta_slow"]),
        rope_attention_factor=float(full["attention_factor"]),
        tie_embeddings=bool(model["tie_word_embeddings"]), n_experts=int(model["num_experts"]),
        experts_per_token=int(model["num_experts_per_tok"]), gate_fn="softmax",
        norm_topk_prob=bool(model["norm_topk_prob"]), experts_held=int(model["num_experts"]), expert_offset=0,
    )


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.mellum import MellumConfig

    return MellumConfig(**{**program_config({**model, "num_hidden_layers": layers}), "max_len": max_len})


# ------------------------------------------------------------ seeded weights


def leaf_shapes(cfg: dict) -> dict:
    """``{path: (shape, std)}`` of every matrix of the program's
    ``init_mellum`` tree; a stack of experts is listed expert by expert
    (``(..., index)``), so that the largest leaves fill in parallel."""
    from sentio_tpu.models.mellum import WO_SCALE, WQ_SCALE

    dim, mlp = cfg["dim"], cfg["mlp_dim"]
    q, kv = cfg["n_heads"] * cfg["head_dim"], cfg["n_kv_heads"] * cfg["head_dim"]
    out = {("embed_tokens", "embedding"): ((cfg["vocab_size"], dim), 0.02),
           ("lm_head", "kernel"): ((dim, cfg["vocab_size"]), dim ** -0.5)}
    for i in range(cfg["n_layers"]):
        layer = f"layers_{i}"
        out[(layer, "attn", "wq", "kernel")] = ((dim, q), WQ_SCALE * dim ** -0.5)
        out[(layer, "attn", "wk", "kernel")] = ((dim, kv), dim ** -0.5)
        out[(layer, "attn", "wv", "kernel")] = ((dim, kv), dim ** -0.5)
        out[(layer, "attn", "wo", "kernel")] = ((q, dim), WO_SCALE * q ** -0.5)
        out[(layer, "moe", "router", "kernel")] = ((dim, cfg["n_experts"]), dim ** -0.5)
        for name, (n_in, n_out) in {"w_gate": (dim, mlp), "w_up": (dim, mlp), "w_down": (mlp, dim)}.items():
            for e in range(cfg["experts_held"]):
                out[(layer, "moe", name, e)] = ((n_in, n_out), n_in ** -0.5)
    return out


def make_params(model: dict, seed: int) -> dict:
    """The tree of the program's ``init_mellum`` in bf16 from ``seed``: one
    generator a matrix (an expert of a stack is one), all filled in parallel,
    so the tree depends on the seed alone. The head's columns for the text ids
    are scaled by ``TEXT_COL_SCALE``."""
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
        tree[path[0]][path[1]][path[2]] = np.stack(leaves)
    for i in range(cfg["n_layers"]):
        tree[f"layers_{i}"].update(attn_norm=ones(), mlp_norm=ones())
    head = tree["lm_head"]["kernel"]
    head[:, : dense.TEXT_IDS] = (head[:, : dense.TEXT_IDS].astype(np.float32) * TEXT_COL_SCALE).astype(head.dtype)
    return tree


def write_checkpoint(path: Path, model: dict, seed: int) -> None:
    from sentio_tpu.runtime.checkpoint import save_pytree

    save_pytree(path, make_params(model, seed),
                meta={"family": "mellum", "config": program_config(model)})


# ------------------------------------------------ bytes and operations

pool_bytes = dense.pool_bytes              # K and V of every layer, the dense family's pool
kv_bytes_per_token = dense.kv_bytes_per_token
rows_advancing = routed.rows_advancing     # context over a page table's capacity: a lower bound


def weight_params(model: dict) -> dict:
    """Parameters: a layer's attention, router and two norms, ONE expert, the
    table (the embedding, and the head again: untied)."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    q, kv = (model[k] * model["head_dim"] for k in ("num_attention_heads", "num_key_value_heads"))
    return {"attention": d * q + 2 * d * kv + q * d, "router": d * model["num_experts"],
            "expert": 3 * d * f, "norms": 2 * d, "table": model["vocab_size"] * d}


def model_weights(model: dict) -> int:
    """Parameters held at the file's depth: the layers with every expert, the
    embedding, the head and the final norm."""
    w = weight_params(model)
    layer = w["attention"] + w["router"] + model["num_experts"] * w["expert"] + w["norms"]
    return model["num_hidden_layers"] * layer + 2 * w["table"] + model["hidden_size"]


def experts_touched(model: dict, rows: float) -> float:
    """Of the 64 experts, those ``rows`` tokens reach, each picking
    ``num_experts_per_tok`` of them evenly: E x (1 - (1 - k/E)^rows)."""
    share = model["num_experts_per_tok"] / model["num_experts"]
    return model["num_experts"] * (1.0 - (1.0 - share) ** rows)


def sliding_keys(model: dict, context_tokens: float) -> float:
    """Tokens of K and V a SLIDING layer's decode attention reads, at least:
    all the context where no table outgrows the window, else ``context x
    window / capacity`` (the module docstring says why a lower bound)."""
    env, window = model["serve_env"], model["sliding_window"]
    capacity = int(env["KV_MAX_PAGES_PER_SEQ"]) * int(env["KV_PAGE_SIZE"])
    return context_tokens * min(1.0, window / capacity)


def keys_seen(model: dict, context_tokens: float) -> float:
    """The same, the mean over the layer kinds at the file's depth."""
    kinds = layer_kinds(model, model["num_hidden_layers"])
    n_sliding = kinds.count(SLIDING)
    return (n_sliding * sliding_keys(model, context_tokens)
            + (len(kinds) - n_sliding) * context_tokens) / len(kinds)


def decode_substep_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """One decode sub-step: every layer's attention matrices and router, the
    experts the advancing rows touch, the head once (the embedding is a gather
    of ``rows`` rows), and the K and V the attention reads under the windows. 2
    operations per multiply-add of every matmul an advancing row goes through
    (its eight picks among them) plus QK and PV over the keys seen."""
    w, n_layers = weight_params(model), model["num_hidden_layers"]
    n = rows_advancing(model, context_tokens)
    layer = w["attention"] + w["router"] + experts_touched(model, n) * w["expert"]
    seen = keys_seen(model, context_tokens)
    bytes_ = (BYTES_BF16 * (n_layers * layer + w["table"] + rows * model["hidden_size"])
              + seen * kv_bytes_per_token(model))
    row = n_layers * (w["attention"] + w["router"] + model["num_experts_per_tok"] * w["expert"]) + w["table"]
    attn = 4 * seen * model["num_attention_heads"] * model["head_dim"] * n_layers
    return {"bytes": float(bytes_), "flops": float(2 * n * row + attn)}


def paged_attention_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the decode attention kernel in a SLIDING layer (the median
    call: the module docstring says why not the mean over kinds): K and V of
    the keys the window leaves, QK and PV over them."""
    seen = sliding_keys(model, context_tokens)
    q = model["num_attention_heads"] * model["head_dim"]
    return {"bytes": float(seen * kv_bytes_per_token(model) / model["num_hidden_layers"]),
            "flops": float(4 * seen * q)}


def expert_mlp_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the grouped expert matmul in a decode sub-step — one of a
    layer's three (``hidden x expert width`` each): the matrices of the experts
    the advancing rows touch, 2 operations per multiply-add of their pairs. The
    pairs' rows in and out (a few dozen vectors) are left out."""
    n = rows_advancing(model, context_tokens)
    matrix = model["hidden_size"] * model["moe_intermediate_size"]
    return {"bytes": float(BYTES_BF16 * experts_touched(model, n) * matrix),
            "flops": float(2 * n * model["num_experts_per_tok"] * matrix)}


KERNEL_COSTS = {"paged_attention": paged_attention_cost, "expert_mlp": expert_mlp_cost}


# ------------------------------------------------------ the reference check


def init_params(key, cfg) -> dict:
    """The tree of the program's ``init_mellum`` (its shapes are asked of it)
    with the program's distributions, every leaf drawn in ONE call (the program
    draws a stack expert by expert), and the head's text-id columns scaled as
    ``make_params`` scales them: the check reads what a cell serves."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.mellum import WO_SCALE, WQ_SCALE, init_mellum

    paths, tree = jax.tree_util.tree_flatten_with_path(jax.eval_shape(lambda k: init_mellum(k, cfg), key))
    leaves = []
    for (path, leaf), k in zip(paths, jax.random.split(key, len(paths))):
        if path[-1].key == "scale":
            leaves.append(jnp.ones(leaf.shape, leaf.dtype))
        elif path[-1].key == "embedding":
            leaves.append(jax.random.normal(k, leaf.shape, leaf.dtype) * 0.02)
        else:  # a matrix or a stack of them: truncated normal over the fan-in
            scale = {"wq": WQ_SCALE, "wo": WO_SCALE}.get(path[-2].key, 1.0)
            drawn = jax.random.truncated_normal(k, -2.0, 2.0, leaf.shape, leaf.dtype) * scale * leaf.shape[-2] ** -0.5
            if path[0].key == "lm_head":
                drawn = drawn * jnp.where(jnp.arange(leaf.shape[-1]) < dense.TEXT_IDS, TEXT_COL_SCALE, 1.0)
            leaves.append(drawn)
    return jax.tree_util.tree_unflatten(tree, leaves)


is_matrix = routed.is_matrix               # matrices and stacks of them: bf16 in a checkpoint


def reference_kwargs(model: dict) -> dict:
    """What the reference takes from the configuration — the layer kinds at the
    CHECK's depth, both rotary settings, and the share of the experts held."""
    cfg = program_config(model)
    return dict(
        {k: cfg[k] for k in ("n_heads", "n_kv_heads", "head_dim", "rope_theta", "norm_eps", "sliding_window",
                             "rope_factor", "rope_original_max", "rope_beta_fast", "rope_beta_slow",
                             "rope_attention_factor", "experts_per_token", "norm_topk_prob", "experts_held",
                             "expert_offset")},
        layer_types=tuple(layer_kinds(model, int(model["check"]["layers"]))))


def reference_params(tree: dict, n_layers: int) -> dict:
    """The program's tree under the reference's flat names, every matrix in the
    checkpoint's own bf16: the reference widens one where it uses it."""
    out = {"embed": np.asarray(tree["embed_tokens"]["embedding"]), "head": np.asarray(tree["lm_head"]["kernel"]),
           "final_norm": np.asarray(tree["final_norm"]["scale"], np.float32), "layers": []}
    for i in range(n_layers):
        lp = tree[f"layers_{i}"]
        out["layers"].append({
            "attn_norm": np.asarray(lp["attn_norm"]["scale"], np.float32),
            "mlp_norm": np.asarray(lp["mlp_norm"]["scale"], np.float32),
            **{k: np.asarray(lp["attn"][k]["kernel"]) for k in ("wq", "wk", "wv", "wo")},
            "router": np.asarray(lp["moe"]["router"]["kernel"]),
            **{k: np.asarray(lp["moe"][k]) for k in ("w_gate", "w_up", "w_down")}})
    return out


# K and V in the dense family's pool, a forward that hands its picks back: the pieces and the served
# requests are ``families/cohere2_moe.py``'s, which name no config class
paged_pieces = routed.paged_pieces
served = routed.served
