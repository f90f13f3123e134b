"""Checkpoint family ``lfm2_moe``: LFM2-24B-A2B as the program runs it through
``Lfm2MoeConfig`` (``sentio_tpu/models/lfm2_moe.py``) — sequential pre-norm
blocks whose mixer is a gated short convolution in three layers of four and
GQA attention (per-head RMSNorm on q and k, 64-wide heads) in the fourth, a
dense SwiGLU in the two leading layers and 64 routed experts picked top-4
under a selection-only bias in every later one, a head tied to the embedding.
The whole contract of ``families/llama.py``'s docstring is here; what a reader
of this family needs beyond it:

THE DEPLOYMENT. A routed layer is 1.208 GB of bf16, so a chip holds every
expert of a layer and no layer is shared between chips; the whole model (47.7
GB) is a PIPELINE of four chips, ten layers a stage. The cell runs the first
stage — the two dense layers and two whole periods of the pattern — with the
tied table, so that it serves whole answers. ``num_experts`` is all 64 and the
program's ``experts_held`` equals it: the expert layer IS the model's.

WHAT A TOKEN LEAVES BEHIND. K and V in the 2 attention layers of 10 (4,096 B a
token), and in the 8 convolution layers nothing per token: their state is two
positions of a 2048-wide product a layer, kept per decode slot and — so that
the radix cache can serve whole pages — per page (64 KB a page). ``pool_bytes``
counts all three, to the byte the engine's pool reports.

THE SEEDED TREE AND A TIED HEAD. ``families/cohere2_moe.py`` has the reasoning,
and its sizes carry over (``models/lfm2_moe.py``: the embedding a quarter as
large, the query projection four times, the text ids' rows a quarter again, so
that no answer holds a text id or ends early). THE EXPERT BIAS is drawn
non-zero (``EXPERT_BIAS_STD`` 0.02, the spread of the TOP scores): the picks
then differ from the unbiased ones for about half the tokens, and a step of 16
rows still touches about what even routing gives (``tests/benchmark`` holds
both).

THE CHECK'S DEPTH. ``check_config`` at 4 layers (the file's ``check``) keeps
layers 0..3: conv and dense twice, attention and routed, conv and routed —
every kind of block. At FEWER layers than hold every kind from the start (the
yardstick's own CPU tests hold every configuration's rehearsal to two) it
keeps ONE dense layer under the first layer's mixer and routed layers behind
it, the first of them attention: at two, a convolution under a dense
feed-forward and attention under routed experts — every operator and both
feed-forwards, not every pairing. The served part sends a cold prompt and
others over its cached head, all in chunks: a restored page tail and a
carried state are both in the comparison.

COSTS. A decode sub-step reads the experts its advancing rows touch, not all
64; the rows are bounded from below as in ``families/cohere2_moe.py`` (a share
reads low, never high). ``expert_mlp`` is ONE ``gmm`` call, one of a routed
layer's three.
"""

from __future__ import annotations

import concurrent.futures
import os
from pathlib import Path

import numpy as np

from benchmark.families import llama as dense

BYTES_BF16 = dense.BYTES_BF16
REFERENCE = "benchmark.lfm2_moe_reference"
# what the forward decides by rank → the reference's keyword for how many it takes
CHOICES = {"experts": "experts_per_token"}
TEXT_ROW_SCALE = 0.25
CONV, FULL = "conv", "full_attention"
CONV_TAPS = 2          # positions of state a convolution layer carries: conv_L_cache - 1

# published key → field of the program's config object (``Lfm2MoeConfig``)
WIDTHS = {
    "hidden_size": "dim", "intermediate_size": "mlp_dim", "moe_intermediate_size": "moe_mlp_dim",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size",
    "num_hidden_layers": "n_layers", "norm_eps": "norm_eps", "max_position_embeddings": "max_len",
    "conv_L_cache": "conv_l_cache", "conv_bias": "conv_bias", "num_dense_layers": "num_dense_layers",
    "num_experts": "n_experts", "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob", "use_expert_bias": "use_expert_bias",
    "routed_scaling_factor": "routed_scaling_factor", "torch_dtype": "dtype", "layer_types": "kinds",
}


def program_config(model: dict) -> dict:
    """Published keys → ``Lfm2MoeConfig`` fields, every one (``layer_types``
    comma-joined, as the config object keeps it and ``/info`` reports it)."""
    rope = model["rope_parameters"]
    assert rope["rope_type"] == "default", rope
    layers = int(model["num_hidden_layers"])
    return dict(
        vocab_size=int(model["vocab_size"]), dim=int(model["hidden_size"]), n_layers=layers,
        n_heads=int(model["num_attention_heads"]), n_kv_heads=int(model["num_key_value_heads"]),
        mlp_dim=int(model["intermediate_size"]), max_len=int(model["max_position_embeddings"]),
        rope_theta=float(rope["rope_theta"]), dtype=str(model.get("torch_dtype", "bfloat16")),
        norm_eps=float(model["norm_eps"]), layer_types=",".join(model["layer_types"][:layers]),
        num_dense_layers=int(model["num_dense_layers"]), conv_l_cache=int(model["conv_L_cache"]),
        conv_bias=bool(model["conv_bias"]), moe_mlp_dim=int(model["moe_intermediate_size"]),
        n_experts=int(model["num_experts"]), experts_per_token=int(model["num_experts_per_tok"]),
        norm_topk_prob=bool(model["norm_topk_prob"]), use_expert_bias=bool(model["use_expert_bias"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        norm_topk_eps=float(model.get("norm_topk_eps", 1e-6)), gate_fn="sigmoid",
        tie_embeddings=bool(model.get("tie_word_embeddings", True)),
        experts_held=int(model["num_experts"]), expert_offset=0,
    )


def check_layers(model: dict, layers: int) -> dict:
    """The keys of ``model`` that say which layers a check of ``layers`` layers
    keeps: the model's own first ones where they hold every kind of block,
    else one dense layer and routed layers behind it, the first of them
    attention (the module docstring says why)."""
    kinds, dense_layers = list(model["layer_types"][:layers]), int(model["num_dense_layers"])
    whole = len(kinds) == layers and {CONV, FULL} <= set(kinds[dense_layers:])
    if not whole:
        kinds, dense_layers = [model["layer_types"][0], FULL] + [CONV] * (layers - 2), 1
    return {"num_hidden_layers": layers, "layer_types": kinds, "num_dense_layers": dense_layers}


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.lfm2_moe import Lfm2MoeConfig

    return Lfm2MoeConfig(**{**program_config({**model, **check_layers(model, layers)}), "max_len": max_len})


# ------------------------------------------------------------ seeded weights


def leaf_shapes(cfg: dict) -> dict:
    """``{path: (shape, std)}`` of every matrix of the program's
    ``init_lfm2_moe`` tree; a stack of experts is listed expert by expert
    (``(..., index)``), so that the largest leaves fill in parallel."""
    from sentio_tpu.models.lfm2_moe import EMBED_STD, EXPERT_BIAS_STD, WO_SCALE, WQ_SCALE

    d, hd = cfg["dim"], cfg["dim"] // cfg["n_heads"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    out = {("embed_tokens", "embedding"): ((cfg["vocab_size"], d), EMBED_STD)}
    for i, kind in enumerate(cfg["layer_types"].split(",")):
        layer = f"layers_{i}"
        if kind == CONV:
            out[(layer, "conv", "w_in", "kernel")] = ((d, 3 * d), d ** -0.5)
            out[(layer, "conv", "kernel")] = ((d, cfg["conv_l_cache"]), cfg["conv_l_cache"] ** -0.5)
            out[(layer, "conv", "w_out", "kernel")] = ((d, d), WO_SCALE * d ** -0.5)
        else:
            out[(layer, "attn", "wq", "kernel")] = ((d, q), WQ_SCALE * d ** -0.5)
            out[(layer, "attn", "wk", "kernel")] = ((d, kv), d ** -0.5)
            out[(layer, "attn", "wv", "kernel")] = ((d, kv), d ** -0.5)
            out[(layer, "attn", "wo", "kernel")] = ((q, d), WO_SCALE * q ** -0.5)
        if i < cfg["num_dense_layers"]:
            f = cfg["mlp_dim"]
            for name, (n_in, n_out) in {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}.items():
                out[(layer, "mlp", name, "kernel")] = ((n_in, n_out), n_in ** -0.5)
            continue
        f = cfg["moe_mlp_dim"]
        out[(layer, "moe", "router", "kernel")] = ((d, cfg["n_experts"]), d ** -0.5)
        out[(layer, "moe", "bias")] = ((cfg["n_experts"],), EXPERT_BIAS_STD if cfg["use_expert_bias"] else 0.0)
        for name, (n_in, n_out) in {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}.items():
            for e in range(cfg["experts_held"]):
                out[(layer, "moe", name, e)] = ((n_in, n_out), n_in ** -0.5)
    return out


# leaves a checkpoint holds in float32 beside the norm scales: the taps (6,144
# numbers a layer) and the expert bias (64), which is ADDED to float32 scores
FLOAT32_LEAVES = (("conv", "kernel"), ("moe", "bias"))


def make_params(model: dict, seed: int) -> dict:
    """The tree of the program's ``init_lfm2_moe`` in bf16 from ``seed``: one
    generator a matrix (an expert of a stack is one), all filled in parallel,
    so the tree depends on the seed alone. The text ids' rows of the embedding
    — which is the head too — are scaled by ``TEXT_ROW_SCALE``."""
    cfg = program_config(model)
    shapes = leaf_shapes(cfg)
    seeds = np.random.SeedSequence(seed).spawn(len(shapes))

    def fill(job):
        seed_, (path, (shape, std)) = job
        rng = np.random.default_rng(seed_)
        if path[-2:] in FLOAT32_LEAVES:
            return (rng.standard_normal(shape, dtype=np.float32) * std).astype(np.float32)
        return dense.normal_bf16(rng, shape, std)

    with concurrent.futures.ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as pool:
        filled = list(pool.map(fill, zip(seeds, shapes.items())))
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
    for i, kind in enumerate(cfg["layer_types"].split(",")):
        layer = tree[f"layers_{i}"]
        layer["op_norm"], layer["ffn_norm"] = ones(cfg["dim"]), ones(cfg["dim"])
        if kind == FULL:
            hd = cfg["dim"] // cfg["n_heads"]
            layer["attn"]["q_norm"], layer["attn"]["k_norm"] = ones(hd), ones(hd)
    table = tree["embed_tokens"]["embedding"]
    table[: dense.TEXT_IDS] = (table[: dense.TEXT_IDS].astype(np.float32) * TEXT_ROW_SCALE).astype(table.dtype)
    return tree


def write_checkpoint(path: Path, model: dict, seed: int) -> None:
    from sentio_tpu.runtime.checkpoint import save_pytree

    save_pytree(path, make_params(model, seed),
                meta={"family": "lfm2_moe", "config": program_config(model)})


# ------------------------------------------------ bytes and operations


def layer_counts(model: dict) -> tuple[int, int]:
    """(attention layers, convolution layers) of the file's depth."""
    kinds = model["layer_types"][: model["num_hidden_layers"]]
    return sum(k == FULL for k in kinds), sum(k == CONV for k in kinds)


def head_dim(model: dict) -> int:
    return model["hidden_size"] // model["num_attention_heads"]


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token: bf16 pages in the ATTENTION layers alone."""
    return 2 * model["num_key_value_heads"] * head_dim(model) * BYTES_BF16 * layer_counts(model)[0]


def conv_state_bytes(model: dict) -> int:
    """One sequence's (or one page's) convolution state: two positions of the
    hidden width in every convolution layer, bf16."""
    return layer_counts(model)[1] * CONV_TAPS * model["hidden_size"] * BYTES_BF16


def pool_bytes(model: dict, env: dict) -> int:
    """What the engine's pool reports: pages of K and V (the scratch page and
    ``slots x pages`` more), a page tail of convolution state for every page,
    and the state of every slot."""
    slots = int(env["LLM_MAX_BATCH"])
    pages = 1 + slots * int(env["KV_MAX_PAGES_PER_SEQ"])
    return (pages * int(env["KV_PAGE_SIZE"]) * kv_bytes_per_token(model)
            + (pages + slots) * conv_state_bytes(model))


def weight_params(model: dict) -> dict:
    """Parameters: a convolution operator, an attention operator, a dense
    SwiGLU, the router (with its bias), ONE expert, the table (tied: once)."""
    d = model["hidden_size"]
    q, kv = (model[k] * head_dim(model) for k in ("num_attention_heads", "num_key_value_heads"))
    return {"conv": d * 3 * d + d * d + d * model["conv_L_cache"], "attention": d * q + 2 * d * kv + q * d,
            "dense_mlp": 3 * d * model["intermediate_size"], "router": d * model["num_experts"] + model["num_experts"],
            "expert": 3 * d * model["moe_intermediate_size"], "table": model["vocab_size"] * d}


def model_weights(model: dict) -> int:
    """Parameters of the file's depth, every expert held."""
    w, (n_attn, n_conv) = weight_params(model), layer_counts(model)
    n_dense = model["num_dense_layers"]
    routed = model["num_hidden_layers"] - n_dense
    return (n_conv * w["conv"] + n_attn * w["attention"] + n_dense * w["dense_mlp"]
            + routed * (w["router"] + model["num_experts"] * w["expert"]) + w["table"])


def rows_advancing(model: dict, context_tokens: float) -> float:
    """At least this many rows hold ``context_tokens``: none holds more than
    its page table (``families/cohere2_moe.py`` says why a lower bound)."""
    env = model["serve_env"]
    return context_tokens / (int(env["KV_MAX_PAGES_PER_SEQ"]) * int(env["KV_PAGE_SIZE"]))


def experts_touched(model: dict, rows: float) -> float:
    """Of a layer's experts, those ``rows`` tokens reach, each picking
    ``num_experts_per_tok`` evenly: E x (1 - (1 - k/E)^rows)."""
    e = model["num_experts"]
    return e * (1.0 - (1.0 - model["num_experts_per_tok"] / e) ** rows)


def decode_substep_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """One decode sub-step: the convolution and attention operators' matrices,
    the dense layers, every routed layer's router and the experts the
    advancing rows touch, the table once (as the head; as the embedding it is
    a gather of ``rows`` rows), K and V of the attention layers, and the
    convolution state of the advancing rows read and written. 2 operations a
    multiply-add of every matmul an advancing row goes through, plus QK and PV
    over the context in the attention layers."""
    w, (n_attn, n_conv) = weight_params(model), layer_counts(model)
    n_dense = model["num_dense_layers"]
    routed = model["num_hidden_layers"] - n_dense
    n = rows_advancing(model, context_tokens)
    mixers = n_conv * w["conv"] + n_attn * w["attention"]
    weights = (mixers + n_dense * w["dense_mlp"]
               + routed * (w["router"] + experts_touched(model, n) * w["expert"]) + w["table"])
    bytes_ = (BYTES_BF16 * (weights + rows * model["hidden_size"])
              + context_tokens * kv_bytes_per_token(model) + 2 * n * conv_state_bytes(model))
    row = (mixers + n_dense * w["dense_mlp"]
           + routed * (w["router"] + model["num_experts_per_tok"] * w["expert"]) + w["table"])
    attn = 4 * context_tokens * model["num_attention_heads"] * head_dim(model) * n_attn
    return {"bytes": float(bytes_), "flops": float(2 * n * row + attn)}


def expert_mlp_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the grouped expert matmul in a decode sub-step — one of a
    routed layer's three (gate, up, down: ``hidden x expert width`` each): the
    matrices of the experts the advancing rows touch, 2 operations a
    multiply-add of the pairs routed. The pairs' rows in and out are left out:
    the share reads a little low."""
    n = rows_advancing(model, context_tokens)
    matrix = model["hidden_size"] * model["moe_intermediate_size"]
    return {"bytes": float(BYTES_BF16 * experts_touched(model, n) * matrix),
            "flops": float(2 * n * model["num_experts_per_tok"] * matrix)}


KERNEL_COSTS = {"expert_mlp": expert_mlp_cost}


# ------------------------------------------------------ the reference check


def init_params(key, cfg) -> dict:
    """The tree of the program's ``init_lfm2_moe`` (its shapes are asked of
    it) with the program's distributions, every leaf drawn in ONE call, and
    the text ids' rows scaled as ``make_params`` scales them: the check reads
    what a cell serves."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.lfm2_moe import EMBED_STD, EXPERT_BIAS_STD, WO_SCALE, WQ_SCALE, init_lfm2_moe

    paths, tree = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda k: init_lfm2_moe(k, cfg), key))
    leaves = []
    for (path, leaf), k in zip(paths, jax.random.split(key, len(paths))):
        names = [p.key for p in path]
        if names[-1] == "scale":
            leaves.append(jnp.ones(leaf.shape, leaf.dtype))
        elif names[-1] == "embedding":
            rows = jnp.where(jnp.arange(leaf.shape[0]) < dense.TEXT_IDS, TEXT_ROW_SCALE, 1.0)
            leaves.append(jax.random.normal(k, leaf.shape, leaf.dtype) * EMBED_STD * rows[:, None])
        elif names[-1] == "bias":
            leaves.append(jax.random.normal(k, leaf.shape, leaf.dtype) * EXPERT_BIAS_STD)
        elif tuple(names[-2:]) == ("conv", "kernel"):
            leaves.append(jax.random.normal(k, leaf.shape, leaf.dtype) * leaf.shape[-1] ** -0.5)
        else:  # a matrix or a stack of them: truncated normal over the fan-in
            scale = {"wq": WQ_SCALE, "wo": WO_SCALE, "w_out": WO_SCALE}.get(names[-2], 1.0)
            leaves.append(jax.random.truncated_normal(k, -2.0, 2.0, leaf.shape, leaf.dtype)
                          * scale * leaf.shape[-2] ** -0.5)
    return jax.tree_util.tree_unflatten(tree, leaves)


def is_matrix(leaf) -> bool:
    """What a checkpoint holds in bf16: matrices and stacks of them. The
    convolution's taps ``[d, 3]`` are a matrix by their shape and float32 by
    ``FLOAT32_LEAVES``: told apart by their last axis, which no matrix of a
    model has (three columns)."""
    return leaf.ndim >= 2 and leaf.shape[-1] > 3


def reference_kwargs(model: dict) -> dict:
    cfg = program_config(model)
    keys = ("n_heads", "n_kv_heads", "rope_theta", "norm_eps", "experts_per_token", "norm_topk_prob",
            "norm_topk_eps", "routed_scaling_factor", "experts_held", "expert_offset")
    return {key: cfg[key] for key in keys}


def reference_params(tree: dict, n_layers: int) -> dict:
    """The program's tree under the reference's flat names, every matrix in
    the checkpoint's own bf16: the reference widens one where it uses it."""
    out = {"embed": np.asarray(tree["embed_tokens"]["embedding"]),
           "final_norm": np.asarray(tree["final_norm"]["scale"], np.float32), "layers": []}
    for i in range(n_layers):
        lp = tree[f"layers_{i}"]
        layer = {"op_norm": np.asarray(lp["op_norm"]["scale"], np.float32),
                 "ffn_norm": np.asarray(lp["ffn_norm"]["scale"], np.float32)}
        if "conv" in lp:
            conv = lp["conv"]
            layer.update(w_in=np.asarray(conv["w_in"]["kernel"]), kernel=np.asarray(conv["kernel"], np.float32),
                         w_out=np.asarray(conv["w_out"]["kernel"]))
        else:
            attn = lp["attn"]
            layer.update({k: np.asarray(attn[k]["kernel"]) for k in ("wq", "wk", "wv", "wo")})
            layer.update(q_norm=np.asarray(attn["q_norm"]["scale"], np.float32),
                         k_norm=np.asarray(attn["k_norm"]["scale"], np.float32))
        if "moe" in lp:
            moe = lp["moe"]
            layer.update(router=np.asarray(moe["router"]["kernel"]), bias=np.asarray(moe["bias"], np.float32),
                         **{k: np.asarray(moe[k]) for k in ("w_gate", "w_up", "w_down")})
        else:
            layer.update({k: np.asarray(lp["mlp"][k]["kernel"]) for k in ("w_gate", "w_up", "w_down")})
        out["layers"].append(layer)
    return out


def paged_pieces(engine, cfg, rows: int, width: int):
    """→ ``(state, prefill, decode)`` as ``families/llama.py`` has them, each
    piece returning ``(logits, state, {"experts": picks})``. The state is what
    the engine's pool holds for THIS family: K and V pages of the attention
    layers, the page tails of the convolution state — and the state of the
    pieces' own ``rows`` sequences, where the engine keeps one a decode slot.
    Prefill is the admission forward from zeros into a fresh cache,
    ``scatter_prefill`` of its K and V, its page tails written where its
    blocks say, and each row's state taken at ITS length; decode is
    ``paged_decode_forward`` over pool and state with the engine's own kernel
    selection. (What a family with state outside the pages changed in
    ``check.py``'s pieces: nothing — the state is opaque to it.)"""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.lfm2_moe import init_lfm2_cache
    from sentio_tpu.runtime.paged import paged_decode_forward, scatter_prefill

    forward_fn, attn_impl, page = engine.forward_fn, engine._attn_impl, engine.page_size

    @jax.jit
    def prefill(params, ids, positions, lens, blocks, state):
        k_pages, v_pages, _conv, tail = state
        pad = jnp.arange(width)[None, :] < lens[:, None]
        logits, cache, routed = forward_fn(params, cfg, ids, positions=positions,
                                           cache=init_lfm2_cache(cfg, rows, width, width // page),
                                           cache_index=0, pad_mask=pad)
        k_pages, v_pages = scatter_prefill(k_pages, v_pages, cache["k"], cache["v"], blocks)
        return (logits, (k_pages, v_pages, cache["conv"], tail.at[:, blocks].set(cache["tail"])),
                {"experts": routed["experts"]})

    @jax.jit
    def decode(params, tok, lens, table, state):
        k_pages, v_pages, conv, tail = state
        logits, k_pages, v_pages, routed, conv, tail = paged_decode_forward(
            params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=attn_impl, return_routed=True,
            conv=conv, tail=tail)
        return logits, (k_pages, v_pages, conv, tail), {"experts": routed["experts"]}

    conv = jnp.zeros((engine.pool.conv.shape[0], rows, *engine.pool.conv.shape[2:]), engine.pool.conv.dtype)
    return (engine.pool.k, engine.pool.v, conv, engine.pool.tail), prefill, decode


def served(engine, prompts, max_new_tokens):
    """The requests through ``engine.run_all`` → (results, each request's own
    picks ``{"experts": [routed layers, prompt + answer tokens - 1, k]}``,
    negative where the radix cache served the position)."""
    results = engine.run_all(prompts, max_new_tokens=max_new_tokens, return_choices=True)
    return results, [r.choices for r in results]
