"""Checkpoint family ``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B as the
program runs it through ``NemotronHConfig`` (``sentio_tpu/models/nemotron_h.py``)
— blocks of ONE operator each by a letter of ``hybrid_override_pattern``:
``M`` a Mamba-2 mixer (64 heads of 64, 8 groups of B and C, state 128, four
taps), ``E`` 128 routed UNGATED relu² experts picked top-6 under a
selection-only bias plus one shared expert, ``*`` rotation-free GQA 32:2; an
untied head. The whole contract of ``families/llama.py``'s docstring is here;
what a reader of this family needs beyond it:

THE DEPLOYMENT. A routed block whole is 2.595 GB of bf16, so TWO chips share
each layer: this one holds experts 0..63 of 128 (``n_routed_experts`` 64,
``n_routed_experts_router`` 128: the router is as wide as published and a
token's picks that lie on the other chip add nothing here), the shared expert,
the Mamba and attention blocks and the norms whole (they are on both), and
65,536 of the 131,072 rows of the embedding and columns of the head. The depth
is the first of four pipeline stages: blocks 0..13, two whole turns of the
7-block run ``MEMEM*E``. Nothing stands in for the other chip or the other
stages.

WHAT A SEQUENCE KEEPS. K and V in the 2 attention blocks of 14 (2,048 B a
token), and in the 6 Mamba blocks a STATE whatever the length: the matrix
``[64, 64, 128]`` float32 a layer (2.10 MB) and three columns of 6,144 (bf16),
kept per decode slot and in ``SSM_SNAPSHOTS`` snapshots for the prefix cache —
a bounded pool, not a tail a page: a state is fifty times the K and V of the
page it ends. ``pool_bytes`` counts all of it, to the byte the engine's pool
reports.

THE SEEDED TREE. The sizes of ``models/nemotron_h.py`` (its comment has the
reasons); the head's columns for the text ids are a quarter as large
(``TEXT_COL_SCALE``), so that no answer holds a text id or ends early (zero, as
the dense family's cells have them, would leave the check's int8 variant
nothing to scale a column by). ``A`` and ``dt`` take the
published initialisation.

THE CHECK'S DEPTH counts BLOCKS. ``check_config`` at 7 (the file's ``check``)
keeps blocks 0..6, ``MEMEM*E``: one whole turn, every kind. At FEWER blocks
than hold every kind from the start (the yardstick's own CPU tests hold every
configuration's rehearsal to two) a check "layer" is what every other family's
is, a mixer and the feed-forward behind it: ``ME`` then ``*E`` — four blocks at
two, every operator. The served part sends a cold prompt in segments and
others over its cached head: a carried state, a restored snapshot and a
snapshot written at a segment's end are all in the comparison.

COSTS. A decode sub-step reads the experts its advancing rows touch, not all
64 held; the rows are bounded from below as in ``families/cohere2_moe.py`` (a
share reads low, never high), and each advancing row's Mamba state is read and
written once a layer. ``expert_mlp`` is ONE ``gmm`` call, one of a routed
block's TWO.
"""

from __future__ import annotations

import concurrent.futures
import os
from pathlib import Path

import numpy as np

from benchmark.families import llama as dense

BYTES_BF16, BYTES_F32 = dense.BYTES_BF16, 4
REFERENCE = "benchmark.nemotron_h_reference"
# what the forward decides by rank → the reference's keyword for how many it takes
CHOICES = {"experts": "experts_per_token"}
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
TEXT_COL_SCALE = 0.25

# published key → field of the program's config object (``NemotronHConfig``)
WIDTHS = {
    "hidden_size": "dim", "intermediate_size": "mlp_dim", "moe_intermediate_size": "mlp_dim",
    "moe_shared_expert_intermediate_size": "shared_mlp_dim", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim", "vocab_size": "vocab_size",
    "num_hidden_layers": "n_layers", "hybrid_override_pattern": "pattern", "norm_eps": "norm_eps",
    "max_position_embeddings": "max_len", "mamba_num_heads": "mamba_heads", "mamba_head_dim": "mamba_head_dim",
    "ssm_state_size": "ssm_state", "n_groups": "n_groups", "conv_kernel": "conv_kernel", "chunk_size": "chunk_size",
    "time_step_min": "time_step_min", "time_step_max": "time_step_max", "time_step_floor": "time_step_floor",
    "n_routed_experts": "experts_held", "n_routed_experts_router": "n_experts",
    "num_experts_per_tok": "experts_per_token", "n_shared_experts": "n_shared_experts",
    "norm_topk_prob": "norm_topk_prob", "routed_scaling_factor": "routed_scaling_factor", "torch_dtype": "dtype",
}


def program_config(model: dict) -> dict:
    """Published keys → ``NemotronHConfig`` fields, every one. What the
    program's block has no switch for is held here: an attention or expert
    matrix with a bias, an activation other than relu², a group-limited router
    or a convolution without bias is another model."""
    import sentio_tpu.models.nemotron_h  # noqa: F401 — a program without this family fails here, at once

    stated = (model["mlp_hidden_act"], model["mamba_hidden_act"], model["use_conv_bias"], model["n_group"],
              model["topk_group"], model["attention_bias"], model["mlp_bias"], model["mamba_proj_bias"],
              model["tie_word_embeddings"])
    assert stated == ("relu2", "silu", True, 1, 1, False, False, False, False), stated
    layers = int(model["num_hidden_layers"])
    return dict(
        vocab_size=int(model["vocab_size"]), dim=int(model["hidden_size"]), n_layers=layers,
        n_heads=int(model["num_attention_heads"]), n_kv_heads=int(model["num_key_value_heads"]),
        mlp_dim=int(model["moe_intermediate_size"]), max_len=int(model["max_position_embeddings"]),
        rope_theta=float(model["rope_theta"]), dtype=str(model.get("torch_dtype", "bfloat16")),
        norm_eps=float(model["norm_eps"]), head_dim=int(model["head_dim"]),
        shared_mlp_dim=int(model["moe_shared_expert_intermediate_size"]),
        pattern=str(model["hybrid_override_pattern"])[:layers],
        mamba_heads=int(model["mamba_num_heads"]), mamba_head_dim=int(model["mamba_head_dim"]),
        ssm_state=int(model["ssm_state_size"]), n_groups=int(model["n_groups"]),
        conv_kernel=int(model["conv_kernel"]), chunk_size=int(model["chunk_size"]),
        time_step_min=float(model["time_step_min"]), time_step_max=float(model["time_step_max"]),
        time_step_floor=float(model["time_step_floor"]),
        n_experts=int(model["n_routed_experts_router"]), experts_per_token=int(model["num_experts_per_tok"]),
        n_shared_experts=int(model["n_shared_experts"]), norm_topk_prob=bool(model["norm_topk_prob"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        norm_topk_eps=float(model.get("norm_topk_eps", 1e-20)),
        experts_held=int(model["n_routed_experts"]), expert_offset=int(model["expert_offset"]),
    )


def check_layers(model: dict, layers: int) -> dict:
    """The keys of ``model`` that say which blocks a check of ``layers``
    keeps: the model's own first ones where they hold every kind, else a
    mixer and the experts behind it a "layer", Mamba first and attention
    second (the module docstring says why)."""
    pattern = str(model["hybrid_override_pattern"])[:layers]
    if len(pattern) < layers or set(pattern) != {MAMBA, EXPERTS, ATTENTION}:
        pattern = "".join((ATTENTION if i == 1 else MAMBA) + EXPERTS for i in range(layers))
    return {"num_hidden_layers": len(pattern), "hybrid_override_pattern": pattern}


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.nemotron_h import NemotronHConfig

    return NemotronHConfig(**{**program_config({**model, **check_layers(model, layers)}), "max_len": max_len})


# ------------------------------------------------------------ seeded weights


def leaf_shapes(cfg: dict) -> dict:
    """``{path: (shape, std)}`` of every matrix of the program's
    ``init_nemotron_h`` tree; a stack of experts is listed expert by expert
    (``(..., index)``), so that the largest leaves fill in parallel."""
    from sentio_tpu.models.nemotron_h import EXPERT_BIAS_STD, HEAD_SCALE, WO_SCALE, WQ_SCALE

    d, hd = cfg["dim"], cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    inner = cfg["mamba_heads"] * cfg["mamba_head_dim"]
    conv_dim = inner + 2 * cfg["n_groups"] * cfg["ssm_state"]
    out = {("embed_tokens", "embedding"): ((cfg["vocab_size"], d), 0.02),
           ("lm_head", "kernel"): ((d, cfg["vocab_size"]), HEAD_SCALE * d ** -0.5)}
    for i, kind in enumerate(cfg["pattern"]):
        layer = f"layers_{i}"
        if kind == MAMBA:
            out[(layer, "mamba", "w_in", "kernel")] = ((d, inner + conv_dim + cfg["mamba_heads"]), d ** -0.5)
            out[(layer, "mamba", "conv_kernel")] = ((conv_dim, cfg["conv_kernel"]), cfg["conv_kernel"] ** -0.5)
            out[(layer, "mamba", "w_out", "kernel")] = ((inner, d), WO_SCALE * inner ** -0.5)
        elif kind == ATTENTION:
            out[(layer, "attn", "wq", "kernel")] = ((d, q), WQ_SCALE * d ** -0.5)
            out[(layer, "attn", "wk", "kernel")] = ((d, kv), d ** -0.5)
            out[(layer, "attn", "wv", "kernel")] = ((d, kv), d ** -0.5)
            out[(layer, "attn", "wo", "kernel")] = ((q, d), WO_SCALE * q ** -0.5)
        else:
            f, fs = cfg["mlp_dim"], cfg["shared_mlp_dim"]
            out[(layer, "moe", "router", "kernel")] = ((d, cfg["n_experts"]), d ** -0.5)
            out[(layer, "moe", "bias")] = ((cfg["n_experts"],), EXPERT_BIAS_STD)
            out[(layer, "moe", "shared", "w_up")] = ((1, d, fs), d ** -0.5)
            out[(layer, "moe", "shared", "w_down")] = ((1, fs, d), fs ** -0.5)
            for name, (n_in, n_out) in {"w_up": (d, f), "w_down": (f, d)}.items():
                for e in range(cfg["experts_held"]):
                    out[(layer, "moe", name, e)] = ((n_in, n_out), n_in ** -0.5)
    return out


# leaves a checkpoint holds in float32 beside the norm scales: the taps and the
# expert bias, which is ADDED to float32 scores (the Mamba's vectors a head are
# made in ``mamba_vectors``)
FLOAT32_LEAVES = (("mamba", "conv_kernel"), ("moe", "bias"))


def mamba_vectors(rng: np.random.Generator, cfg: dict) -> dict:
    """A Mamba block's float32 vectors under the published initialisation:
    ``A`` uniform in 1..16 (stored as its log), ``dt`` log-uniform in
    ``time_step_min..max`` and floored (stored as the inverse softplus),
    ``D`` ones, no convolution bias, a unit norm weight."""
    heads = cfg["mamba_heads"]
    inner = heads * cfg["mamba_head_dim"]
    dt = np.exp(rng.random(heads) * (np.log(cfg["time_step_max"]) - np.log(cfg["time_step_min"]))
                + np.log(cfg["time_step_min"]))
    dt = np.maximum(dt, cfg["time_step_floor"])
    return {"dt_bias": (dt + np.log(-np.expm1(-dt))).astype(np.float32),
            "a_log": np.log(rng.uniform(1.0, 16.0, heads)).astype(np.float32),
            "d": np.ones((heads,), np.float32),
            "conv_bias": np.zeros((inner + 2 * cfg["n_groups"] * cfg["ssm_state"],), np.float32),
            "out_norm": {"scale": np.ones((inner,), np.float32)}}


def make_params(model: dict, seed: int) -> dict:
    """The tree of the program's ``init_nemotron_h`` in bf16 from ``seed``:
    one generator a matrix (an expert of a stack is one), all filled in
    parallel, so the tree depends on the seed alone. The head's columns for
    the text ids are scaled by ``TEXT_COL_SCALE``."""
    cfg = program_config(model)
    shapes = leaf_shapes(cfg)
    seeds = np.random.SeedSequence(seed).spawn(len(shapes) + cfg["n_layers"])

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
    for i, kind in enumerate(cfg["pattern"]):
        layer = tree[f"layers_{i}"]
        layer["norm"] = ones(cfg["dim"])
        if kind == MAMBA:
            layer["mamba"].update(mamba_vectors(np.random.default_rng(seeds[len(shapes) + i]), cfg))
    head = tree["lm_head"]["kernel"]
    head[:, : dense.TEXT_IDS] = (head[:, : dense.TEXT_IDS].astype(np.float32) * TEXT_COL_SCALE).astype(head.dtype)
    return tree


def write_checkpoint(path: Path, model: dict, seed: int) -> None:
    from sentio_tpu.runtime.checkpoint import save_pytree

    save_pytree(path, make_params(model, seed),
                meta={"family": "nemotron_h", "config": program_config(model)})


# ------------------------------------------------ bytes and operations


def block_counts(model: dict) -> dict:
    """Blocks of each letter at the file's depth."""
    pattern = model["hybrid_override_pattern"][: model["num_hidden_layers"]]
    return {kind: pattern.count(kind) for kind in (MAMBA, EXPERTS, ATTENTION)}


def mamba_widths(model: dict) -> tuple[int, int]:
    """(inner width, what the convolution runs over)."""
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    return inner, inner + 2 * model["n_groups"] * model["ssm_state_size"]


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token: bf16 pages in the ATTENTION blocks alone."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * BYTES_BF16 * block_counts(model)[ATTENTION]


def state_bytes(model: dict) -> int:
    """One sequence's (or one snapshot's) Mamba state: in every Mamba block
    the matrix a head in float32 and ``conv_kernel - 1`` columns in bf16."""
    inner, conv_dim = mamba_widths(model)
    return block_counts(model)[MAMBA] * (inner * model["ssm_state_size"] * BYTES_F32
                                         + (model["conv_kernel"] - 1) * conv_dim * BYTES_BF16)


def pool_bytes(model: dict, env: dict) -> int:
    """What the engine's pool reports: pages of K and V (the scratch page and
    ``slots x pages`` more), the state of every slot, and the snapshot pool."""
    slots = int(env["LLM_MAX_BATCH"])
    pages = 1 + slots * int(env["KV_MAX_PAGES_PER_SEQ"])
    return (pages * int(env["KV_PAGE_SIZE"]) * kv_bytes_per_token(model)
            + (slots + int(env["SSM_SNAPSHOTS"])) * state_bytes(model))


def weight_params(model: dict) -> dict:
    """Parameters: a Mamba block, an attention block, the router (with its
    bias), ONE routed expert, the shared expert, the embedding (the head is as
    large again), each without its norm."""
    d = model["hidden_size"]
    inner, conv_dim = mamba_widths(model)
    heads = model["mamba_num_heads"]
    q, kv = (model[k] * model["head_dim"] for k in ("num_attention_heads", "num_key_value_heads"))
    return {"mamba": (d * (inner + conv_dim + heads) + conv_dim * (model["conv_kernel"] + 1) + 3 * heads
                      + inner + inner * d),
            "attention": d * q + 2 * d * kv + q * d,
            "router": d * model["n_routed_experts_router"] + model["n_routed_experts_router"],
            "expert": 2 * d * model["moe_intermediate_size"],
            "shared": 2 * d * model["moe_shared_expert_intermediate_size"],
            "table": model["vocab_size"] * d}


def model_weights(model: dict) -> int:
    """Parameters of the file's depth and share (norms left out)."""
    w, n = weight_params(model), block_counts(model)
    return (n[MAMBA] * w["mamba"] + n[ATTENTION] * w["attention"]
            + n[EXPERTS] * (w["router"] + model["n_routed_experts"] * w["expert"] + w["shared"]) + 2 * w["table"])


def rows_advancing(model: dict, context_tokens: float) -> float:
    """At least this many rows hold ``context_tokens``: none holds more than
    its page table (``families/cohere2_moe.py`` says why a lower bound)."""
    env = model["serve_env"]
    return context_tokens / (int(env["KV_MAX_PAGES_PER_SEQ"]) * int(env["KV_PAGE_SIZE"]))


def experts_touched(model: dict, rows: float) -> float:
    """Of the experts held, those ``rows`` tokens reach, each picking
    ``num_experts_per_tok`` of the router's ``n_routed_experts_router``
    evenly: held x (1 - (1 - k/E)^rows)."""
    share = model["num_experts_per_tok"] / model["n_routed_experts_router"]
    return model["n_routed_experts"] * (1.0 - (1.0 - share) ** rows)


def pairs_held(model: dict) -> float:
    """Of a token's picks, those that fall on an expert held here under even routing."""
    return model["num_experts_per_tok"] * model["n_routed_experts"] / model["n_routed_experts_router"]


def decode_substep_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """One decode sub-step: the Mamba and attention blocks' matrices, every
    routed block's router, shared expert and the experts the advancing rows
    touch, the head (the embedding is a gather of ``rows`` rows), K and V of
    the attention blocks, and each advancing row's Mamba state read and
    written. 2 operations a multiply-add of every matmul an advancing row
    goes through — the state's update and read-out among them (``S`` is
    multiplied into twice a step) — plus QK and PV over the context in the
    attention blocks."""
    w, blocks = weight_params(model), block_counts(model)
    n = rows_advancing(model, context_tokens)
    mixers = blocks[MAMBA] * w["mamba"] + blocks[ATTENTION] * w["attention"]
    weights = (mixers + blocks[EXPERTS] * (w["router"] + w["shared"] + experts_touched(model, n) * w["expert"])
               + w["table"])
    bytes_ = (BYTES_BF16 * (weights + rows * model["hidden_size"])
              + context_tokens * kv_bytes_per_token(model) + 2 * n * state_bytes(model))
    row = (mixers + blocks[EXPERTS] * (w["router"] + w["shared"] + pairs_held(model) * w["expert"]) + w["table"]
           + 2 * blocks[MAMBA] * mamba_widths(model)[0] * model["ssm_state_size"])
    attn = 4 * context_tokens * model["num_attention_heads"] * model["head_dim"] * blocks[ATTENTION]
    return {"bytes": float(bytes_), "flops": float(2 * n * row + attn)}


def expert_mlp_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the grouped expert matmul in a decode sub-step — one of a
    routed block's two (up, down: ``hidden x expert width`` each): the
    matrices of the experts the advancing rows touch, 2 operations a
    multiply-add of the pairs held here. The pairs' rows in and out are left
    out: the share reads a little low."""
    n = rows_advancing(model, context_tokens)
    matrix = model["hidden_size"] * model["moe_intermediate_size"]
    return {"bytes": float(BYTES_BF16 * experts_touched(model, n) * matrix),
            "flops": float(2 * n * pairs_held(model) * matrix)}


def paged_attention_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the decode attention kernel (one attention block of one
    sub-step): its K and V of the context, QK and PV."""
    heads, hd = model["num_attention_heads"], model["head_dim"]
    per_token = 2 * model["num_key_value_heads"] * hd * BYTES_BF16
    return {"bytes": float(context_tokens * per_token), "flops": float(4 * context_tokens * heads * hd)}


KERNEL_COSTS = {"expert_mlp": expert_mlp_cost, "paged_attention": paged_attention_cost}


# ------------------------------------------------------ the reference check


def init_params(key, cfg) -> dict:
    """The tree of the program's ``init_nemotron_h`` as that function draws it
    (its distributions ARE the sizes above), with the head's columns for the
    text ids scaled as ``make_params`` scales them: the check reads what a
    cell serves."""
    import jax.numpy as jnp

    from sentio_tpu.models.nemotron_h import init_nemotron_h

    tree = init_nemotron_h(key, cfg)
    head = tree["lm_head"]["kernel"]
    cols = jnp.where(jnp.arange(head.shape[1]) < dense.TEXT_IDS, TEXT_COL_SCALE, 1.0)
    tree["lm_head"] = {"kernel": head * cols[None, :]}
    return tree


def is_matrix(leaf) -> bool:
    """What a checkpoint holds in bf16: matrices and stacks of them. The
    convolution's taps ``[conv_dim, 4]`` are a matrix by their shape and
    float32 by ``FLOAT32_LEAVES``: told apart by their last axis, which no
    matrix of a model has (four columns)."""
    return leaf.ndim >= 2 and leaf.shape[-1] > 4


def reference_kwargs(model: dict) -> dict:
    cfg = program_config(model)
    keys = ("n_heads", "n_kv_heads", "norm_eps", "mamba_heads", "mamba_head_dim", "n_groups", "ssm_state",
            "experts_per_token", "norm_topk_prob", "norm_topk_eps", "routed_scaling_factor", "experts_held",
            "expert_offset")
    return {key: cfg[key] for key in keys}


def reference_params(tree: dict, n_layers: int) -> dict:
    """The program's tree under the reference's flat names, every matrix in
    the checkpoint's own bf16: the reference widens one where it uses it.
    Every block of the tree goes (``n_layers`` counts the check's layers,
    which this family's ``check_layers`` may have made two blocks each)."""
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    out = {"embed": np.asarray(tree["embed_tokens"]["embedding"]), "head": np.asarray(tree["lm_head"]["kernel"]),
           "final_norm": f32(tree["final_norm"]["scale"]), "layers": []}
    for i in range(sum(1 for name in tree if name.startswith("layers_"))):
        lp = tree[f"layers_{i}"]
        layer = {"norm": f32(lp["norm"]["scale"])}
        if "mamba" in lp:
            mp = lp["mamba"]
            layer.update(w_in=np.asarray(mp["w_in"]["kernel"]), w_out=np.asarray(mp["w_out"]["kernel"]),
                         out_norm=f32(mp["out_norm"]["scale"]),
                         **{k: f32(mp[k]) for k in ("conv_kernel", "conv_bias", "dt_bias", "a_log", "d")})
        elif "attn" in lp:
            attn = lp["attn"]
            layer.update({k: np.asarray(attn[k]["kernel"]) for k in ("wq", "wk", "wv", "wo")})
        else:
            moe = lp["moe"]
            layer.update(router=np.asarray(moe["router"]["kernel"]), bias=f32(moe["bias"]),
                         w_up=np.asarray(moe["w_up"]), w_down=np.asarray(moe["w_down"]),
                         shared_up=np.asarray(moe["shared"]["w_up"][0]),
                         shared_down=np.asarray(moe["shared"]["w_down"][0]))
        out["layers"].append(layer)
    return out


def paged_pieces(engine, cfg, rows: int, width: int):
    """→ ``(state, prefill, decode)`` as ``families/llama.py`` has them, each
    piece returning ``(logits, state, {"experts": picks})``. The state is what
    the engine's pool holds for THIS family's pieces: K and V pages of the
    attention blocks, and the Mamba state of the pieces' own ``rows``
    sequences, where the engine keeps one a decode slot (no snapshot: the
    pieces serve no prefix). Prefill is the admission forward from zeros into
    a fresh cache — the chunked scan —, ``scatter_prefill`` of its K and V,
    and each row's state taken at ITS length; decode is
    ``paged_decode_forward`` over pool and state with the engine's own kernel
    selection — the one-token update."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.nemotron_h import init_nemotron_cache, zero_state
    from sentio_tpu.runtime.paged import paged_decode_forward, scatter_prefill

    forward_fn, attn_impl = engine.forward_fn, engine._attn_impl

    @jax.jit
    def prefill(params, ids, positions, lens, blocks, state):
        k_pages, v_pages, _ssm = state
        pad = jnp.arange(width)[None, :] < lens[:, None]
        logits, cache, routed = forward_fn(params, cfg, ids, positions=positions,
                                           cache=init_nemotron_cache(cfg, rows, width), cache_index=0, pad_mask=pad)
        k_pages, v_pages = scatter_prefill(k_pages, v_pages, cache["k"], cache["v"], blocks)
        return logits, (k_pages, v_pages, cache["state"]), {"experts": routed["experts"]}

    @jax.jit
    def decode(params, tok, lens, table, state):
        k_pages, v_pages, ssm = state
        logits, k_pages, v_pages, routed, ssm, _snaps = paged_decode_forward(
            params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=attn_impl, return_routed=True, conv=ssm)
        return logits, (k_pages, v_pages, ssm), {"experts": routed["experts"]}

    return (engine.pool.k, engine.pool.v, zero_state(cfg, rows)), prefill, decode


def served(engine, prompts, max_new_tokens):
    """The requests through ``engine.run_all`` → (results, each request's own
    picks ``{"experts": [routed blocks, prompt + answer tokens - 1, k]}``,
    negative where the radix cache served the position)."""
    results = engine.run_all(prompts, max_new_tokens=max_new_tokens, return_choices=True)
    return results, [r.choices for r in results]
