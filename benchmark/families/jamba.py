"""Checkpoint family ``jamba``: AI21-Jamba2-3B as the program runs it through
``JambaConfig`` (``sentio_tpu/models/jamba.py``) — every layer a mixer and a
dense SwiGLU; the mixer attention where ``i % attn_layer_period ==
attn_layer_offset`` (20 query heads on ONE KV head, rotation-free) and a
Mamba-1 mixer everywhere else (inner 5120, state 16, Δ through a rank-160
bottleneck, four taps, an RMSNorm each on Δ, B and C); a head tied to the
embedding. The whole contract of ``families/llama.py``'s docstring is here;
what a reader of this family needs beyond it:

THE DEPLOYMENT. One chip holds the model WHOLE: 28 layers, the whole
vocabulary, 3.03 B parameters, 6.06 GB of bf16. Nothing is cut.

WHAT A SEQUENCE KEEPS. K and V in the 2 attention layers of 28 (1,024 B a
token), and in the 26 Mamba layers a STATE whatever the length: ``S [5120,
16]`` float32 a layer (327,680 B; the program holds it transposed) and three
columns of 5,120 (bf16), kept per decode slot and in ``SSM_SNAPSHOTS``
snapshots for the prefix cache — 9,318,400 B a sequence, the K and V of 71
pages. ``pool_bytes`` counts all of it, to the byte the engine's pool reports.

THE SEEDED TREE AND A TIED HEAD. ``families/cohere2_moe.py`` has the
reasoning, ``models/jamba.py`` the sizes (the embedding a quarter as large, the
query projection four times, the mixers' output projections 0.3); the text
ids' rows of the table are a quarter again (``TEXT_ROW_SCALE``), so that no
answer holds a text id or ends early. The Mamba's vectors take the published
initialisation: ``A_log = log(1..16)`` along the state's columns, ``b_dt`` the
inverse softplus of a step log-uniform in 0.001..0.1, ``D`` ones; ``A_log`` is
held ``[N, inner]`` as the program holds it.

THE CHECK'S DEPTH. ``check_config`` keeps the model's own first layers where
they hold both mixers (8: layers 0..7, seven Mamba and the attention layer);
at fewer (the yardstick's CPU tests hold a rehearsal to two) the period is cut
to the depth with the attention layer last, so that every kind is there. The
served part sends a cold prompt in segments and others over its cached head: a
carried state, a restored snapshot and a snapshot written at a segment's end
are all in the comparison.

COSTS. A decode sub-step reads every weight once (the model is dense), the K
and V of the context in the two attention layers, and each advancing row's
Mamba state once and writes it once a Mamba layer; the rows that advance are
bounded from below as in ``families/cohere2_moe.py`` (a share reads low, never
high).
"""

from __future__ import annotations

import concurrent.futures
import os
from pathlib import Path

import numpy as np

from benchmark.families import llama as dense

BYTES_BF16, BYTES_F32 = dense.BYTES_BF16, 4
REFERENCE = "benchmark.jamba_reference"
TEXT_ROW_SCALE = 0.25

# published key → field of the program's config object (``JambaConfig``)
WIDTHS = {
    "hidden_size": "dim", "intermediate_size": "mlp_dim", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
    "rms_norm_eps": "norm_eps", "max_position_embeddings": "max_len", "attn_layer_period": "attn_layer_period",
    "attn_layer_offset": "attn_layer_offset", "mamba_d_state": "mamba_d_state", "mamba_d_conv": "mamba_d_conv",
    "mamba_dt_rank": "mamba_dt_rank", "mamba_expand": "mamba_expand", "mamba_conv_bias": "mamba_conv_bias",
    "mamba_proj_bias": "mamba_proj_bias", "tie_word_embeddings": "tie_word_embeddings",
    "num_experts": "num_experts", "torch_dtype": "dtype",
}


def program_config(model: dict) -> dict:
    """Published keys → ``JambaConfig`` fields, every one. What the program's
    layer has no switch for is held here: another activation, more than one
    expert a token or a window is another model."""
    import sentio_tpu.models.jamba  # noqa: F401 — a program without this family fails here, at once

    stated = (model["hidden_act"], model["num_experts_per_tok"], model["sliding_window"])
    assert stated == ("silu", 1, None), stated
    return dict(
        vocab_size=int(model["vocab_size"]), dim=int(model["hidden_size"]), n_layers=int(model["num_hidden_layers"]),
        n_heads=int(model["num_attention_heads"]), n_kv_heads=int(model["num_key_value_heads"]),
        mlp_dim=int(model["intermediate_size"]), max_len=int(model["max_position_embeddings"]),
        rope_theta=0.0, dtype=str(model.get("torch_dtype", "bfloat16")), norm_eps=float(model["rms_norm_eps"]),
        attn_layer_period=int(model["attn_layer_period"]), attn_layer_offset=int(model["attn_layer_offset"]),
        mamba_d_state=int(model["mamba_d_state"]), mamba_d_conv=int(model["mamba_d_conv"]),
        mamba_dt_rank=int(model["mamba_dt_rank"]), mamba_expand=int(model["mamba_expand"]),
        mamba_conv_bias=bool(model["mamba_conv_bias"]), mamba_proj_bias=bool(model["mamba_proj_bias"]),
        tie_word_embeddings=bool(model["tie_word_embeddings"]), num_experts=int(model["num_experts"]),
    )


def check_layers(model: dict, layers: int) -> dict:
    """The keys of ``model`` that say which layers a check of ``layers``
    keeps: the model's own first ones where both mixers are among them, else
    a period of ``layers`` with the attention layer last."""
    period, offset = int(model["attn_layer_period"]), int(model["attn_layer_offset"])
    if not offset < layers <= int(model["num_hidden_layers"]) or layers < 2:
        period, offset = layers, layers - 1
    return {"num_hidden_layers": layers, "attn_layer_period": period, "attn_layer_offset": offset}


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.jamba import JambaConfig

    return JambaConfig(**{**program_config({**model, **check_layers(model, layers)}), "max_len": max_len})


# ------------------------------------------------------------ seeded weights


def mamba_layers(cfg: dict) -> list[int]:
    return [i for i in range(cfg["n_layers"]) if i % cfg["attn_layer_period"] != cfg["attn_layer_offset"]]


def leaf_shapes(cfg: dict) -> dict:
    """``{path: (shape, std)}`` of every matrix of the program's
    ``init_jamba`` tree, one generator each, so that they fill in parallel."""
    from sentio_tpu.models.jamba import CONV_BIAS_STD, EMBED_STD, WO_SCALE, WQ_SCALE

    d, f, hd = cfg["dim"], cfg["mlp_dim"], cfg["dim"] // cfg["n_heads"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    inner, n, rank = cfg["mamba_expand"] * d, cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    out = {("embed_tokens", "embedding"): ((cfg["vocab_size"], d), EMBED_STD)}
    mamba = set(mamba_layers(cfg))
    for i in range(cfg["n_layers"]):
        layer = f"layers_{i}"
        if i in mamba:
            out[(layer, "mamba", "w_in", "kernel")] = ((d, 2 * inner), d ** -0.5)
            out[(layer, "mamba", "conv_kernel")] = ((inner, cfg["mamba_d_conv"]), cfg["mamba_d_conv"] ** -0.5)
            if cfg["mamba_conv_bias"]:
                out[(layer, "mamba", "conv_bias")] = ((inner,), CONV_BIAS_STD)
            out[(layer, "mamba", "w_x", "kernel")] = ((inner, rank + 2 * n), inner ** -0.5)
            out[(layer, "mamba", "w_dt", "kernel")] = ((rank, inner), rank ** -0.5)
            out[(layer, "mamba", "w_out", "kernel")] = ((inner, d), WO_SCALE * inner ** -0.5)
        else:
            out[(layer, "attn", "wq", "kernel")] = ((d, q), WQ_SCALE * d ** -0.5)
            out[(layer, "attn", "wk", "kernel")] = ((d, kv), d ** -0.5)
            out[(layer, "attn", "wv", "kernel")] = ((d, kv), d ** -0.5)
            out[(layer, "attn", "wo", "kernel")] = ((q, d), WO_SCALE * q ** -0.5)
        for name, (n_in, n_out) in {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}.items():
            out[(layer, "mlp", name, "kernel")] = ((n_in, n_out), n_in ** -0.5)
    return out


# leaves a checkpoint holds in float32 beside the norm scales: the taps and
# their bias (the Mamba's other vectors are made in ``mamba_vectors``)
FLOAT32_LEAVES = (("mamba", "conv_kernel"), ("mamba", "conv_bias"))


def mamba_vectors(rng: np.random.Generator, cfg: dict) -> dict:
    """A Mamba layer's float32 vectors under the published initialisation:
    ``A_log = log(1..N)`` along the state's columns (held ``[N, inner]``),
    ``b_dt`` the inverse softplus of a step log-uniform in 0.001..0.1
    (floored), ``D`` ones, unit weights for the three inner norms."""
    from sentio_tpu.models.jamba import DT_FLOOR, DT_MAX, DT_MIN

    inner, n = cfg["mamba_expand"] * cfg["dim"], cfg["mamba_d_state"]
    dt = np.maximum(np.exp(rng.random(inner) * (np.log(DT_MAX) - np.log(DT_MIN)) + np.log(DT_MIN)), DT_FLOOR)
    ones = lambda k: {"scale": np.ones((k,), np.float32)}  # noqa: E731
    return {"dt_bias": (dt + np.log(-np.expm1(-dt))).astype(np.float32),
            "a_log": np.ascontiguousarray(np.broadcast_to(
                np.log(np.arange(1, n + 1, dtype=np.float32))[:, None], (n, inner))),
            "d": np.ones((inner,), np.float32),
            "dt_norm": ones(cfg["mamba_dt_rank"]), "b_norm": ones(n), "c_norm": ones(n)}


def make_params(model: dict, seed: int) -> dict:
    """The tree of the program's ``init_jamba`` in bf16 from ``seed``: one
    generator a matrix, all filled in parallel, so the tree depends on the
    seed alone. The text ids' rows of the embedding — which is the head too —
    are scaled by ``TEXT_ROW_SCALE``."""
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
    for path, leaf in zip(shapes, filled):
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    ones = lambda n: {"scale": np.ones((n,), np.float32)}  # noqa: E731
    tree["final_norm"] = ones(cfg["dim"])
    mamba = set(mamba_layers(cfg))
    for i in range(cfg["n_layers"]):
        layer = tree[f"layers_{i}"]
        layer["norm"], layer["mlp_norm"] = ones(cfg["dim"]), ones(cfg["dim"])
        if i in mamba:
            layer["mamba"].update(mamba_vectors(np.random.default_rng(seeds[len(shapes) + i]), cfg))
    table = tree["embed_tokens"]["embedding"]
    table[: dense.TEXT_IDS] = (table[: dense.TEXT_IDS].astype(np.float32) * TEXT_ROW_SCALE).astype(table.dtype)
    return tree


def write_checkpoint(path: Path, model: dict, seed: int) -> None:
    from sentio_tpu.runtime.checkpoint import save_pytree

    save_pytree(path, make_params(model, seed), meta={"family": "jamba", "config": program_config(model)})


# ------------------------------------------------ bytes and operations


def layer_counts(model: dict) -> tuple[int, int]:
    """(attention layers, Mamba layers) of the file's depth."""
    attn = sum(i % model["attn_layer_period"] == model["attn_layer_offset"] for i in range(model["num_hidden_layers"]))
    return attn, model["num_hidden_layers"] - attn


def head_dim(model: dict) -> int:
    return model["hidden_size"] // model["num_attention_heads"]


def inner_width(model: dict) -> int:
    return model["mamba_expand"] * model["hidden_size"]


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token: bf16 pages in the ATTENTION layers alone."""
    return 2 * model["num_key_value_heads"] * head_dim(model) * BYTES_BF16 * layer_counts(model)[0]


def state_bytes(model: dict) -> int:
    """One sequence's (or one snapshot's) Mamba state: in every Mamba layer
    ``S [inner, N]`` in float32 and ``mamba_d_conv - 1`` columns in bf16."""
    inner = inner_width(model)
    return layer_counts(model)[1] * (inner * model["mamba_d_state"] * BYTES_F32
                                     + (model["mamba_d_conv"] - 1) * inner * BYTES_BF16)


def pool_bytes(model: dict, env: dict) -> int:
    """What the engine's pool reports: pages of K and V (the scratch page and
    ``slots x pages`` more), the state of every slot, and the snapshot pool."""
    slots = int(env["LLM_MAX_BATCH"])
    pages = 1 + slots * int(env["KV_MAX_PAGES_PER_SEQ"])
    return (pages * int(env["KV_PAGE_SIZE"]) * kv_bytes_per_token(model)
            + (slots + int(env["SSM_SNAPSHOTS"])) * state_bytes(model))


def weight_params(model: dict) -> dict:
    """Parameters: a Mamba mixer (its taps, bias, vectors and three inner
    norms among them), an attention mixer, the SwiGLU, the table (tied:
    once), a layer's two norms."""
    d, inner, n, rank = model["hidden_size"], inner_width(model), model["mamba_d_state"], model["mamba_dt_rank"]
    q, kv = (model[k] * head_dim(model) for k in ("num_attention_heads", "num_key_value_heads"))
    return {"mamba": (d * 2 * inner + inner * (model["mamba_d_conv"] + 1) + inner * (rank + 2 * n)
                      + rank * inner + inner + inner * n + inner + (rank + 2 * n) + inner * d),
            "attention": d * q + 2 * d * kv + q * d,
            "mlp": 3 * d * model["intermediate_size"],
            "table": model["vocab_size"] * d,
            "norms": 2 * d}


def model_weights(model: dict) -> int:
    """Every parameter of the file's depth: the mixers, the SwiGLUs, the
    norms, the table once and the final norm."""
    w, (attn, mamba) = weight_params(model), layer_counts(model)
    return (mamba * w["mamba"] + attn * w["attention"] + (attn + mamba) * (w["mlp"] + w["norms"])
            + w["table"] + model["hidden_size"])


def rows_advancing(model: dict, context_tokens: float) -> float:
    """At least this many rows hold ``context_tokens``: none holds more than
    its page table (``families/cohere2_moe.py`` says why a lower bound)."""
    env = model["serve_env"]
    return context_tokens / (int(env["KV_MAX_PAGES_PER_SEQ"]) * int(env["KV_PAGE_SIZE"]))


def decode_substep_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """One decode sub-step: every weight once — the table as the head (the
    embedding is a gather of ``rows`` rows) —, K and V of the attention
    layers, and each advancing row's Mamba state read and written once. 2
    operations a multiply-add of every matmul a row goes through (every slot
    of the fixed batch is computed), the state's update and read-out of the
    rows that advance, plus QK and PV over the context in the attention
    layers."""
    w, (attn, mamba) = weight_params(model), layer_counts(model)
    n = rows_advancing(model, context_tokens)
    weights = model_weights(model)
    bytes_ = (BYTES_BF16 * (weights + rows * model["hidden_size"])
              + context_tokens * kv_bytes_per_token(model) + 2 * n * state_bytes(model))
    state_ops = 2 * n * 2 * mamba * inner_width(model) * model["mamba_d_state"]
    scores = 4 * context_tokens * model["num_attention_heads"] * head_dim(model) * attn
    matmuls = 2 * rows * (mamba * w["mamba"] + attn * w["attention"] + (attn + mamba) * w["mlp"] + w["table"])
    return {"bytes": float(bytes_), "flops": float(matmuls + state_ops + scores)}


def paged_attention_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the decode attention kernel (one attention layer of one
    sub-step): its K and V of the context, QK and PV."""
    heads, hd = model["num_attention_heads"], head_dim(model)
    per_token = 2 * model["num_key_value_heads"] * hd * BYTES_BF16
    return {"bytes": float(context_tokens * per_token), "flops": float(4 * context_tokens * heads * hd)}


KERNEL_COSTS = {"paged_attention": paged_attention_cost}


# ------------------------------------------------------ the reference check


def init_params(key, cfg) -> dict:
    """The tree of the program's ``init_jamba`` as that function draws it (its
    distributions ARE the sizes above), with the text ids' rows of the table
    scaled as ``make_params`` scales them: the check reads what a cell serves."""
    import jax.numpy as jnp

    from sentio_tpu.models.jamba import init_jamba

    tree = init_jamba(key, cfg)
    table = tree["embed_tokens"]["embedding"]
    rows = jnp.where(jnp.arange(table.shape[0]) < dense.TEXT_IDS, TEXT_ROW_SCALE, 1.0)
    tree["embed_tokens"] = {"embedding": table * rows[:, None]}
    return tree


def is_matrix(leaf) -> bool:
    """What a checkpoint holds in bf16: the matrices. The taps ``[inner, 4]``
    and ``A_log [16, inner]`` are two-dimensional and float32: told apart by an
    axis no matrix of a model has (four columns; sixteen rows or fewer)."""
    return leaf.ndim == 2 and leaf.shape[-1] > 4 and leaf.shape[0] > 16


def reference_kwargs(model: dict) -> dict:
    return dict(n_heads=int(model["num_attention_heads"]), n_kv_heads=int(model["num_key_value_heads"]),
                norm_eps=float(model["rms_norm_eps"]), d_state=int(model["mamba_d_state"]),
                dt_rank=int(model["mamba_dt_rank"]))


def reference_params(tree: dict, n_layers: int) -> dict:
    """The program's tree under the reference's flat names, every matrix in
    the checkpoint's own bf16: the reference widens one where it uses it."""
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    out = {"embed": np.asarray(tree["embed_tokens"]["embedding"]), "final_norm": f32(tree["final_norm"]["scale"]),
           "layers": []}
    for i in range(n_layers):
        lp = tree[f"layers_{i}"]
        layer = {"norm": f32(lp["norm"]["scale"]), "mlp_norm": f32(lp["mlp_norm"]["scale"]),
                 **{k: np.asarray(lp["mlp"][k]["kernel"]) for k in ("w_gate", "w_up", "w_down")}}
        if "mamba" in lp:
            mp = lp["mamba"]
            layer.update({k: np.asarray(mp[k]["kernel"]) for k in ("w_in", "w_x", "w_dt", "w_out")},
                         **{k: f32(mp[k]["scale"]) for k in ("dt_norm", "b_norm", "c_norm")},
                         **{k: f32(mp[k]) for k in ("conv_kernel", "dt_bias", "a_log", "d")},
                         conv_bias=f32(mp["conv_bias"]) if "conv_bias" in mp else np.zeros_like(f32(mp["d"])))
        else:
            layer.update({k: np.asarray(lp["attn"][k]["kernel"]) for k in ("wq", "wk", "wv", "wo")})
        out["layers"].append(layer)
    return out


def paged_pieces(engine, cfg, rows: int, width: int):
    """→ ``(state, prefill, decode)`` as ``families/llama.py`` has them. The
    state is what the engine's pool holds for THIS family's pieces: K and V
    pages of the attention layers, and the Mamba state of the pieces' own
    ``rows`` sequences (no snapshot: the pieces serve no prefix), threaded as
    ``(K pages, V pages, {conv, ssm})``. Prefill is the admission forward from
    zeros into a fresh cache — the selective scan —,
    ``scatter_prefill`` of its K and V, and each row's state taken at ITS
    length; decode is ``paged_decode_forward`` over pool and state with the
    engine's own kernel selection — the one-token update."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.jamba import init_jamba_cache, zero_state
    from sentio_tpu.runtime.paged import paged_decode_forward, scatter_prefill

    forward_fn, attn_impl = engine.forward_fn, engine._attn_impl

    @jax.jit
    def prefill(params, ids, positions, lens, blocks, state):
        k_pages, v_pages, _ssm = state
        pad = jnp.arange(width)[None, :] < lens[:, None]
        logits, cache = forward_fn(params, cfg, ids, positions=positions,
                                   cache=init_jamba_cache(cfg, rows, width), cache_index=0, pad_mask=pad)
        k_pages, v_pages = scatter_prefill(k_pages, v_pages, cache["k"], cache["v"], blocks)
        return logits, (k_pages, v_pages, cache["state"])

    @jax.jit
    def decode(params, tok, lens, table, state):
        k_pages, v_pages, ssm = state
        logits, k_pages, v_pages, _routed, ssm, _snaps = paged_decode_forward(
            params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=attn_impl, conv=ssm)
        return logits, (k_pages, v_pages, ssm)

    return (engine.pool.k, engine.pool.v, zero_state(cfg, rows)), prefill, decode
