"""A second family for the seam test, as files and nothing else: the
program's own ``moe`` family (``models/moe.py``: the dense attention, routed
SwiGLU experts behind a capacity dispatch) held to the dropless reference
beside this file. ``capacity_factor`` is experts over experts per token, so
an expert's buffer holds every token of a call and none is dropped.

The WHOLE contract is here (``families/llama.py`` has it): the served half
that ``run.py`` asks for (``program_config``, ``WIDTHS``, ``write_checkpoint``,
``pool_bytes``), the check's half, and the rooflines' counts. The state a
token leaves is K and V, as in the dense family, and the teacher-forced
pieces are the dense family's — but this family CHOOSES: which experts a
token goes to is decided by rank, so its pieces also say what the program
chose (``CHOICES``, ``reading_picks``).

Two ways in, one file: registered for a test under the name ``load_family``
looks up (``test_benchmark_family_seam.py``), or laid into a copy of
``benchmark/`` as ``families/scratch_moe.py`` with its reference beside it
(``test_benchmark_second_family.py``: the whole run in child processes).
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark.families import llama as dense

# beside this file, wherever that is: a top-level module next to the tests,
# ``benchmark.families.scratch_moe_reference`` in a tree the file is laid into
REFERENCE = f"{__package__}.scratch_moe_reference" if __package__ else "scratch_moe_reference"
# what the forward decides by rank → the reference's keyword for how many it takes
CHOICES = {"experts": "experts_per_token"}


# published key → field of the program's config object (``MoeConfig``)
WIDTHS = {**dense.WIDTHS, "num_local_experts": "n_experts", "num_experts_per_tok": "experts_per_token"}


def program_config(model: dict) -> dict:
    """Published keys → ``sentio_tpu.models.moe.MoeConfig`` fields, every one
    (``/info`` reports the whole dataclass). ``capacity_factor`` is experts
    over experts a token: an expert's buffer then holds every token of a call
    and the program's capacity dispatch drops none, as the reference drops
    none; ``router_aux_weight`` is the program's default, a training term."""
    experts, chosen = int(model["num_local_experts"]), int(model["num_experts_per_tok"])
    return {**dense.program_config(model), "n_experts": experts, "experts_per_token": chosen,
            "capacity_factor": experts / chosen, "router_aux_weight": 0.01}


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.moe import MoeConfig

    return MoeConfig(**{**program_config(model), "n_layers": layers, "max_len": max_len})


# ------------------------------------------------------------ seeded weights


def _layer(rng, cfg: dict) -> dict:
    """One layer of the program's ``init_moe`` tree in bf16: the dense
    family's attention, a router and three stacks of expert matrices."""
    dim, mlp, experts = cfg["dim"], cfg["mlp_dim"], cfg["n_experts"]
    kv_dim = cfg["n_kv_heads"] * (dim // cfg["n_heads"])
    matrix = lambda n_in, n_out: {"kernel": dense.normal_bf16(rng, (n_in, n_out), n_in ** -0.5)}  # noqa: E731
    stack = lambda n_in, n_out: dense.normal_bf16(rng, (experts, n_in, n_out), n_in ** -0.5)  # noqa: E731
    return {
        "attn_norm": {"scale": np.ones((dim,), np.float32)},
        "attn": {"wq": matrix(dim, dim), "wk": matrix(dim, kv_dim),
                 "wv": matrix(dim, kv_dim), "wo": matrix(dim, dim)},
        "mlp_norm": {"scale": np.ones((dim,), np.float32)},
        "moe": {"router": matrix(dim, experts), "w_gate": stack(dim, mlp),
                "w_up": stack(dim, mlp), "w_down": stack(mlp, dim)},
    }


def make_params(model: dict, seed: int) -> dict:
    """The tree of the program's ``init_moe``, in bf16, from ``seed``: the
    dense family's trunk (its head's text columns zero, for its reason)."""
    return dense.seeded_tree(program_config(model), seed, _layer)


def write_checkpoint(path, model: dict, seed: int) -> None:
    from sentio_tpu.runtime.checkpoint import save_pytree

    save_pytree(path, make_params(model, seed), meta={"family": "moe", "config": program_config(model)})


# ------------------------------------------------ bytes and operations

pool_bytes = dense.pool_bytes  # K and V of every layer and nothing else: the dense family's pool


def weight_params(model: dict) -> dict:
    """Parameters a layer: its attention, its router, ONE expert; the head."""
    d, f = model["hidden_size"], model["intermediate_size"]
    q, kv = (model[k] * model["head_dim"] for k in ("num_attention_heads", "num_key_value_heads"))
    return {"attention": d * q + 2 * d * kv + q * d, "router": d * model["num_local_experts"],
            "expert": 3 * d * f, "head": model["vocab_size"] * d}


def experts_read(model: dict, rows: int) -> float:
    """The experts of one layer that ``rows`` tokens reach, each taking
    ``num_experts_per_tok`` of them: E (1 - (1 - k/E)^rows) under a router
    that spreads evenly, which a seeded one does. The LEAST a routed step
    must read; the program's capacity dispatch reads every expert."""
    experts, chosen = model["num_local_experts"], model["num_experts_per_tok"]
    return experts * (1.0 - (1.0 - chosen / experts) ** rows)


def decode_substep_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """One decode sub-step over ``rows`` slots (all computed) whose occupied
    rows hold ``context_tokens`` tokens: every layer's attention and router,
    the experts the rows reach (``experts_read``), the head, the embedding's
    ``rows`` rows, and the K and V the attention reads; 2 operations per
    multiply-add of every matmul a row goes through (its own
    ``num_experts_per_tok`` experts), plus QK and PV over the context."""
    w, n_layers = weight_params(model), model["num_hidden_layers"]
    layer = w["attention"] + w["router"] + experts_read(model, rows) * w["expert"]
    bytes_ = (dense.BYTES_BF16 * (n_layers * layer + w["head"] + rows * model["hidden_size"])
              + context_tokens * dense.kv_bytes_per_token(model))
    row = n_layers * (w["attention"] + w["router"] + model["num_experts_per_tok"] * w["expert"]) + w["head"]
    attn = 4 * context_tokens * model["num_attention_heads"] * model["head_dim"] * n_layers
    return {"bytes": float(bytes_), "flops": float(2 * rows * row + attn)}


# the decode attention kernel is the dense family's, over the same pool
KERNEL_COSTS = {"paged_attention": dense.paged_attention_cost}


# ------------------------------------------------------ the reference check


def init_params(key, cfg) -> dict:
    """The tree of the program's ``init_moe`` (its shapes are asked of it)
    with the program's distributions, every leaf drawn in ONE call: the
    program draws a stack expert by expert, 768 draws at 128 experts in 2
    layers, and that program takes 20 s to compile on the CPU at 64."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.moe import init_moe

    paths, tree = jax.tree_util.tree_flatten_with_path(jax.eval_shape(lambda k: init_moe(k, cfg), key))
    leaves = []
    for (path, leaf), k in zip(paths, jax.random.split(key, len(paths))):
        if path[-1].key == "scale":
            leaves.append(jnp.ones(leaf.shape, leaf.dtype))
        elif path[-1].key == "embedding":
            leaves.append(jax.random.normal(k, leaf.shape, leaf.dtype) * 0.02)
        else:  # a matrix or a stack of them: truncated normal over the fan-in
            leaves.append(jax.random.truncated_normal(k, -2.0, 2.0, leaf.shape, leaf.dtype)
                          * leaf.shape[-2] ** -0.5)
    return jax.tree_util.tree_unflatten(tree, leaves)


def is_matrix(leaf) -> bool:
    """Matrices and stacks of them ``[E, in, out]``: all bf16 in a checkpoint."""
    return leaf.ndim >= 2


def reference_kwargs(model: dict) -> dict:
    return {**dense.reference_kwargs(model), "experts_per_token": int(model["num_experts_per_tok"])}


def reference_params(tree: dict, n_layers: int) -> dict:
    out = dense.reference_trunk(tree)
    for i in range(n_layers):
        lp = tree[f"layers_{i}"]
        out["layers"].append({**dense.reference_attention(lp),
                              "router": np.asarray(lp["moe"]["router"]["kernel"], np.float32),
                              **{k: np.asarray(lp["moe"][k], np.float32)
                                 for k in ("w_gate", "w_up", "w_down")}})
    return out


@contextlib.contextmanager
def reading_picks():
    """The experts the program's OWN routing sent each token to, while a
    piece is traced: ``models/moe.py::route_topk`` hands ``moe_mlp`` a
    ``dispatch [G, E, C]`` of buffer positions, and the experts whose buffers
    hold a token are that token's picks — the tensor the timed code computes
    with, not a second routing beside it. Yields the list that fills with one
    ``[G, k]`` int32 a routed layer (-1 where a token got fewer than ``k``:
    padding, or a drop over capacity). The program has no output for its
    picks yet (PERF.md, Open questions), so ``route_topk`` is wrapped for the
    length of the trace."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models import moe

    picks, route_topk = [], moe.route_topk

    def reading(logits, k, capacity, valid=None):
        dispatch, combine, aux = route_topk(logits, k, capacity, valid)
        held, expert = jax.lax.top_k(dispatch.any(axis=-1).astype(jnp.int32), k)
        picks.append(jnp.where(held > 0, expert, -1).astype(jnp.int32))
        return dispatch, combine, aux

    moe.route_topk = reading
    try:
        yield picks
    finally:
        moe.route_topk = route_topk


def paged_pieces(engine, cfg, rows: int, width: int):
    """The dense family's pieces (same pool, same programs: the engine
    chooses the routed forward from the config's class), each traced under
    ``reading_picks`` and returning ``(logits, state, {"experts": picks})`` —
    prefill ``[layers, rows, width, k]``, decode ``[layers, rows, k]``."""
    import jax
    import jax.numpy as jnp

    state, prefill, decode = dense.paged_pieces(engine, cfg, rows, width)

    def saying_what_it_chose(piece, *shape):
        @jax.jit
        def traced(*args):
            with reading_picks() as picks:  # ``__wrapped__``: the piece's body, in THIS trace
                logits, pool = piece.__wrapped__(*args)
            return logits, pool, {"experts": jnp.stack(picks).reshape(cfg.n_layers, *shape)}
        return traced

    k = cfg.experts_per_token
    return state, saying_what_it_chose(prefill, rows, width, k), saying_what_it_chose(decode, rows, k)
