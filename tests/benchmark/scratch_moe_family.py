"""A second family for the seam test, as files and nothing else: the
program's own ``moe`` family (``models/moe.py``: the dense attention, routed
SwiGLU experts behind a capacity dispatch) held to the dropless reference
beside this file. ``capacity_factor`` is experts over experts per token, so
an expert's buffer holds every token of a call and none is dropped.

Only what ``check.py`` asks of a family is here (``families/llama.py`` has
the whole contract). The state a token leaves is K and V, as in the dense
family, and the teacher-forced pieces are the dense family's — but this
family CHOOSES: which experts a token goes to is decided by rank, so its
pieces also say what the program chose (``CHOICES``, ``reading_picks``).
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark.families import llama as dense

REFERENCE = "scratch_moe_reference"
# what the forward decides by rank → the reference's keyword for how many it takes
CHOICES = {"experts": "experts_per_token"}


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.moe import MoeConfig

    experts, chosen = int(model["num_local_experts"]), int(model["num_experts_per_tok"])
    return MoeConfig(**{**dense.program_config(model), "n_layers": layers, "max_len": max_len},
                     n_experts=experts, experts_per_token=chosen,
                     capacity_factor=experts / chosen)


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
