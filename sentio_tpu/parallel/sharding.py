"""Sharding specs: regex partition rules → NamedSharding over the mesh.

This is the tensor-parallel half of the comm layer (SURVEY.md §2.12): weight
matrices get PartitionSpecs by parameter-path pattern, activations get batch
sharding over the data axes, and XLA inserts the all-reduces. Rules follow
the Megatron layout — attention QKV and MLP up/gate column-sharded (output
feature dim on ``tp``), attention out and MLP down row-sharded (input feature
dim on ``tp``) — so each transformer block needs exactly two psums.
"""

from __future__ import annotations

import logging
import re
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sentio_tpu.parallel.mesh import AXIS_DCN, AXIS_DP, AXIS_EP, AXIS_TP

logger = logging.getLogger(__name__)

# (path regex, PartitionSpec). First match wins; unmatched params replicate.
# Param paths are "/"-joined pytree key paths, e.g. "layers_0/attn/wq/kernel".
Rules = Sequence[tuple[str, P]]

LLAMA_TP_RULES: Rules = (
    # embeddings: shard vocab dim (row) — logits psum'd at the head
    (r".*embed_tokens/embedding$", P(AXIS_TP, None)),
    (r".*lm_head/kernel$", P(None, AXIS_TP)),
    # attention: q/k/v column-parallel, o row-parallel. The serving tree
    # (models/llama.py ``serving_layout``) stores q/k/v [out, in]: the same
    # split of the output features is then a split of the rows
    (r".*attn/(wq|wk|wv)/kernel$", P(None, AXIS_TP)),
    (r".*attn/(wq_t|wk_t|wv_t)/kernel$", P(AXIS_TP, None)),
    (r".*attn/wo/kernel$", P(AXIS_TP, None)),
    # swiglu mlp: gate/up column-parallel, down row-parallel
    (r".*mlp/(w_gate|w_up)/kernel$", P(None, AXIS_TP)),
    (r".*mlp/w_down/kernel$", P(AXIS_TP, None)),
    # norms replicate
    (r".*norm.*", P()),
)

# MoE decoder: attention follows the Llama layout; expert-indexed weights
# shard experts over ``ep`` on the leading dim (expert parallelism — the
# dispatch/combine einsums become all_to_all-style collectives) and keep the
# Megatron column/row split on the per-expert matmul dims over ``tp``. The
# router is a tiny [d, E] projection — replicated.
MOE_EP_RULES: Rules = (
    (r".*moe/router/kernel$", P()),
    (r".*moe/(w_gate|w_up)$", P(AXIS_EP, None, AXIS_TP)),
    (r".*moe/w_down$", P(AXIS_EP, AXIS_TP, None)),
) + tuple(LLAMA_TP_RULES)

ENCODER_TP_RULES: Rules = (
    (r".*embed(_tokens|_positions)?/embedding$", P(None, None)),
    (r".*attn/(wq|wk|wv)/kernel$", P(None, AXIS_TP)),
    (r".*attn/wo/kernel$", P(AXIS_TP, None)),
    (r".*mlp/(w_gate|w_up|w_in)/kernel$", P(None, AXIS_TP)),
    (r".*mlp/(w_down|w_out)/kernel$", P(AXIS_TP, None)),
    (r".*", P()),
)


def path_str(path: tuple) -> str:
    parts = []
    for entry in path:
        if hasattr(entry, "key"):
            parts.append(str(entry.key))
        elif hasattr(entry, "idx"):
            parts.append(str(entry.idx))
        else:
            parts.append(str(entry))
    return "/".join(parts)


def spec_for(path: str, rules: Rules, ndim: int) -> P:
    """Resolve the PartitionSpec for one parameter path; pads/truncates the
    spec to the tensor rank so rules can be written for the common 2D case."""
    for pattern, spec in rules:
        if re.match(pattern, path):
            entries = tuple(spec)
            if len(entries) > ndim:
                entries = entries[-ndim:] if ndim > 0 else ()
            elif len(entries) < ndim:
                entries = (None,) * (ndim - len(entries)) + entries
            return P(*entries)
    return P()


def make_param_shardings(params: Any, mesh: Mesh, rules: Rules) -> Any:
    """Pytree of NamedShardings matching ``params``' structure."""

    def one(path, leaf):
        spec = spec_for(path_str(path), rules, getattr(leaf, "ndim", 0))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def shard_params(params: Any, mesh: Optional[Mesh], rules: Rules) -> Any:
    """Place a host pytree onto the mesh according to the rules — or, with
    no mesh, onto the default device. This is the startup weight-load step
    (reference's lazy first-request init inverted — SURVEY.md §3.3): every
    leaf goes from host memory straight to its final placement ONCE. A
    checkpoint tree left as host numpy would ride every jit call as an
    argument and be uploaded again on each dispatch."""
    if mesh is None:
        return jax.device_put(params)
    shardings = make_param_shardings(params, mesh, rules)
    return jax.device_put(params, shardings)


def place_encoder(model: str, params: Any, cfg: Any, mesh: Optional[Mesh],
                  owned: bool) -> tuple[Any, str, int]:
    """An encoder's weights as its serving class keeps them → (tree on the
    device or mesh, the dtype most of it is held in, its bytes). Cast ONCE,
    here, to what the forward computes in (models/transformer.py
    ``serving_dtypes``; ``owned``: the class made the tree, so each wide leaf
    is let go as it is cast), and only then placed: under ``ENCODER_TP_RULES``
    the shards are cut from the narrow leaves."""
    from sentio_tpu.infra import tracing
    from sentio_tpu.models.transformer import param_summary, serving_dtypes

    with tracing.span("weights.place", model=model) as place:
        params, cast, given_back = serving_dtypes(params, cfg, owned=owned)
        params = shard_params(params, mesh, ENCODER_TP_RULES)
        jax.block_until_ready(params)  # the span's seconds are the placement's
        dtype, held = param_summary(params)
        place.fields.update(bytes=held, leaves_cast=cast)
    logger.info("%s weights placed: %d leaves cast at load, %.3f GB given back, %.3f GB held (%s)",
                model, cast, given_back / 1e9, held / 1e9, dtype)
    return params, dtype, held


def batch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Shard the leading (batch) dim over all data axes, replicate the rest."""
    data = tuple(a for a in (AXIS_DCN, AXIS_DP) if mesh.shape[a] > 1)
    spec = P(data if data else None, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def describe_shardings(params: Any, mesh: Mesh, rules: Rules) -> dict[str, str]:
    """Human-readable {path: spec} map — surfaced by the health endpoint so
    operators can audit the layout without a debugger."""
    out: dict[str, str] = {}

    def one(path, leaf):
        p = path_str(path)
        out[p] = str(spec_for(p, rules, getattr(leaf, "ndim", 0)))
        return leaf

    jax.tree_util.tree_map_with_path(one, params)
    return out
