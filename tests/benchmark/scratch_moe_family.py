"""A second family for the seam test, as files and nothing else: the
program's own ``moe`` family (``models/moe.py``: the dense attention, routed
SwiGLU experts behind a capacity dispatch) held to the dropless reference
beside this file. ``capacity_factor`` is experts over experts per token, so
an expert's buffer holds every token of a call and none is dropped.

Only what ``check.py`` asks of a family is here (``families/llama.py`` has
the whole contract). The state a token leaves is K and V, as in the dense
family, so the teacher-forced pieces are the dense family's own: the engine
chooses the routed forward from the config's class.
"""

from __future__ import annotations

import numpy as np

from benchmark.families import llama as dense
from benchmark.families.llama import paged_pieces  # noqa: F401  (same pool, same pieces)

REFERENCE = "scratch_moe_reference"


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.moe import MoeConfig

    experts, chosen = int(model["num_local_experts"]), int(model["num_experts_per_tok"])
    return MoeConfig(**{**dense.program_config(model), "n_layers": layers, "max_len": max_len},
                     n_experts=experts, experts_per_token=chosen,
                     capacity_factor=experts / chosen)


def init_params(key, cfg) -> dict:
    from sentio_tpu.models.moe import init_moe

    return init_moe(key, cfg)


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
