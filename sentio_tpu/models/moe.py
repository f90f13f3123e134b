"""Mixture-of-Experts decoder: Llama geometry with routed SwiGLU experts.

The reference delegates all model compute to hosted APIs (SURVEY.md §0) and
has no model families of its own; this family exists so the framework's
generator seam can serve sparse models at the same per-token FLOP cost as a
much smaller dense model — the standard scale path on TPU pods.

Design (GShard/Switch-style, static shapes throughout — XLA-friendly):

* Each block keeps the Llama attention (reused from models/llama.py) and
  replaces the dense SwiGLU with ``n_experts`` SwiGLU experts plus a linear
  router. Top-``experts_per_token`` routing with renormalized gates.
* Dispatch/combine are one-hot einsums over a fixed per-expert capacity
  ``C = ceil(G·k/E · capacity_factor)`` — tokens over capacity are dropped
  (their residual stream passes through untouched), which keeps every shape
  static under jit.
* Expert parallelism is pure sharding: expert-indexed weights carry the
  ``ep`` mesh axis on their leading dim (MOE_EP_RULES in
  parallel/sharding.py), token activations stay on the data axes, and XLA
  lowers the dispatch/combine einsums to all_to_all-style collectives over
  ICI. No manual collectives here — mesh geometry is the comm layer.
* The router computes in float32 (softmax stability) and adds the Switch
  load-balance auxiliary loss so training keeps experts utilized.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from sentio_tpu.models import layers as L
from sentio_tpu.models.llama import Cache, LlamaConfig, _attn, init_cache  # noqa: F401

Array = jax.Array


@dataclass(frozen=True)
class MoeConfig(LlamaConfig):
    n_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01

    @classmethod
    def tiny(cls) -> "MoeConfig":
        """CPU-test scale, byte-level vocab."""
        return cls(
            vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            mlp_dim=128, max_len=512, rope_theta=10_000.0,
            n_experts=4, experts_per_token=2,
        )


def init_moe(rng: Array, cfg: MoeConfig) -> dict:
    keys = iter(jax.random.split(rng, 2 + cfg.n_layers * 8))
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    params: dict = {
        "embed_tokens": L.embed_init(next(keys), cfg.vocab_size, cfg.dim),
        "lm_head": L.dense_init(next(keys), cfg.dim, cfg.vocab_size, with_bias=False),
        "final_norm": L.rmsnorm_init(cfg.dim),
    }

    def expert_stack(key, in_dim, out_dim):
        ws = [
            L.dense_init(k, in_dim, out_dim, with_bias=False)["kernel"]
            for k in jax.random.split(key, cfg.n_experts)
        ]
        return jnp.stack(ws)  # [E, in, out]

    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = {
            "attn_norm": L.rmsnorm_init(cfg.dim),
            "attn": {
                "wq": L.dense_init(next(keys), cfg.dim, cfg.dim, with_bias=False),
                "wk": L.dense_init(next(keys), cfg.dim, kv_dim, with_bias=False),
                "wv": L.dense_init(next(keys), cfg.dim, kv_dim, with_bias=False),
                "wo": L.dense_init(next(keys), cfg.dim, cfg.dim, with_bias=False),
            },
            "mlp_norm": L.rmsnorm_init(cfg.dim),
            "moe": {
                "router": L.dense_init(next(keys), cfg.dim, cfg.n_experts, with_bias=False),
                "w_gate": expert_stack(next(keys), cfg.dim, cfg.mlp_dim),
                "w_up": expert_stack(next(keys), cfg.dim, cfg.mlp_dim),
                "w_down": expert_stack(next(keys), cfg.mlp_dim, cfg.dim),
            },
        }
    return params


def expert_capacity(cfg: MoeConfig, n_tokens: int) -> int:
    import math

    per_expert = n_tokens * cfg.experts_per_token / cfg.n_experts
    return max(1, math.ceil(per_expert * cfg.capacity_factor))


def route_topk(
    logits: Array, k: int, capacity: int, valid: Optional[Array] = None
) -> tuple[Array, Array, Array]:
    """GShard-style top-k dispatch with fixed capacity.

    logits [G, E] (float32) → (dispatch [G, E, C] bool, combine [G, E, C]
    float32, aux scalar). Tokens beyond an expert's capacity in choice-
    priority order are dropped (combine weight 0). Gates of the kept choices
    are renormalized over the *selected* experts. ``valid`` [G] bool masks
    padding tokens out entirely: they take no capacity slots and contribute
    nothing to the load-balance aux statistics.
    """
    g, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vmask = (
        jnp.ones((g,), jnp.float32) if valid is None else valid.astype(jnp.float32)
    )

    remaining = probs
    counts = jnp.zeros((e,), jnp.float32)
    dispatch = jnp.zeros((g, e, capacity), bool)
    combine = jnp.zeros((g, e, capacity), jnp.float32)
    gate_total = jnp.zeros((g,), jnp.float32)

    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                  # [G]
        gate = jnp.take_along_axis(probs, idx[:, None], 1)[:, 0]
        # padding tokens choose nothing: zeroed one-hots take no buffer
        # positions and advance no expert counts
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32) * vmask[:, None]
        # position of each token within its chosen expert's buffer
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot) + counts[None, :]
        pos = (pos_in_expert * onehot).sum(-1)                # [G]
        keep = (pos < capacity) & (vmask > 0)
        pos_oh = jax.nn.one_hot(
            jnp.clip(pos, 0, capacity - 1).astype(jnp.int32), capacity,
            dtype=jnp.float32,
        )                                                     # [G, C]
        slot = onehot[:, :, None] * pos_oh[:, None, :]        # [G, E, C]
        slot = slot * keep[:, None, None]
        dispatch = dispatch | (slot > 0)
        combine = combine + slot * gate[:, None, None]
        gate_total = gate_total + gate * keep
        counts = counts + onehot.sum(0)
        remaining = remaining * (1.0 - onehot)

    # renormalize kept gates so each token's expert mix sums to 1
    combine = combine / jnp.maximum(gate_total[:, None, None], 1e-9)

    # Switch aux loss over REAL tokens only: E * sum_e (fraction ASSIGNED to
    # e, pre-drop — capacity clipping must not cap the imbalance signal) *
    # (mean router prob of e)
    n_valid = jnp.maximum(vmask.sum(), 1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    mean_prob = (probs * vmask[:, None]).sum(0) / n_valid
    aux = (frac * mean_prob).sum() * e
    return dispatch, combine, aux


def moe_mlp(
    mp: dict, cfg: MoeConfig, x: Array, pad_mask: Optional[Array] = None
) -> tuple[Array, Array]:
    """Routed SwiGLU over x [B, T, D] → (out [B, T, D], aux loss scalar).
    ``pad_mask`` [B, T] keeps padding tokens from consuming expert capacity
    or skewing the load-balance statistics."""
    dt = cfg.jdtype
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    capacity = expert_capacity(cfg, b * t)

    logits = L.dense(mp["router"], flat, jnp.float32)          # [G, E] f32
    valid = None if pad_mask is None else pad_mask.reshape(b * t)
    dispatch, combine, aux = route_topk(
        logits, cfg.experts_per_token, capacity, valid
    )

    # dispatch tokens to per-expert buffers: [E, C, D]
    expert_in = jnp.einsum(
        "gec,gd->ecd", dispatch.astype(dt), flat.astype(dt)
    )
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, mp["w_gate"].astype(dt)))
    up = jnp.einsum("ecd,edf->ecf", expert_in, mp["w_up"].astype(dt))
    expert_out = jnp.einsum("ecf,efd->ecd", gate * up, mp["w_down"].astype(dt))

    out = jnp.einsum("gec,ecd->gd", combine.astype(dt), expert_out)
    return out.reshape(b, t, d), aux


def moe_forward(
    params: dict,
    cfg: MoeConfig,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
) -> tuple[Array, Optional[Cache], Array]:
    """ids [B, T] → (logits [B, T, vocab] f32, cache, total aux loss).

    Prefill/decode (cache + positions) semantics match models/llama.py
    ``llama_forward``, but the return adds a trailing router-aux scalar the
    training loss consumes — serving code that expects the two-tuple
    contract uses :func:`moe_serving_forward`, which drops it.
    """
    dt = cfg.jdtype
    b, t = ids.shape
    if cache is not None:
        cache = dict(cache)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    rope_len = cache["k"].shape[2] if cache is not None else max(t, cfg.max_len)
    cos, sin = L.rope_frequencies(cfg.head_dim, rope_len, cfg.rope_theta)

    x = L.embed(params["embed_tokens"], ids, dt)
    aux_total = jnp.zeros((), jnp.float32)
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        attn_out, cache = _attn(
            lp["attn"], cfg, L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps),
            positions, cos, sin, i, cache, cache_index, pad_mask, attn_fn,
        )
        x = x + attn_out
        moe_out, aux = moe_mlp(
            lp["moe"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps), pad_mask
        )
        x = x + moe_out
        aux_total = aux_total + aux
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.dense(params["lm_head"], x, dt)
    return logits.astype(jnp.float32), cache, aux_total


def moe_serving_forward(
    params: dict,
    cfg: MoeConfig,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
) -> tuple[Array, Optional[Cache]]:
    """Two-tuple adapter matching ``llama_forward``'s serving contract
    (runtime/paged.py unpacks ``logits, cache``); the
    router aux loss is a training-only signal and is dropped here."""
    logits, cache, _ = moe_forward(
        params, cfg, ids, positions, cache, cache_index, pad_mask, attn_fn
    )
    return logits, cache


def moe_loss(params: dict, cfg: MoeConfig, ids: Array, mask: Array) -> Array:
    """Next-token cross-entropy + router aux — the ep train-step objective."""
    logits, _, aux = moe_forward(params, cfg, ids[:, :-1], pad_mask=mask[:, :-1])
    targets = ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[:, :, None], axis=-1)[..., 0]
    weights = mask[:, 1:].astype(jnp.float32)
    ce = (nll * weights).sum() / jnp.maximum(weights.sum(), 1.0)
    return ce + cfg.router_aux_weight * aux
