"""The plain reference: a dense decoder's forward pass in ``jax.numpy``.

Float32, ``jax.default_matmul_precision("highest")`` (on a TPU a float32
matmul otherwise runs in bf16 passes), no cache, no kernels, no batching
tricks, no import from the program. It follows the published description of
the Llama/Mistral/Yi block:

    h   = embed[ids]
    h  += wo . attention(rope(wq . rms(h)), rope(wk . rms(h)), wv . rms(h))
    h  += w_down . (silu(w_gate . rms(h)) * (w_up . rms(h)))
    out = head . rms(h)

RMSNorm with the configuration's eps; RoPE at the configuration's theta in
the half-split ("rotate_half") layout of the published checkpoints; grouped
query attention (each KV head serves ``n_heads / n_kv_heads`` query heads);
causal mask; untied head. No departure from the description is made.

``params`` is the flat dict ``families/<family>.reference_params`` builds:
``embed [V, d]``, ``head [d, V]``, ``final_norm [d]``, ``layers`` — a list of
dicts with ``attn_norm, wq, wk, wv, wo, mlp_norm, w_gate, w_up, w_down``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [T, H, D]; positions [T]. Half-split rotation."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [T, D/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def forward(params: dict, ids, *, n_heads: int, n_kv_heads: int, head_dim: int,
            rope_theta: float, norm_eps: float):
    """ids [T] int → logits [T, V] float32: one sequence, full causal
    attention over itself."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        positions = jnp.arange(t)
        causal = positions[None, :] <= positions[:, None]  # [Tq, Tk]
        rep = n_heads // n_kv_heads
        h = params["embed"][ids]
        for lp in params["layers"]:
            x = rms_norm(h, lp["attn_norm"], norm_eps)
            q = rope((x @ lp["wq"]).reshape(t, n_heads, head_dim), positions, rope_theta)
            k = rope((x @ lp["wk"]).reshape(t, n_kv_heads, head_dim), positions, rope_theta)
            v = (x @ lp["wv"]).reshape(t, n_kv_heads, head_dim)
            k = jnp.repeat(k, rep, axis=1)  # KV head j serves query heads j*rep..
            v = jnp.repeat(v, rep, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
            scores = jnp.where(causal[None], scores, -jnp.inf)
            attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
            h = h + attn.reshape(t, n_heads * head_dim) @ lp["wo"]
            x = rms_norm(h, lp["mlp_norm"], norm_eps)
            h = h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        return rms_norm(h, params["final_norm"], norm_eps) @ params["head"]
