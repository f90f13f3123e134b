"""Shared neural net primitives — pure-JAX functional style.

Every model in the framework (bi-encoder, cross-encoder, decoder LM) is an
explicit parameter pytree + pure apply functions. No module framework: param
paths are then stable and human-chosen, which is what the tensor-parallel
partition rules in :mod:`sentio_tpu.parallel.sharding` match on, and the KV
cache threads through calls as a plain pytree (jit/pjit-friendly, no mutable
state). Compute dtype is bfloat16 on TPU (MXU-native). A training tree keeps
float32 masters and the functions here cast at use; a SERVING tree holds each
leaf in the dtype it is used in, cast once where it is loaded (the decoders'
checkpoints; ``transformer.serving_dtypes`` for the encoders), and the
``astype`` of the dtype held emits nothing — a cast at use is a cast on every
call.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
PyTree = dict


def dense_init(rng: Array, in_dim: int, out_dim: int, with_bias: bool = True) -> PyTree:
    """Truncated-normal fan-in init, matching transformer practice."""
    std = 1.0 / np.sqrt(in_dim)
    kernel = jax.random.truncated_normal(rng, -2.0, 2.0, (in_dim, out_dim)) * std
    params = {"kernel": kernel.astype(jnp.float32)}
    if with_bias:
        params["bias"] = jnp.zeros((out_dim,), jnp.float32)
    return params


def dense(params: PyTree, x: Array, dtype: jnp.dtype = jnp.bfloat16) -> Array:
    y = x.astype(dtype) @ params["kernel"].astype(dtype)
    if "bias" in params:
        y = y + params["bias"].astype(dtype)
    return y


def dense_t(params: PyTree, x: Array, dtype: jnp.dtype = jnp.bfloat16) -> Array:
    """:func:`dense` over a kernel stored ``[out, in]`` (the layout a serving
    program reads it in: models/llama.py ``serving_layout``)."""
    y = jax.lax.dot_general(
        x.astype(dtype), params["kernel"].astype(dtype),
        (((x.ndim - 1,), (1,)), ((), ())))
    if "bias" in params:
        y = y + params["bias"].astype(dtype)
    return y


def embed_init(rng: Array, vocab: int, dim: int) -> PyTree:
    emb = jax.random.normal(rng, (vocab, dim)) * 0.02
    return {"embedding": emb.astype(jnp.float32)}


def embed(params: PyTree, ids: Array, dtype: jnp.dtype = jnp.bfloat16) -> Array:
    # gather, then cast: the same values, and a table held wider than ``dtype``
    # costs its rows — the compiler does not move a cast through a gather, so
    # cast-then-gather converted the WHOLE table on every call (PR 52)
    return params["embedding"][ids].astype(dtype)


def layernorm_init(dim: int) -> PyTree:
    return {"scale": jnp.ones((dim,), jnp.float32), "bias": jnp.zeros((dim,), jnp.float32)}


def layernorm(params: PyTree, x: Array, eps: float = 1e-6) -> Array:
    # norm math in fp32 for stability, output back in input dtype
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"] + params["bias"]
    return y.astype(x.dtype)


def rmsnorm_init(dim: int) -> PyTree:
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rmsnorm(params: PyTree, x: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * params["scale"]).astype(x.dtype)


def rope_frequencies(head_dim: int, max_len: int, theta: float = 10_000.0) -> tuple[Array, Array]:
    """Precomputed cos/sin tables [max_len, head_dim//2], float32."""
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(max_len)
    freqs = np.outer(t, inv_freq)
    return jnp.asarray(np.cos(freqs), jnp.float32), jnp.asarray(np.sin(freqs), jnp.float32)


def apply_rope(x: Array, positions: Array, cos: Array, sin: Array) -> Array:
    """Rotate q/k. x: [B, T, H, D]; positions: [B, T] absolute positions
    (explicit, so paged/continuation decode just passes offsets)."""
    c = cos[positions][:, :, None, :]  # [B, T, 1, D/2]
    s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def attention(
    q: Array,
    k: Array,
    v: Array,
    mask: Optional[Array],
    dtype: jnp.dtype = jnp.bfloat16,
) -> Array:
    """Plain batched MHA core: q [B,T,H,D], k/v [B,S,H,D], mask broadcastable
    to [B,H,T,S] (True = attend). Softmax in fp32. The Pallas flash kernel in
    :mod:`sentio_tpu.kernels` replaces this on TPU for long sequences; this
    XLA form is the universal fallback and fuses well for moderate T."""
    head_dim = q.shape[-1]
    scale = 1.0 / np.sqrt(head_dim)
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(dtype), k.astype(dtype))
    logits = logits.astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", weights.astype(dtype), v.astype(dtype))
    return out


def repeat_kv(x: Array, n_rep: int) -> Array:
    """GQA: expand kv heads to match query heads. [B,S,Hkv,D] -> [B,S,Hkv*n,D]."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def causal_mask(t: int, s: Optional[int] = None, offset: int = 0) -> Array:
    """[1, 1, T, S] boolean causal mask; offset shifts query positions (decode
    with cache: query i attends keys <= offset + i)."""
    s = s if s is not None else t
    qi = jnp.arange(t)[:, None] + offset
    kj = jnp.arange(s)[None, :]
    return (kj <= qi)[None, None, :, :]
