"""The plain reference of the family ``jamba`` (AI21-Jamba2-3B): one
sequence's forward pass in ``jax.numpy``, float32 arithmetic under
``jax.default_matmul_precision("highest")``, no cache, no pages, no carried
state, no blocks, no kernel, no batching and no import from the program. The
model, as its ``config.json`` (``model_type`` ``jamba``) and its published
modelling code give it (``h`` the residual; every norm an RMSNorm with weight
``w`` and no unit offset, ``rms_norm_eps``):

    every layer:   h <- h + Mixer_i(norm(h));   h <- h + W_down (silu(W_gate u) * W_up u),  u = norm(h)
                   (num_experts 1: the dense SwiGLU in EVERY layer; expert_layer_period / _offset select nothing)

    Mixer_i, i % attn_layer_period == attn_layer_offset: attention
        q = u W_q (20 heads x 128), k = u W_k, v = u W_v (ONE head x 128); no bias, NO rotation, no position
        embedding anywhere; causal softmax(q k^T / sqrt(128)) v, every query head on the one KV head; W_o

    Mixer_i otherwise: Mamba-1 (inner = mamba_expand x hidden; state N = mamba_d_state; R = mamba_dt_rank;
                                K = mamba_d_conv taps)
        [x | z]      = u W_in                        widths inner | inner, split in THAT order, no bias
        x_t          <- silu(sum_{j<K} k[:, j] x_{t-K+1+j} + b_conv)      depthwise, causal, x_t = 0 for t < 0
        [r | B | C]  = x W_x                         widths R | N | N, no bias
        r, B, C      <- norm_dt(r), norm_B(B), norm_C(C)                  three RMSNorms, a weight each, the model's eps
        D_t          = softplus(r_t W_dt + b_dt)     R -> inner, WITH bias
        A            = -exp(A_log)                   [inner, N]: a number per channel AND state column
        S_t[d, n]    = exp(D_t[d] A[d, n]) S_{t-1}[d, n] + D_t[d] x_t[d] B_t[n]        S_{-1} = 0
        y_t[d]       = sum_n S_t[d, n] C_t[n] + D[d] x_t[d]
        Mixer(u)_t   = (y_t * silu(z_t)) W_out       no norm between gate and projection
      computed here TOKEN BY TOKEN (``lax.scan`` over t, ``S`` as ``[inner, N]``), the recurrence as
      written: it shares nothing with the program's blocked scan or its transposed state.

    logits = norm(h) E^T                             tie_word_embeddings: the head IS the embedding

ASSUMED (the configuration file lists the same): which layers are attention —
the rule above is the published modelling code's as the builder knows it, the
catalog does not give the order of the layer types; ``num_experts`` 1 means a
dense MLP in every layer; no rotation and no position embedding; the three
inner RMSNorms, their one weight each and the model's eps; ``b_dt`` present
while ``W_dt``, ``W_x``, ``W_in`` and ``W_out`` have no bias
(``mamba_proj_bias`` false); the convolution has one (``mamba_conv_bias`` true).

DEPARTURES, each forced by size and none changing a number:
* ``params`` keeps the checkpoint's bf16 VALUES and every matrix is widened to
  float32 where it is used, one at a time;
* the queries go through attention a block at a time;
* ``a_log`` arrives ``[N, inner]`` (how the program's tree holds it) and is
  transposed to the published ``[inner, N]`` before anything reads it.

``forward(params, ids, **kwargs) -> logits [T, V]``.

``params``: ``embed [V, d]``, ``final_norm [d]``, ``layers`` — a dict a layer
with ``norm [d]``, ``mlp_norm [d]``, ``w_gate [d, f]``, ``w_up``, ``w_down
[f, d]`` and, by its mixer (read off its keys), ``w_in [d, 2 inner]``,
``conv_kernel [inner, K]``, ``conv_bias [inner]``, ``w_x [inner, R + 2 N]``,
``dt_norm [R]``, ``b_norm [N]``, ``c_norm [N]``, ``w_dt [R, inner]``,
``dt_bias [inner]``, ``a_log [N, inner]``, ``d [inner]``, ``w_out [inner,
d]`` (Mamba) or ``wq [d, H*D]``, ``wk``, ``wv [d, Hkv*D]``, ``wo [H*D, d]``
(attention). A test's CONTROLS: ``inner_norms=False`` drops the three
RMSNorms, ``dt_bias=False`` drops ``b_dt``, ``one_decay_column=True`` reads
column 0 of ``A`` for every state column.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def wide(a):
    return a.astype(F32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * wide(scale)


def attention(q, k, v):
    """q [T, H, D], k and v [T, Hkv, D] → [T, H*D]; causal; every ``H / Hkv``
    query heads on one KV head; a block of queries at a time."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    block = next(b for b in (128, 64, 32, 16, 8, 4, 2, 1) if t % b == 0)
    keys = jnp.arange(t)

    def one(args):
        qb, at = args                                        # [blk, H, D], [blk]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * d ** -0.5
        scores = jnp.where((keys[None, :] <= at[:, None])[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v).reshape(block, -1)

    out = jax.lax.map(one, (q.reshape(t // block, block, h, d), keys.reshape(t // block, block)))
    return out.reshape(t, -1)


def mamba(u, lp, *, d_state, dt_rank, norm_eps, inner_norms=True, dt_bias=True, one_decay_column=False):
    """The Mamba-1 mixer over the whole sequence, u [T, d], the recurrence a token at a time."""
    t = u.shape[0]
    x, z = jnp.split(u @ wide(lp["w_in"]), 2, axis=-1)
    taps = wide(lp["conv_kernel"])                           # [inner, K]
    width = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), F32), x], axis=0)
    x = jax.nn.silu(sum(padded[j: j + t] * taps[:, j] for j in range(width)) + wide(lp["conv_bias"]))
    r, b_mat, c_mat = jnp.split(x @ wide(lp["w_x"]), [dt_rank, dt_rank + d_state], axis=-1)
    if inner_norms:
        r, b_mat, c_mat = (rms_norm(v, lp[name], norm_eps)
                           for v, name in ((r, "dt_norm"), (b_mat, "b_norm"), (c_mat, "c_norm")))
    delta = r @ wide(lp["w_dt"])
    delta = jax.nn.softplus(delta + wide(lp["dt_bias"]) if dt_bias else delta)       # [T, inner]
    a = -jnp.exp(wide(lp["a_log"])).T                        # [inner, N], as published
    if one_decay_column:
        a = jnp.broadcast_to(a[:, :1], a.shape)

    def step(state, at):                                     # state [inner, N]
        x_t, b_t, c_t, d_t = at
        state = jnp.exp(d_t[:, None] * a) * state + (d_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((x.shape[1], d_state), F32), (x, b_mat, c_mat, delta))
    y = y + wide(lp["d"]) * x
    return (y * jax.nn.silu(z)) @ wide(lp["w_out"])


def forward(params: dict, ids, *, n_heads: int, n_kv_heads: int, norm_eps: float, d_state: int, dt_rank: int,
            inner_norms: bool = True, dt_bias: bool = True, one_decay_column: bool = False):
    """ids [T] int → logits [T, V] float32. A layer's mixer is read off its
    parameters (``w_in``: Mamba; else attention)."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        x = wide(params["embed"][ids])
        for lp in params["layers"]:
            u = rms_norm(x, lp["norm"], norm_eps)
            if "w_in" in lp:
                x = x + mamba(u, lp, d_state=d_state, dt_rank=dt_rank, norm_eps=norm_eps, inner_norms=inner_norms,
                              dt_bias=dt_bias, one_decay_column=one_decay_column)
            else:
                q = (u @ wide(lp["wq"])).reshape(t, n_heads, -1)
                k = (u @ wide(lp["wk"])).reshape(t, n_kv_heads, -1)
                v = (u @ wide(lp["wv"])).reshape(t, n_kv_heads, -1)
                x = x + attention(q, k, v) @ wide(lp["wo"])
            u = rms_norm(x, lp["mlp_norm"], norm_eps)
            x = x + (jax.nn.silu(u @ wide(lp["w_gate"])) * (u @ wide(lp["w_up"]))) @ wide(lp["w_down"])
        return rms_norm(x, params["final_norm"], norm_eps) @ wide(params["embed"]).T
