"""The plain reference of the family ``lfm2_moe`` (LFM2-24B-A2B): one
sequence's forward pass in ``jax.numpy``, float32 arithmetic under
``jax.default_matmul_precision("highest")``, no cache, no carried state, no
kernel, no batching and no import from the program. The block, as the model's
``config.json`` (``model_type`` ``lfm2_moe``) and its published modelling code
give it (``x`` the residual; every norm an RMSNorm with weight ``w`` and no
unit offset, ``norm_eps``; no bias on any matrix):

    u = norm_op(x);   h = x + Op_l(u);   a = norm_ffn(h);   x <- h + FF_l(a)

    Op, layer_types[l] == "conv" (the gated short convolution):
        [B_t | C_t | X_t] = u_t W_in          hidden -> 3 x hidden, split in THAT order
        z_t = B_t * X_t
        c_t = k[:, 0] z_{t-2} + k[:, 1] z_{t-1} + k[:, 2] z_t     depthwise, causal, z_t = 0 for t < 0
        Op(u)_t = (C_t * c_t) W_out
      computed here as three shifted products over the whole sequence: there is
      no state to carry.
    Op, "full_attention":
        q = u W_q (32 heads x 64);  k = u W_k, v = u W_v (8 heads x 64)
        each head of q and of k under an RMSNorm over its 64 (own weights, norm_eps), BEFORE rotary
        rotary on all 64 in the half-split form (rotate_half), theta, no scaling
        causal softmax(q k^T / sqrt(64)) v, 4 query heads a KV head;  W_o
    FF, l < num_dense_layers:  (silu(a W_1) * a W_3) W_2                               width 11776
    FF, later layers:  s = sigmoid(a W_r) over ALL 64 experts, float32
        picks = the num_experts_per_tok (4) largest of s + b, b the expert bias (use_expert_bias):
        the bias decides the picks and is NO part of a gate
        g = s[picks];  g <- g / (sum g + 1e-6) (norm_topk_prob);  g <- g x routed_scaling_factor (1)
        FF(a) = sum_picked g_e (silu(a W_1^e) * a W_3^e) W_2^e                         width 1536, no shared expert
    logits = norm(x) E^T                                                               the head IS the embedding

ASSUMED (the configuration file lists the same): a tied table (the catalog's
row lacks the key; the family's published configs tie it); the ``1e-6`` under
the gates' sum and the bias being selection-only are the published modelling
code as the builder knows it, not keys of ``config.json``.

THE SHARE. ``experts_held`` / ``expert_offset`` say which experts' matrices
``params`` holds, as in the other routed references; the cell holds every
expert (64 from 0), and then this IS the layer. A test cuts the layer in two
shares and adds them up.

DEPARTURES, each forced by size and none changing a number:
* ``params`` keeps the checkpoint's bf16 VALUES and every matrix is widened
  to float32 where it is used, one at a time;
* the queries go through attention a block at a time and the held experts one
  after another (every token through each, weighted by its gate, zero where
  not picked: nothing is dropped).

``forward(params, ids, forced=None, **kwargs) -> (logits [T, V], {"experts":
[routed layers, T, n_experts]})``: the scores are what the picks are RANKED
by, ``s + b``. With ``forced = {"experts": [Lr, T, k]}`` those picks replace
the reference's own; the gates are then the unbiased ``s`` of the forced
picks, renormalised as published.

``params``: ``embed [V, d]``, ``final_norm [d]``, ``layers`` — dicts with
``op_norm``, ``ffn_norm [d]``, then either ``w_in [d, 3d]``, ``kernel [d,
3]``, ``w_out [d, d]`` (conv) or ``wq [d, H*D]``, ``wk``, ``wv [d, Hkv*D]``,
``wo [H*D, d]``, ``q_norm``, ``k_norm [D]`` (attention), and either
``w_gate``, ``w_up [d, f]``, ``w_down [f, d]`` (dense) or ``router [d, E]``,
``bias [E]`` and the held stacks ``w_gate``, ``w_up [held, d, f]``,
``w_down [held, f, d]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def wide(a):
    return a.astype(F32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * wide(scale)


def rotate_half(x, positions, theta):
    """x [T, H, D]: the half-split rotary on all D."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    halves = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(angle) + halves * jnp.sin(angle)


def attention(q, k, v):
    """q [T, H, D], k and v [T, Hkv, D] → [T, H*D]; causal; grouped queries;
    a block of queries at a time."""
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


def short_conv(u, lp):
    """The gated short convolution over the whole sequence, u [T, d]."""
    gate_b, gate_c, x = jnp.split(u @ wide(lp["w_in"]), 3, axis=-1)
    z = gate_b * x
    taps = wide(lp["kernel"])                                # [d, 3]
    width = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, z.shape[1]), F32), z], axis=0)
    c = sum(padded[j: j + z.shape[0]] * taps[:, j] for j in range(width))
    return (gate_c * c) @ wide(lp["w_out"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ wide(w_gate)) * (x @ wide(w_up))) @ wide(w_down)


def experts(a, lp, forced, *, experts_per_token, norm_topk_prob, norm_topk_eps, routed_scaling_factor,
            experts_held, expert_offset):
    """→ (the routed sum over the held picks [T, d], what the picks are ranked by [T, E])."""
    s = jax.nn.sigmoid(a @ wide(lp["router"]))               # [T, E] float32
    ranked = s + wide(lp["bias"])
    picks = jax.lax.top_k(ranked, experts_per_token)[1] if forced is None else forced
    gates = jnp.take_along_axis(s, picks, axis=-1)           # the bias is no part of a gate
    if norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + norm_topk_eps)
    gates = gates * routed_scaling_factor
    weight = jnp.sum(jax.nn.one_hot(picks, s.shape[1], dtype=F32) * gates[..., None], axis=1)
    weight = weight[:, expert_offset: expert_offset + experts_held]           # the picks held here

    def add_expert(total, mats):
        w_gate, w_up, w_down, w = mats
        return total + w[:, None] * swiglu(a, w_gate, w_up, w_down), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(a),
                             (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    return routed, ranked


def forward(params: dict, ids, forced=None, *, n_heads: int, n_kv_heads: int, rope_theta: float,
            norm_eps: float, experts_per_token: int, norm_topk_prob: bool, norm_topk_eps: float,
            routed_scaling_factor: float, experts_held: int, expert_offset: int):
    """ids [T] int → (logits [T, V] float32, {"experts": [Lr, T, E]}). A
    layer's kind is read off its parameters (``w_in``: a convolution;
    ``router``: routed)."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        positions = jnp.arange(t)
        x = wide(params["embed"][ids])
        scores, routed_layer = [], 0
        for lp in params["layers"]:
            u = rms_norm(x, lp["op_norm"], norm_eps)
            if "w_in" in lp:
                x = x + short_conv(u, lp)
            else:
                q = (u @ wide(lp["wq"])).reshape(t, n_heads, -1)
                k = (u @ wide(lp["wk"])).reshape(t, n_kv_heads, -1)
                v = (u @ wide(lp["wv"])).reshape(t, n_kv_heads, -1)
                q = rotate_half(rms_norm(q, lp["q_norm"], norm_eps), positions, rope_theta)
                k = rotate_half(rms_norm(k, lp["k_norm"], norm_eps), positions, rope_theta)
                x = x + attention(q, k, v) @ wide(lp["wo"])
            a = rms_norm(x, lp["ffn_norm"], norm_eps)
            if "router" not in lp:
                x = x + swiglu(a, lp["w_gate"], lp["w_up"], lp["w_down"])
                continue
            out, ranked = experts(
                a, lp, None if forced is None else forced["experts"][routed_layer],
                experts_per_token=experts_per_token, norm_topk_prob=norm_topk_prob,
                norm_topk_eps=norm_topk_eps, routed_scaling_factor=routed_scaling_factor,
                experts_held=experts_held, expert_offset=expert_offset)
            x = x + out
            scores.append(ranked)
            routed_layer += 1
        logits = rms_norm(x, params["final_norm"], norm_eps) @ wide(params["embed"]).T
        return logits, {"experts": jnp.stack(scores)}
