"""The plain reference of the family ``deepseek_v2`` (DeepSeek-V2): one
sequence's forward pass in ``jax.numpy``, float32 arithmetic under
``jax.default_matmul_precision("highest")``, no cache, no kernel, no batching
and no import from the program. The block, as the model's ``config.json`` and
its ``modeling_deepseek.py`` give it (``x`` the residual; every norm an
RMSNorm with ``rms_norm_eps``; no bias anywhere):

    h = norm(x)
    c_q = norm(h W_dq)                       q_lora_rank (1536)
    q = c_q W_uq -> n_heads heads of [q_nope (128) | q_pe (64)]
    [c | k_pe] = h W_dkv                     kv_lora_rank (512) | qk_rope_head_dim (64); k_pe ONE vector a token
    c_kv = norm(c);  [k_nope | v] = c_kv W_ukv -> n_heads heads of [128 | 128]
    q_pe, k_pe rotated on 64 dims: the checkpoint's pairs are interleaved, (2j, 2j+1); they are
        DE-INTERLEAVED (evens, then odds) and rotated as halves (rotate_half), as the published code does.
        Frequencies are YaRN's: below dimension `low` 1/theta^(2i/64) as trained, above `high` the same
        over `factor`, a linear ramp between; low, high = floor, ceil of 64 ln(orig / (beta 2 pi)) / (2 ln theta)
        for beta_fast, beta_slow. cos and sin times yarn_mscale(factor, mscale) / yarn_mscale(factor,
        mscale_all_dim), yarn_mscale(f, m) = 0.1 m ln f + 1.
    scores = (q_nope . k_nope + q_pe . k_pe) * 192^-1/2 * yarn_mscale(factor, mscale_all_dim)^2
    causal softmax;  o = sum p v (n_heads x 128);  x <- x + o W_o
    h = norm(x)
    layer < first_k_dense_replace:  x <- x + (silu(h Wg) * h Wu) Wd                       width 12288
    later layers:  s = softmax(h W_g) over ALL n_routed_experts (160), float32
        a group's score = the MAX of its 20 experts; the best topk_group (3) of n_group (8) groups stay,
        the other groups' scores are zeroed (group_limited_greedy); the num_experts_per_tok (6) largest
        remaining are the picks; gate_e = s_e * routed_scaling_factor (16) — NOT renormalised
        (norm_topk_prob false);  x <- x + sum_picked gate_e F_e(h) + F_shared(h),  F = SwiGLU of width
        1536; the n_shared_experts (2) shared experts are ONE SwiGLU of width 3072, added with weight 1
        (here: two of width 1536 summed, which is the same function).
    logits = norm(x) W_head                                                                an untied head

Only the EXPANDED attention is here: keys and values are made for every head
(the program's decode never forms them: it absorbs W_uk and W_uv and reads the
latents). The auxiliary losses (``seq_aux``) are training's and no part of
a forward pass.

THE SHARE. ``experts_held`` / ``expert_offset`` say which experts' matrices
``params`` holds: the reference is given the same share of a layer as the
program (one chip of the eight that hold a group each). Routing is over all
experts and all groups; of the routed sum the picks whose expert is not held
add nothing, here as in the program. With every expert held it is the layer.

DEPARTURES, each forced by size and none changing a number:
* ``params`` keeps the checkpoint's bf16 VALUES and every matrix is widened
  to float32 where it is used, one at a time — the same numbers as a float32
  copy, the arithmetic in float32;
* ``kv_b_proj`` arrives as the program's tree holds it, split by head into
  ``w_uk`` and ``w_uv`` ``[n_heads, 128, 512]``: the same matrix, row for row;
* the queries go through attention a block at a time and the held experts one
  after another (every token through each, weighted by its gate, zero where
  not picked: nothing is dropped).

``forward(params, ids, forced=None, **kwargs) -> (logits [T, V], {"groups":
[routed layers, T, n_group], "experts": [routed layers, T, n_experts]})``:
TWO things are decided by rank. ``scores["groups"]`` are the group maxima.
``scores["experts"]`` are the softmax scores with ``-inf`` outside the groups
that were FOLLOWED: with ``forced = {"groups": [Lr, T, topk_group],
"experts": [Lr, T, k]}`` those groups and picks replace the reference's own
(gates stay ``s_e * 16``), so a near-tie between two groups is counted once,
as a group disagreement, and the experts are ranked inside the groups the
program chose.

``params``: ``embed [V, d]``, ``head [d, V]``, ``final_norm [d]``, ``layers``
— dicts with ``attn_norm``, ``mlp_norm [d]``, ``wq_a [d, q_lora]``, ``q_norm``,
``wq_b [q_lora, H*192]``, ``wkv_a [d, 576]``, ``kv_norm [512]``, ``w_uk``,
``w_uv [H, 128, 512]``, ``wo [H*128, d]`` and either ``w_gate``, ``w_up [d,
f]``, ``w_down [f, d]`` (dense) or ``router [d, E]``, the held stacks
``w_gate``, ``w_up [held, d, f]``, ``w_down [held, f, d]`` and the shared
stacks ``shared_gate``, ``shared_up [S, d, f]``, ``shared_down [S, f, d]``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def wide(a):
    return a.astype(F32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * wide(scale)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, factor, original_max_len, beta_fast, beta_slow):
    """[dim / 2] float32 (the docstring's ramp)."""
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    if factor <= 1:
        return extra

    def correction(turns):
        return dim * math.log(original_max_len / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(correction(beta_fast)), 0), min(math.ceil(correction(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rotate(x, positions, inv_freq, scale):
    """x [T, H, D] with interleaved pairs: de-interleaved, then rotate_half."""
    t, h, d = x.shape
    x = x.reshape(t, h, d // 2, 2).swapaxes(-1, -2).reshape(t, h, d)
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    halves = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * (jnp.cos(angle) * scale) + halves * (jnp.sin(angle) * scale)


def attention(q, k, v, scale):
    """q, k [T, H, Dk], v [T, H, Dv] → [T, H*Dv]; causal; a block of queries at a time."""
    t, h, _ = q.shape
    block = next(b for b in (128, 64, 32, 16, 8, 4, 2, 1) if t % b == 0)
    keys = jnp.arange(t)

    def one(args):
        qb, at = args                                        # [blk, H, Dk], [blk]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        scores = jnp.where((keys[None, :] <= at[:, None])[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v).reshape(block, -1)

    out = jax.lax.map(one, (q.reshape(t // block, block, h, -1), keys.reshape(t // block, block)))
    return out.reshape(t, -1)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ wide(w_gate)) * (x @ wide(w_up))) @ wide(w_down)


def experts(x, lp, forced, *, n_group, topk_group, experts_per_token, routed_scaling_factor,
            norm_topk_prob, experts_held, expert_offset):
    """→ (routed over the held picks + shared [T, d], group scores [T, n_group],
    expert scores [T, E] with -inf outside the followed groups)."""
    s = jax.nn.softmax(x @ wide(lp["router"]), axis=-1)                       # [T, E] float32
    t, e = s.shape
    group_scores = s.reshape(t, n_group, e // n_group).max(-1)
    groups = jax.lax.top_k(group_scores, topk_group)[1] if forced is None else forced["groups"]
    kept = jnp.repeat(jnp.sum(jax.nn.one_hot(groups, n_group, dtype=F32), axis=1) > 0, e // n_group, axis=1)
    picks = jax.lax.top_k(jnp.where(kept, s, 0.0), experts_per_token)[1] if forced is None else forced["experts"]
    gates = jnp.take_along_axis(s, picks, axis=-1)
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * routed_scaling_factor
    weight = jnp.sum(jax.nn.one_hot(picks, e, dtype=F32) * gates[..., None], axis=1)
    weight = weight[:, expert_offset: expert_offset + experts_held]           # the picks held here

    def add_expert(total, mats):
        w_gate, w_up, w_down, w = mats
        return total + w[:, None] * swiglu(x, w_gate, w_up, w_down), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                             (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    shared, _ = jax.lax.scan(lambda total, m: (total + swiglu(x, *m), None), jnp.zeros_like(x),
                             (lp["shared_gate"], lp["shared_up"], lp["shared_down"]))
    return routed + shared, group_scores, jnp.where(kept, s, -jnp.inf)


def forward(params: dict, ids, forced=None, *, n_heads: int, kv_lora_rank: int, qk_nope_head_dim: int,
            qk_rope_head_dim: int, v_head_dim: int, rope_theta: float, rope_factor: float,
            rope_original_max_len: int, rope_beta_fast: float, rope_beta_slow: float, rope_mscale: float,
            rope_mscale_all_dim: float, norm_eps: float, n_group: int, topk_group: int,
            experts_per_token: int, routed_scaling_factor: float, norm_topk_prob: bool,
            experts_held: int, expert_offset: int):
    """ids [T] int → (logits [T, V] float32, {"groups": [Lr, T, n_group], "experts": [Lr, T, E]})."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        positions = jnp.arange(t)
        inv_freq = yarn_inv_freq(qk_rope_head_dim, rope_theta, rope_factor, rope_original_max_len,
                                 rope_beta_fast, rope_beta_slow)
        cos_sin_scale = yarn_mscale(rope_factor, rope_mscale) / yarn_mscale(rope_factor, rope_mscale_all_dim)
        scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5 * yarn_mscale(rope_factor, rope_mscale_all_dim) ** 2
        x = wide(params["embed"][ids])
        group_scores, expert_scores, routed_layer = [], [], 0
        for lp in params["layers"]:
            h = rms_norm(x, lp["attn_norm"], norm_eps)
            c_q = rms_norm(h @ wide(lp["wq_a"]), lp["q_norm"], norm_eps)
            q = (c_q @ wide(lp["wq_b"])).reshape(t, n_heads, qk_nope_head_dim + qk_rope_head_dim)
            kv_a = h @ wide(lp["wkv_a"])
            c_kv = rms_norm(kv_a[:, :kv_lora_rank], lp["kv_norm"], norm_eps)
            k_nope = jnp.einsum("tc,hnc->thn", c_kv, wide(lp["w_uk"]))
            v = jnp.einsum("tc,hvc->thv", c_kv, wide(lp["w_uv"]))
            q_pe = rotate(q[..., qk_nope_head_dim:], positions, inv_freq, cos_sin_scale)
            k_pe = rotate(kv_a[:, None, kv_lora_rank:], positions, inv_freq, cos_sin_scale)
            q_full = jnp.concatenate([q[..., :qk_nope_head_dim], q_pe], axis=-1)
            k_full = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (t, n_heads, qk_rope_head_dim))], axis=-1)
            x = x + attention(q_full, k_full, v, scale) @ wide(lp["wo"])
            h = rms_norm(x, lp["mlp_norm"], norm_eps)
            if "router" not in lp:
                x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
                continue
            out, groups, scores = experts(
                h, lp, None if forced is None else {name: picks[routed_layer] for name, picks in forced.items()},
                n_group=n_group, topk_group=topk_group, experts_per_token=experts_per_token,
                routed_scaling_factor=routed_scaling_factor, norm_topk_prob=norm_topk_prob,
                experts_held=experts_held, expert_offset=expert_offset)
            x = x + out
            group_scores.append(groups)
            expert_scores.append(scores)
            routed_layer += 1
        logits = rms_norm(x, params["final_norm"], norm_eps) @ wide(params["head"])
        return logits, {"groups": jnp.stack(group_scores), "experts": jnp.stack(expert_scores)}
