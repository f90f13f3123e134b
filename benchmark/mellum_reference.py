"""The plain reference of the family ``mellum`` (Mellum2-12B-A2.5B-Instruct):
one sequence's forward pass in ``jax.numpy``, float32 arithmetic under
``jax.default_matmul_precision("highest")``, no cache, no pages, no kernel, no
batching and no import from the program. The model, as its ``config.json``
(``model_type`` ``mellum``) gives it (``x`` the residual of the hidden width;
every norm an RMSNorm with weight ``w``, ``rms_norm_eps``; no bias anywhere):

    x0 = E[ids]                                              E untied from the head
    layer i, kind_i = layer_types[i]:
      u = norm_attn(x);  q = u Wq as n_heads heads of head_dim (wider than the hidden size),
                         k = u Wk, v = u Wv as n_kv_heads heads
      q, k <- rotate_half rotary over ALL head_dim dimensions at the token's position p:
                 (x1, x2) -> (x1 c - x2 s, x2 c + x1 s),  c, s = A cos(p f_j), A sin(p f_j)
         "sliding_attention": f_j = theta^(-2j/D),  A = 1                       rope_type "default"
         "full_attention":    f_j = (1 - r_j) theta^(-2j/D) / factor + r_j theta^(-2j/D)   rope_type "yarn"
                              r_j = 1 - clip((j - low) / (high - low), 0, 1)
                              low  = floor(dim_of(beta_fast)), high = ceil(dim_of(beta_slow)), inside [0, D - 1]
                              dim_of(n) = D ln(original_max / (2 pi n)) / (2 ln theta)
                              A = attention_factor (cos and sin BOTH scaled: the scores carry A^2)
      a = softmax(q k^T / sqrt(D) over keys j <= p, and j > p - sliding_window in a sliding layer) v
      x <- x + concat(heads) Wo
      h = norm_ffn(x);  s = softmax(h Wr) over ALL num_experts, float32
      picks = the num_experts_per_tok largest s;  g = s[picks] / sum s[picks]   (norm_topk_prob)
      x <- x + sum_picked g_e (silu(h Wg_e) * h Wu_e) Wd_e                       no shared expert
    logits = norm(x) W_head

DEPARTURES FROM THE PUBLISHED DESCRIPTION (the configuration file's ``assumed``
lists the same): no RMSNorm on q or k (the config has no key for one); no MTP
head (the catalog's ``described_as`` names one, the config has no key for it:
next-token logits alone are served and compared); YaRN's ``truncate`` left at
its default (the ramp's ends rounded outwards, as above); no router bias and
no routed scaling factor (no key for either).

THE SHARE. ``experts_held`` / ``expert_offset`` say which experts' matrices
``params`` holds, as in the other routed references; the cell holds every
expert (64 from 0), and then this IS the layer.

DEPARTURES forced by size, none changing a number: ``params`` keeps the
checkpoint's bf16 VALUES and every matrix is widened to float32 where it is
used, one at a time; the queries go through attention a block at a time under
a literal mask by position (32 heads x T^2 float32 scores would be 2.4 GB at
the check's 4.3k tokens) and the held experts one after another (every token
through each, weighted by its gate, zero where not picked: nothing is dropped).

``forward(params, ids, forced=None, **kwargs) -> (logits [T, V], {"experts":
scores [layers, T, num_experts]})``: with ``forced["experts"] [layers, T, k]``
those picks replace the reference's own (gates renormalised over them), and the
scores are its own softmax scores on the trajectory it ran.

``params``: ``embed [V, d]``, ``head [d, V]``, ``final_norm [d]``, ``layers`` —
dicts with ``attn_norm``, ``mlp_norm [d]``, ``wq [d, H*D]``, ``wk``, ``wv [d,
Hkv*D]``, ``wo [H*D, d]``, ``router [d, E]`` and the held stacks ``w_gate``,
``w_up [held, d, f]``, ``w_down [held, f, d]``.
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


def frequencies(kind, *, head_dim, rope_theta, rope_factor, rope_original_max, rope_beta_fast,
                rope_beta_slow, rope_attention_factor):
    """→ (f [D/2], A) of a layer of ``kind``, by the table above."""
    j = jnp.arange(head_dim // 2, dtype=F32)
    plain = rope_theta ** (-2.0 * j / head_dim)
    if kind != "full_attention":
        return plain, 1.0

    def dim_of(turns):
        return head_dim * math.log(rope_original_max / (2 * math.pi * turns)) / (2 * math.log(rope_theta))

    low = max(math.floor(dim_of(rope_beta_fast)), 0)
    high = min(math.ceil(dim_of(rope_beta_slow)), head_dim - 1)
    r = 1.0 - jnp.clip((j - low) / (high - low if high != low else 0.001), 0.0, 1.0)
    return (1.0 - r) * plain / rope_factor + r * plain, rope_attention_factor


def rotary(x, positions, freq, amplitude):
    """x [T, H, D]: the half-split rotary on all D, cos and sin times ``amplitude``."""
    d = x.shape[-1]
    angle = positions.astype(F32)[:, None] * freq[None, :]
    cos, sin = (amplitude * fn(angle)[:, None, :] for fn in (jnp.cos, jnp.sin))
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window):
    """q [T, H, D], k and v [T, Hkv, D] → [T, H*D]; causal, inside ``window``
    keys where one is given; grouped queries; a block of queries at a time."""
    t, h, d = q.shape
    hkv = k.shape[1]
    block = next(b for b in (128, 64, 32, 16, 8, 4, 2, 1) if t % b == 0)
    keys = jnp.arange(t)

    def one(args):
        qb, at = args                                        # [blk, H, D], [blk]
        qg = qb.reshape(block, hkv, h // hkv, d)             # KV head g serves query heads g*rep..
        scores = jnp.einsum("qgrd,kgd->grqk", qg, k) / math.sqrt(d)
        seen = keys[None, :] <= at[:, None]
        if window is not None:
            seen &= keys[None, :] > at[:, None] - window
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v).reshape(block, h * d)

    out = jax.lax.map(one, (q.reshape(t // block, block, h, d), keys.reshape(t // block, block)))
    return out.reshape(t, h * d)


def experts(h, lp, forced, *, experts_per_token, norm_topk_prob, experts_held, expert_offset):
    """→ (the routed sum over the held picks [T, d], the softmax scores [T, E])."""
    s = jax.nn.softmax(h @ wide(lp["router"]), axis=-1)      # [T, E] float32
    picks = jax.lax.top_k(s, experts_per_token)[1] if forced is None else forced
    gates = jnp.take_along_axis(s, picks, axis=-1)
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(picks, s.shape[1], dtype=F32) * gates[..., None], axis=1)
    weight = weight[:, expert_offset: expert_offset + experts_held]           # the picks held here

    def add_expert(total, mats):
        w_gate, w_up, w_down, w = mats
        out = (jax.nn.silu(h @ wide(w_gate)) * (h @ wide(w_up))) @ wide(w_down)
        return total + w[:, None] * out, None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                             (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    return routed, s


def forward(params: dict, ids, forced=None, *, n_heads: int, n_kv_heads: int, head_dim: int,
            rope_theta: float, norm_eps: float, layer_types, sliding_window: int, rope_factor: float,
            rope_original_max: int, rope_beta_fast: float, rope_beta_slow: float,
            rope_attention_factor: float, experts_per_token: int, norm_topk_prob: bool,
            experts_held: int, expert_offset: int):
    """ids [T] int → (logits [T, V] float32, {"experts": scores [layers, T, E]})."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        positions = jnp.arange(t)
        rope = {kind: frequencies(
            kind, head_dim=head_dim, rope_theta=rope_theta, rope_factor=rope_factor,
            rope_original_max=rope_original_max, rope_beta_fast=rope_beta_fast, rope_beta_slow=rope_beta_slow,
            rope_attention_factor=rope_attention_factor) for kind in set(layer_types)}
        x = wide(params["embed"][ids])
        all_scores = []
        for i, lp in enumerate(params["layers"]):
            u = rms_norm(x, lp["attn_norm"], norm_eps)
            q = rotary((u @ wide(lp["wq"])).reshape(t, n_heads, head_dim), positions, *rope[layer_types[i]])
            k = rotary((u @ wide(lp["wk"])).reshape(t, n_kv_heads, head_dim), positions, *rope[layer_types[i]])
            v = (u @ wide(lp["wv"])).reshape(t, n_kv_heads, head_dim)
            window = sliding_window if layer_types[i] == "sliding_attention" else None
            x = x + attention(q, k, v, window) @ wide(lp["wo"])
            out, scores = experts(
                rms_norm(x, lp["mlp_norm"], norm_eps), lp, None if forced is None else forced["experts"][i],
                experts_per_token=experts_per_token, norm_topk_prob=norm_topk_prob,
                experts_held=experts_held, expert_offset=expert_offset)
            x = x + out
            all_scores.append(scores)
        logits = rms_norm(x, params["final_norm"], norm_eps) @ wide(params["head"])
        return logits, {"experts": jnp.stack(all_scores)}
