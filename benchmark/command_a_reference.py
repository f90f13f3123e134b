"""The plain reference of the family ``cohere2_moe`` (Command A+): one
sequence's forward pass in ``jax.numpy``, float32 arithmetic under
``jax.default_matmul_precision("highest")``, no cache, no kernel, no batching
and no import from the program. The layer, as the configuration's
``config.json`` and its description give it (``h``, ``x`` of the hidden
width; every matrix without bias):

    LN(x)    = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * g       mean-centred, ONE a block
    h        = LN(x);   x <- x + Attn_i(h) + Experts(h)                 use_parallel_block
    logits   = logit_scale * LN(x) E^T                                  E the embedding (tied)

    Attn_i:  q = h Wq as n_heads heads of head_dim (wider than the hidden size), k, v = h Wk, h Wv
             as n_kv_heads heads, scores scaled by head_dim^-1/2, o = concat(heads) Wo.
             layer_types[i] "sliding_attention": q, k rotated by the INTERLEAVED rotary (rope_gptj:
             pairs (2j, 2j+1), angle pos * theta^(-2j/head_dim)) over the whole head; a query at p
             sees keys p - sliding_window < j <= p.  "full_attention": NO rotation, plain causal.
    Experts: s = sigmoid(h Wr) over ALL num_experts;  the num_experts_per_tok largest s are picked;
             w_e = s_e / sum_picked s  (norm_topk_prob);  routed = sum_picked w_e F_e(h),
             F(h) = (silu(h Wg) * h Wu) Wd;  shared = mean_j F^shared_j(h);  Experts = routed + shared.

ASSUMED (the configuration file lists the same three):
1. ``shared_expert_combination_strategy: "average"``: the shared experts'
   outputs are averaged and that average is ADDED to the routed sum.
2. The config names no selection bias and no routed scaling factor: there is
   none.
3. ``intermediate_size`` is the width of one routed and of one shared expert.

THE SHARE. ``experts_held`` / ``expert_offset`` say which experts' matrices
``params`` holds: the reference is given the same share of a layer as the
program (one chip of a deployment that splits the routed experts). Routing is
over all ``n_experts``; of the routed sum the picks whose expert is not held
add nothing, here as in the program, and that partial sum is what goes on.
With every expert held it is the whole layer.

DEPARTURES, each forced by size and none changing a number:
* ``params`` keeps the checkpoint's bf16 VALUES (two layers of the benchmark's
  cut are 2.3 B parameters: 9.2 GB in float32 beside the program's 4.6 GB)
  and every matrix is widened to float32 where it is used, one at a time —
  the same numbers as a float32 copy, the arithmetic in float32;
* the queries go through attention a block at a time and the held experts one
  after another (every token through each, weighted by its gate, zero where
  not picked: nothing is dropped), so that no tensor of all heads x all
  queries x all keys, or all experts x all tokens, exists at once.

``forward(params, ids, forced=None, **kwargs) -> (logits [T, V], {"experts":
scores [layers, T, n_experts]})``: with ``forced["experts"] [layers, T, k]``
those picks replace the reference's own (gates renormalised over them), and
the scores are its own sigmoid scores on the trajectory it ran.

``params``: ``embed [V, d]``, ``final_norm [d]``, ``layers`` — dicts with
``norm [d]``, ``wq [d, H*D]``, ``wk``, ``wv [d, Hkv*D]``, ``wo [H*D, d]``,
``router [d, E]``, the held stacks ``w_gate``, ``w_up [held, d, f]``,
``w_down [held, f, d]`` and the shared stacks ``shared_gate``, ``shared_up
[S, d, f]``, ``shared_down [S, f, d]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def wide(a):
    return a.astype(F32)


def layer_norm(x, scale, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * wide(scale)


def rope_interleaved(x, positions, theta):
    """x [T, H, D]: the pair (2j, 2j+1) rotated by positions * theta^(-2j/D)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def attention(q, k, v, window):
    """q [T, H, D], k, v [T, Hkv, D] → [T, H*D]; a block of queries at a time."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    block = next(b for b in (128, 64, 32, 16, 8, 4, 2, 1) if t % b == 0)
    keys = jnp.arange(t)

    def one(args):
        qb, at = args                                        # [blk, H, D], [blk]
        qg = qb.reshape(block, k.shape[1], rep, d)           # KV head g serves query heads g*rep..
        scores = jnp.einsum("qgrd,kgd->grqk", qg, k) / jnp.sqrt(F32(d))
        seen = keys[None, :] <= at[:, None]
        if window is not None:
            seen &= keys[None, :] > at[:, None] - window
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v).reshape(block, h * d)

    out = jax.lax.map(one, (q.reshape(t // block, block, h, d), keys.reshape(t // block, block)))
    return out.reshape(t, h * d)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ wide(w_gate)) * (x @ wide(w_up))) @ wide(w_down)


def experts(x, lp, experts_per_token, experts_held, expert_offset, forced):
    """→ (routed over the held picks + averaged shared [T, d], scores [T, E])."""
    scores = jax.nn.sigmoid(x @ wide(lp["router"]))                          # [T, E]
    picks = jax.lax.top_k(scores, experts_per_token)[1] if forced is None else forced
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(picks, scores.shape[-1], dtype=F32) * gates[..., None], axis=1)
    weight = weight[:, expert_offset: expert_offset + experts_held]          # the picks held here

    def add_expert(total, mats):
        w_gate, w_up, w_down, w = mats
        return total + w[:, None] * swiglu(x, w_gate, w_up, w_down), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                             (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    shared, _ = jax.lax.scan(lambda total, m: (total + swiglu(x, *m), None), jnp.zeros_like(x),
                             (lp["shared_gate"], lp["shared_up"], lp["shared_down"]))
    return routed + shared / lp["shared_gate"].shape[0], scores


def forward(params: dict, ids, forced=None, *, n_heads: int, n_kv_heads: int, head_dim: int,
            rope_theta: float, norm_eps: float, layer_types, sliding_window: int,
            experts_per_token: int, experts_held: int, expert_offset: int, logit_scale: float):
    """ids [T] int → (logits [T, V] float32, {"experts": scores [layers, T, E]})."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        positions = jnp.arange(t)
        x = wide(params["embed"][ids])
        all_scores = []
        for i, lp in enumerate(params["layers"]):
            h = layer_norm(x, lp["norm"], norm_eps)
            q = (h @ wide(lp["wq"])).reshape(t, n_heads, head_dim)
            k = (h @ wide(lp["wk"])).reshape(t, n_kv_heads, head_dim)
            v = (h @ wide(lp["wv"])).reshape(t, n_kv_heads, head_dim)
            sliding = layer_types[i] == "sliding_attention"
            if sliding:
                q, k = rope_interleaved(q, positions, rope_theta), rope_interleaved(k, positions, rope_theta)
            attn = attention(q, k, v, sliding_window if sliding else None) @ wide(lp["wo"])
            out, scores = experts(h, lp, experts_per_token, experts_held, expert_offset,
                                  None if forced is None else forced["experts"][i])
            x = x + attn + out
            all_scores.append(scores)
        x = layer_norm(x, params["final_norm"], norm_eps)
        # the head is the embedding, a block of rows at a time is not needed: [T, d] x [d, V]
        return logit_scale * (x @ wide(params["embed"]).T), {"experts": jnp.stack(all_scores)}
