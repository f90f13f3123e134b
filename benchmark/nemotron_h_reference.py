"""The plain reference of the family ``nemotron_h``
(NVIDIA-Nemotron-3-Nano-30B-A3B): one sequence's forward pass in ``jax.numpy``,
float32 arithmetic under ``jax.default_matmul_precision("highest")``, no
cache, no carried state, no chunking, no kernel, no batching and no import
from the program. The model, as its ``config.json`` (``model_type``
``nemotron_h``) and its published modelling code give it (``x`` the residual;
every norm an RMSNorm with weight ``w`` and no unit offset, ``norm_eps``; no
bias on any matrix):

    every block ONE operator, by its letter of hybrid_override_pattern:   x <- x + Op(norm(x))

    "M", Mamba-2 (H heads of P; inner = H P, NOT expand x hidden; G groups; state N; conv K taps):
        [z | xBC | dt] = u W_in                      widths inner | inner + 2 G N | H, split in THAT order
        xBC_t <- silu(sum_{j<K} k[:, j] xBC_{t-K+1+j} + b)      depthwise, causal, xBC_t = 0 for t < 0
        x_t = xBC_t[:inner] as [H, P];  B_t, C_t = the next G N each as [G, N];  head j reads group j // (H / G)
        D_t = softplus(dt_t + dt_bias)               no clamp: time_step_min / max / floor are the INIT's
        A = -exp(A_log), a scalar a head
        S_t[j] = exp(D_t[j] A[j]) S_{t-1}[j] + D_t[j] x_t[j] (x) B_t[g(j)]        S_{-1} = 0, [P, N] a head
        y_t[j] = S_t[j] C_t[g(j)] + D[j] x_t[j]
        Op(u)_t = (GroupRMSNorm(y_t * silu(z_t)) ) W_out        the gate BEFORE the norm; G groups of
                                                                inner / G under one [inner] weight
      computed here TOKEN BY TOKEN (``lax.scan`` over t), the recurrence as written: it shares
      nothing with the program's chunked scan.
    "*", attention: q = u W_q (32 heads x 128), k = u W_k, v = u W_v (2 heads x 128); NO rotation, no
        bias; causal softmax(q k^T / sqrt(128)) v, 16 query heads a KV head; W_o
    "E", experts: s = sigmoid(u W_r) over ALL 128 experts, float32
        picks = the num_experts_per_tok (6) largest of s + b (e_score_correction_bias; the router's
        n_group and topk_group are 1: no group limit); the bias is NO part of a gate
        g = s[picks];  g <- g / (sum g + 1e-20) (norm_topk_prob);  g <- g x routed_scaling_factor (2.5)
        Op(u) = sum_picked g_e W_down^e relu(W_up^e u)^2  +  W_down^s relu(W_up^s u)^2
        every expert UNGATED (mlp_hidden_act relu2: two matrices), width 1856; ONE shared expert, 3712
    logits = norm(x) W_head                                   the head is NOT the embedding

ASSUMED (the configuration file lists the same): no rotation in the attention
blocks (``rope_theta`` is a key of ``config.json`` that the published
modelling code does not use); the ``1e-20`` under the gates' sum and the bias
being selection-only are the published modelling code as the builder knows
it, not keys of ``config.json``.

THE SHARE. ``experts_held`` / ``expert_offset`` say which experts' matrices
``params`` holds, as in the other routed references: the cell's chip holds
experts 0..63 of 128, a token's picks that lie elsewhere add nothing here, the
shared expert is on every chip. A test adds the two shares up, the shared
expert counted once. The vocabulary is whatever ``embed`` and ``head`` hold.

DEPARTURES, each forced by size and none changing a number:
* ``params`` keeps the checkpoint's bf16 VALUES and every matrix is widened
  to float32 where it is used, one at a time;
* the queries go through attention a block at a time and the held experts one
  after another (every token through each, weighted by its gate, zero where
  not picked: nothing is dropped).

``forward(params, ids, forced=None, **kwargs) -> (logits [T, V], {"experts":
[routed blocks, T, n_experts]})``: the scores are what the picks are RANKED
by, ``s + b``. With ``forced = {"experts": [Le, T, k]}`` those picks replace
the reference's own; the gates are then the unbiased ``s`` of the forced
picks, renormalised as published.

``params``: ``embed [V, d]``, ``head [d, V]``, ``final_norm [d]``, ``layers``
— a dict a block with ``norm [d]`` and, by its kind (read off its keys),
``w_in [d, 2 inner + 2 G N + H]``, ``conv_kernel [inner + 2 G N, K]``,
``conv_bias``, ``dt_bias [H]``, ``a_log [H]``, ``d [H]``, ``out_norm
[inner]``, ``w_out [inner, d]`` (Mamba); ``wq [d, H*D]``, ``wk``, ``wv [d,
Hkv*D]``, ``wo [H*D, d]`` (attention); ``router [d, E]``, ``bias [E]``, the
held stacks ``w_up [held, d, f]``, ``w_down [held, f, d]`` and
``shared_up [d, fs]``, ``shared_down [fs, d]`` (experts).
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


def mamba(u, lp, *, mamba_heads, mamba_head_dim, n_groups, ssm_state, norm_eps):
    """The Mamba-2 mixer over the whole sequence, u [T, d], the recurrence a token at a time."""
    t = u.shape[0]
    inner, gn = mamba_heads * mamba_head_dim, n_groups * ssm_state
    z, xbc, dt = jnp.split(u @ wide(lp["w_in"]), [inner, 2 * inner + 2 * gn], axis=-1)
    taps = wide(lp["conv_kernel"])                           # [inner + 2 G N, K]
    width = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1]), F32), xbc], axis=0)
    xbc = jax.nn.silu(sum(padded[j: j + t] * taps[:, j] for j in range(width)) + wide(lp["conv_bias"]))
    x = xbc[:, :inner].reshape(t, mamba_heads, mamba_head_dim)
    rep = mamba_heads // n_groups                            # head j reads group j // rep
    b_mat = jnp.repeat(xbc[:, inner: inner + gn].reshape(t, n_groups, ssm_state), rep, axis=1)
    c_mat = jnp.repeat(xbc[:, inner + gn:].reshape(t, n_groups, ssm_state), rep, axis=1)
    delta = jax.nn.softplus(dt + wide(lp["dt_bias"]))        # [T, H]
    a = -jnp.exp(wide(lp["a_log"]))

    def step(state, at):                                     # state [H, P, N]
        x_t, b_t, c_t, d_t = at
        state = jnp.exp(d_t * a)[:, None, None] * state + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((mamba_heads, mamba_head_dim, ssm_state), F32), (x, b_mat, c_mat, delta))
    y = (y + wide(lp["d"])[:, None] * x).reshape(t, inner)
    gated = (y * jax.nn.silu(z)).reshape(t, n_groups, -1)    # the gate BEFORE the norm
    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + norm_eps)
    return (normed.reshape(t, inner) * wide(lp["out_norm"])) @ wide(lp["w_out"])


def relu2_mlp(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ wide(w_up))) @ wide(w_down)


def experts(a, lp, forced, *, experts_per_token, norm_topk_prob, norm_topk_eps, routed_scaling_factor,
            experts_held, expert_offset):
    """→ (the routed sum over the held picks plus the shared expert [T, d],
    what the picks are ranked by [T, E])."""
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
        w_up, w_down, w = mats
        return total + w[:, None] * relu2_mlp(a, w_up, w_down), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(a), (lp["w_up"], lp["w_down"], weight.T))
    return routed + relu2_mlp(a, lp["shared_up"], lp["shared_down"]), ranked


def forward(params: dict, ids, forced=None, *, n_heads: int, n_kv_heads: int, norm_eps: float,
            mamba_heads: int, mamba_head_dim: int, n_groups: int, ssm_state: int,
            experts_per_token: int, norm_topk_prob: bool, norm_topk_eps: float,
            routed_scaling_factor: float, experts_held: int, expert_offset: int):
    """ids [T] int → (logits [T, V] float32, {"experts": [Le, T, E]}). A
    block's kind is read off its parameters (``w_in``: Mamba; ``router``:
    experts; else attention)."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        x = wide(params["embed"][ids])
        scores = []
        for lp in params["layers"]:
            u = rms_norm(x, lp["norm"], norm_eps)
            if "w_in" in lp:
                x = x + mamba(u, lp, mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
                              n_groups=n_groups, ssm_state=ssm_state, norm_eps=norm_eps)
            elif "router" in lp:
                out, ranked = experts(
                    u, lp, None if forced is None else forced["experts"][len(scores)],
                    experts_per_token=experts_per_token, norm_topk_prob=norm_topk_prob,
                    norm_topk_eps=norm_topk_eps, routed_scaling_factor=routed_scaling_factor,
                    experts_held=experts_held, expert_offset=expert_offset)
                x = x + out
                scores.append(ranked)
            else:
                q = (u @ wide(lp["wq"])).reshape(t, n_heads, -1)
                k = (u @ wide(lp["wk"])).reshape(t, n_kv_heads, -1)
                v = (u @ wide(lp["wv"])).reshape(t, n_kv_heads, -1)
                x = x + attention(q, k, v) @ wide(lp["wo"])
        logits = rms_norm(x, params["final_norm"], norm_eps) @ wide(params["head"])
        return logits, {"experts": jnp.stack(scores)}
