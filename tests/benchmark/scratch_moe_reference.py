"""The plain reference of the seam test's second family: a decoder whose
feed-forward is DROPLESS top-k routed experts, in ``jax.numpy``.

Float32, ``jax.default_matmul_precision("highest")``, no cache, no capacity,
no import from the program. Attention is the dense block's (RMSNorm, RoPE in
the half-split layout, GQA, causal). The feed-forward of a token ``x``:

    p     = softmax(router . rms(x))              over ALL experts
    top   = the experts_per_token largest of p
    gate  = p[top] / sum(p[top])                  renormalised over the chosen
    out   = sum_e gate_e * w_down_e . (silu(w_gate_e . x) * (w_up_e . x))

Every expert is computed for every token and weighted by its gate (zero for
the experts not chosen): nothing is dropped, whatever the batch.

The choice of experts is the one thing here decided by RANK, and a rank can
turn on the last bit of a score. ``forward(..., forced={"experts": [layers,
T, k] int})`` takes those experts at every layer and position in place of
its own ``top`` (gates renormalised over them, as above) and so computes the
function the program computed; without ``forced`` it is left to its own. It
returns ``(logits, {"experts": [layers, T, E]})``: beside the logits its
own router scores (``router . rms(x)``, before the softmax, which keeps
their order) on the trajectory it ran, so that the check can rank them
itself and see by how much each differing pick of the program was a tie.

``params``: ``embed [V, d]``, ``head [d, V]``, ``final_norm [d]``, ``layers`` —
dicts with ``attn_norm, wq, wk, wv, wo, mlp_norm, router [d, E]`` and the
stacks ``w_gate, w_up [E, d, f]``, ``w_down [E, f, d]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import rms_norm, rope


def routed_experts(x, lp, experts_per_token: int, forced=None):
    """→ (out [T, d], router scores [T, E]); ``forced [T, k]`` replaces ``top``."""
    scores = x @ lp["router"]
    probs = jax.nn.softmax(scores, axis=-1)                               # [T, E]
    top_e = jax.lax.top_k(probs, experts_per_token)[1] if forced is None else forced
    top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(top_e, probs.shape[-1]) * gates[..., None], axis=1)  # [T, E]
    hidden = jax.nn.silu(jnp.einsum("td,edf->etf", x, lp["w_gate"])) \
        * jnp.einsum("td,edf->etf", x, lp["w_up"])
    return jnp.einsum("te,etd->td", weight, jnp.einsum("etf,efd->etd", hidden, lp["w_down"])), scores


def forward(params: dict, ids, *, n_heads: int, n_kv_heads: int, head_dim: int,
            rope_theta: float, norm_eps: float, experts_per_token: int, forced=None):
    """ids [T] int → (logits [T, V] float32, {"experts": scores [layers, T, E]})."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[0]
        positions = jnp.arange(t)
        causal = positions[None, :] <= positions[:, None]
        rep = n_heads // n_kv_heads
        h = params["embed"][ids]
        routers = []
        for i, lp in enumerate(params["layers"]):
            x = rms_norm(h, lp["attn_norm"], norm_eps)
            q = rope((x @ lp["wq"]).reshape(t, n_heads, head_dim), positions, rope_theta)
            k = rope((x @ lp["wk"]).reshape(t, n_kv_heads, head_dim), positions, rope_theta)
            v = (x @ lp["wv"]).reshape(t, n_kv_heads, head_dim)
            k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
            scores = jnp.where(causal[None], scores, -jnp.inf)
            attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
            h = h + attn.reshape(t, n_heads * head_dim) @ lp["wo"]
            out, router = routed_experts(rms_norm(h, lp["mlp_norm"], norm_eps), lp, experts_per_token,
                                         None if forced is None else forced["experts"][i])
            h = h + out
            routers.append(router)
        return rms_norm(h, params["final_norm"], norm_eps) @ params["head"], {"experts": jnp.stack(routers)}
