"""Decoder family ``lfm2_moe`` (LFM2-24B-A2B): sequential pre-norm blocks whose
token mixer is, by the layer's kind, a GATED SHORT CONVOLUTION or attention
(three convolutions to one attention, as published), a dense SwiGLU in the
leading layers and routed experts picked under a SELECTION-ONLY bias in every
later one, a head tied to the embedding.

    h = x + Op_l(norm(x));   x' = h + FF_l(norm(h))          plain RMSNorm, no unit offset

    Op = conv:       [B_t, C_t, X_t] = W_in u_t              2048 -> 3 x 2048, no bias
                     z_t = B_t * X_t
                     c_t = sum_{j<3} k[:, j] * z_{t-2+j}     depthwise, causal, z_t = 0 for t < 0
                     Op(u)_t = W_out (C_t * c_t)
    Op = attention:  q, k, v = W_q u, W_k u, W_v u            GQA 32:8, head width 64
                     RMSNorm over each head of q and of k (own weights) BEFORE rotary
                     rotary on all 64 (rotate-half), theta 1e6;  softmax(q k^T / 8) v;  W_o
    FF dense  (l < num_dense_layers):  W_2(silu(W_1 a) * W_3 a)
    FF routed: s = sigmoid(W_r a) in float32;  picks = top4(s + b);  g = s[picks]
               g <- g / (sum g + 1e-6);  sum_e g_e Expert_e(a)                  no shared expert

WHAT A TOKEN LEAVES BEHIND differs by the layer's kind. An attention layer
leaves a key and a value in the page pool, as every family does — but only
the attention layers have pages, so the POOL's layer axis counts attention
layers (``attn_index``), not the model's. A convolution layer leaves nothing
per token: its state is ``z`` at the last ``conv_l_cache - 1 = 2`` positions,
2 x 2048 numbers a layer and sequence whatever the length.
``runtime/paged.py`` keeps that state per decode SLOT beside the pool, and —
so that the radix cache can serve a prefix of whole pages — per PAGE: every
full page holds, a convolution layer, ``z`` at its last two positions, and a
sequence that starts behind ``n`` cached pages starts from page ``n``'s tail.

ONE function serves prefill and decode. :func:`conv_segment` runs a segment
``[B, T, d]`` from a carried state (zeros at position 0, a page's tail behind
a radix hit or an earlier chunk) and hands back each row's state AT ITS OWN
LENGTH (rows are right-padded) and the tails of the pages the segment covers;
a decode step is a segment of one token over the slot's state. ``z`` is
rounded to the model's dtype ONCE, where it is made, and the three taps are
summed in float32 over those rounded values in one order, so a prompt
prefilled whole, in chunks, or behind a restored tail reads the same numbers.

The expert layer is ``models/moe.py::expert_layer`` with every expert held
(``experts_held == n_experts``): ``mp["bias"]`` decides the picks and is no
part of a gate, and the normaliser carries ``norm_topk_eps``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp

from sentio_tpu.models import layers as L
from sentio_tpu.models.families import DecodeStep, Family, StateBeside
from sentio_tpu.models.llama import Cache, LlamaConfig, _write_cache, qkv_proj
from sentio_tpu.models.moe import expert_layer, expert_tiles

Array = jax.Array

CONV, FULL = "conv", "full_attention"

# Seeded weights (tests, the fake-model mode, the benchmark's checkpoints). The
# head is TIED, so ``models/cohere2_moe.py``'s two findings carry over with
# their sizes: the embedding is drawn a quarter as large (a token's own row
# must not out-vote 65k others) and the query projection four times as large
# (attention peaked on a few keys, not the context's average); the mixers'
# output projections ``WO_SCALE`` as there. The convolution's taps are drawn
# over their fan-in of three, so ``c`` has ``z``'s size. THE EXPERT BIAS is
# drawn non-zero, or nothing would tell a biased pick from an unbiased one:
# the top four of 64 sigmoid scores lie in the flat of the curve (0.82 to 0.9
# for logits of unit size), 0.02 apart, and a normal of THAT spread changes
# the pick set of about 47 % of tokens and leaves 16 rows touching 40 of 64
# experts where no bias gives 41.2; one of the spread of ALL scores (0.2)
# would send every token to the same few experts (21 of 64 touched) — a
# trained bias evens the load, a random one of that size undoes it.
EMBED_STD = 0.005
WQ_SCALE = 4.0
WO_SCALE = 0.3
EXPERT_BIAS_STD = 0.02


@dataclass(frozen=True)
class Lfm2MoeConfig(LlamaConfig):
    """``layer_types``: one kind a layer, ``conv`` or ``full_attention``,
    given as a list (or a tuple) and KEPT comma-joined — a string reads the
    same in a checkpoint's JSON meta, in ``/info`` and here; ``kinds`` is the
    tuple. No period is assumed. ``num_dense_layers``: the leading layers whose
    feed-forward is a dense SwiGLU of ``mlp_dim``; ``moe_mlp_dim``: ONE routed
    expert; ``max_len`` the positions the model declares (nothing is sized by
    it)."""

    vocab_size: int = 65_536
    dim: int = 2048
    n_layers: int = 10
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 11_776
    max_len: int = 128_000
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    layer_types: str = ",".join((CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV))
    num_dense_layers: int = 2
    conv_l_cache: int = 3
    conv_bias: bool = False
    moe_mlp_dim: int = 1536
    n_experts: int = 64
    experts_per_token: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_topk_eps: float = 1e-6         # the published code's ``+ 1e-6`` under the gates' sum
    gate_fn: str = "sigmoid"
    tie_embeddings: bool = True
    experts_held: int = 64              # every expert: a chip holds whole layers of this model
    expert_offset: int = 0
    # how ``models/moe.py::expert_layer`` picks, as this family always does:
    # over all experts at once, no shared expert
    n_group: ClassVar[int] = 1
    topk_group: ClassVar[int] = 1

    def __post_init__(self):
        if not isinstance(self.layer_types, str):
            object.__setattr__(self, "layer_types", ",".join(self.layer_types))
        stated = (self.conv_l_cache, self.conv_bias, self.gate_fn, self.tie_embeddings)
        if stated != (3, False, "sigmoid", True):
            raise ValueError(f"conv_l_cache, conv_bias, gate_fn, tie_embeddings = {stated}: this family is "
                             "a three-tap convolution without bias, sigmoid scores, a tied head")
        if len(self.kinds) != self.n_layers:
            raise ValueError(f"{len(self.kinds)} layer types for {self.n_layers} layers")
        if not set(self.kinds) <= {CONV, FULL}:
            raise ValueError(f"layer types must be {CONV!r} or {FULL!r}: {self.layer_types}")
        if not 0 <= self.expert_offset <= self.n_experts - self.experts_held:
            raise ValueError(f"experts {self.expert_offset}..+{self.experts_held} of {self.n_experts}")

    @property
    def kinds(self) -> list[str]:
        """``layer_types`` as the list it was given as."""
        return self.layer_types.split(",")

    @property
    def attn_layers(self) -> tuple[int, ...]:
        """The model layers that are attention — the layers the page pool has."""
        return tuple(i for i, kind in enumerate(self.kinds) if kind == FULL)

    @property
    def conv_layers(self) -> tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.kinds) if kind == CONV)

    def attn_index(self, layer: int) -> int:
        """Model layer → its layer of the page pool."""
        return self.attn_layers.index(layer)

    def conv_index(self, layer: int) -> int:
        """Model layer → its layer of the convolution state."""
        return self.conv_layers.index(layer)

    @property
    def conv_taps(self) -> int:
        """Positions of ``z`` a convolution layer carries: ``conv_l_cache - 1``."""
        return self.conv_l_cache - 1

    def routed_layer(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    @property
    def n_routed_layers(self) -> int:
        return max(self.n_layers - self.num_dense_layers, 0)

    @classmethod
    def tiny(cls) -> "Lfm2MoeConfig":
        """CPU-test scale: every kind of block (conv and attention, dense and
        routed), head width 16."""
        return cls(vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=2, mlp_dim=128,
                   max_len=512, rope_theta=10_000.0,
                   layer_types=(CONV, CONV, FULL, CONV), num_dense_layers=2,
                   moe_mlp_dim=32, n_experts=8, experts_per_token=2, experts_held=8)


def init_lfm2_moe(rng: Array, cfg: Lfm2MoeConfig) -> dict:
    """Seeded tree (the sizes above). Canonical ``[in, out]`` kernels;
    ``models/llama.py::serving_layout`` turns an attention layer's ``wq``,
    ``wk``, ``wv`` as it does every family's."""
    keys = iter(jax.random.split(rng, 2 + cfg.n_layers * 16))
    q_dim, kv_dim, hd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim, cfg.head_dim

    def dense(n_in, n_out, scale=1.0):
        kernel = jax.random.truncated_normal(next(keys), -2.0, 2.0, (n_in, n_out)) * scale * n_in ** -0.5
        return {"kernel": kernel.astype(jnp.float32)}

    def stack(count, n_in, n_out):  # a stack of experts in one draw
        kernel = jax.random.truncated_normal(next(keys), -2.0, 2.0, (count, n_in, n_out)) * n_in ** -0.5
        return kernel.astype(jnp.float32)

    params: dict = {
        "embed_tokens": {"embedding": (jax.random.normal(next(keys), (cfg.vocab_size, cfg.dim))
                                       * EMBED_STD).astype(jnp.float32)},
        "final_norm": L.rmsnorm_init(cfg.dim),
    }
    for i in range(cfg.n_layers):
        layer: dict = {"op_norm": L.rmsnorm_init(cfg.dim), "ffn_norm": L.rmsnorm_init(cfg.dim)}
        if cfg.kinds[i] == CONV:
            layer["conv"] = {
                "w_in": dense(cfg.dim, 3 * cfg.dim),
                # the depthwise taps [d, 3]: column j weighs z_{t-2+j}
                "kernel": (jax.random.normal(next(keys), (cfg.dim, cfg.conv_l_cache))
                           * cfg.conv_l_cache ** -0.5).astype(jnp.float32),
                "w_out": dense(cfg.dim, cfg.dim, WO_SCALE),
            }
        else:
            layer["attn"] = {
                "wq": dense(cfg.dim, q_dim, WQ_SCALE), "wk": dense(cfg.dim, kv_dim),
                "wv": dense(cfg.dim, kv_dim), "wo": dense(q_dim, cfg.dim, WO_SCALE),
                "q_norm": L.rmsnorm_init(hd), "k_norm": L.rmsnorm_init(hd),
            }
        if cfg.routed_layer(i):
            layer["moe"] = {
                "router": dense(cfg.dim, cfg.n_experts),
                "bias": (jax.random.normal(next(keys), (cfg.n_experts,)) * EXPERT_BIAS_STD
                         if cfg.use_expert_bias else jnp.zeros((cfg.n_experts,))).astype(jnp.float32),
                "w_gate": stack(cfg.experts_held, cfg.dim, cfg.moe_mlp_dim),
                "w_up": stack(cfg.experts_held, cfg.dim, cfg.moe_mlp_dim),
                "w_down": stack(cfg.experts_held, cfg.moe_mlp_dim, cfg.dim),
            }
        else:
            layer["mlp"] = {"w_gate": dense(cfg.dim, cfg.mlp_dim), "w_up": dense(cfg.dim, cfg.mlp_dim),
                            "w_down": dense(cfg.mlp_dim, cfg.dim)}
        params[f"layers_{i}"] = layer
    return params


def init_lfm2_cache(cfg: Lfm2MoeConfig, batch: int, max_len: int, pages: int = 0) -> Cache:
    """The contiguous cache of a prefill: K and V of the ATTENTION layers
    ``[La, B, S, Hkv, D]``; ``conv`` ``[Lc, B, 2, d]``, the state each row
    STARTS from (zeros: position 0); ``tail`` ``[Lc, B, pages, 2, d]``, where
    the forward leaves ``z`` at the last two positions of each of the
    segment's ``pages`` equal pages."""
    dt = cfg.jdtype
    kv = (len(cfg.attn_layers), batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    lc = len(cfg.conv_layers)
    return {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
            "conv": jnp.zeros((lc, batch, cfg.conv_taps, cfg.dim), dt),
            "tail": jnp.zeros((lc, batch, pages, cfg.conv_taps, cfg.dim), dt)}


# ------------------------------------------------------ the two token mixers


def _fir(kernel: Array, ext: Array, t: int) -> Array:
    """``ext [B, 2 + t, d]`` (``z`` from two positions before the segment on)
    under the taps ``kernel [d, 3]`` → ``c [B, t, d]`` float32: tap ``j``
    weighs ``z_{t-2+j}``, the three summed in float32 in one order."""
    taps = kernel.astype(jnp.float32)
    return sum(ext[:, j: j + t].astype(jnp.float32) * taps[:, j] for j in range(taps.shape[1]))


def conv_segment(cp: dict, cfg: Lfm2MoeConfig, u: Array, state: Array, lens: Optional[Array],
                 pages: int = 0) -> tuple[Array, Array, Optional[Array]]:
    """The gated short convolution over a segment: u ``[B, T, d]`` from the
    carried ``state [B, 2, d]`` (``z`` at the two positions before the
    segment) → (out ``[B, T, d]``, each row's state after ITS ``lens[b]``
    tokens ``[B, 2, d]`` — after all ``T`` where ``lens`` is None —, the tails
    ``[B, pages, 2, d]`` of the segment's ``pages`` equal pages, or None)."""
    dt = cfg.jdtype
    b, t, d = u.shape
    with jax.named_scope("mixer.conv"):
        with jax.named_scope("conv.in"):
            gate_b, gate_c, x = jnp.split(L.dense(cp["w_in"], u, dt), 3, axis=-1)
            z = (gate_b * x).astype(dt)
        with jax.named_scope("conv.fir"):
            ext = jnp.concatenate([state.astype(dt), z], axis=1)             # [B, 2 + T, d]
            c = _fir(cp["kernel"], ext, t)
        with jax.named_scope("conv.out"):
            out = L.dense(cp["w_out"], gate_c * c.astype(dt), dt)
        # ext[n : n + 2] is z at the last two positions of a row of n tokens
        # (the carried state itself for a row of none)
        if lens is None:
            last = ext[:, t:]
        else:
            last = jax.vmap(lambda row, n: jax.lax.dynamic_slice(row, (n, 0), (cfg.conv_taps, d)))(ext, lens)
        tails = z.reshape(b, pages, t // pages, d)[:, :, -cfg.conv_taps:] if pages else None
    return out, last, tails


def qk_normed(ap: dict, cfg: Lfm2MoeConfig, u: Array, positions: Array) -> tuple[Array, Array, Array]:
    """u [B, T, d] at absolute ``positions`` → q [B, T, H, D], k and v [B, T,
    Hkv, D]: each head of q and of k under its RMSNorm, then rotated."""
    q, k, v = qkv_proj(ap, cfg, u)
    with jax.named_scope("attn.qk_norm"):
        q = L.rmsnorm(ap["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm(ap["k_norm"], k, cfg.norm_eps)
    cos, sin = rope_at(cfg, positions)
    return _rotate_half(q, cos, sin), _rotate_half(k, cos, sin), v


def rope_at(cfg: Lfm2MoeConfig, positions: Array) -> tuple[Array, Array]:
    """cos and sin ``[B, T, 1, D/2]`` at the positions asked: no table of
    ``max_len`` positions is folded into a program."""
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, cfg.head_dim, 2, dtype=jnp.float32) / cfg.head_dim))
    angles = positions.astype(jnp.float32)[..., None, None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_half(x: Array, cos: Array, sin: Array) -> Array:
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _attn(ap: dict, cfg: Lfm2MoeConfig, u: Array, positions: Array, layer: int, cache: Optional[Cache],
          cache_index, pad_mask: Optional[Array], attn_fn, project=qk_normed) -> tuple[Array, Optional[Cache]]:
    """``models/llama.py::_attn`` with the per-head norms before the rotation
    (``project``: what makes q, k and v of ``u`` — ``models/nemotron_h.py``
    brings its own, without either); ``layer`` is the POOL's layer
    (``attn_index``)."""
    dt = cfg.jdtype
    b, t, _ = u.shape
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    q, k, v = project(ap, cfg, u, positions)
    if cache is not None:
        k = _write_cache(cache["k"][layer], k.astype(dt), cache_index)
        v = _write_cache(cache["v"][layer], v.astype(dt), cache_index)
        cache["k"], cache["v"] = cache["k"].at[layer].set(k), cache["v"].at[layer].set(v)
    if attn_fn is not None and t > 1:
        # right pads lie past every real query: position alone hides them
        out = attn_fn(q, k, v, cache_index if cache is not None else 0)
    else:
        if cache is not None:
            mask = jnp.arange(k.shape[1])[None, None, None, :] <= positions[:, None, :, None]
        else:
            mask = L.causal_mask(t)
            if pad_mask is not None:
                mask = mask & pad_mask[:, None, None, :]
        out = L.attention(q, L.repeat_kv(k, h // hkv), L.repeat_kv(v, h // hkv), mask, dt)
    return L.dense(ap["wo"], out.reshape(b, t, -1), dt), cache


def mlp_or_experts(lp: dict, cfg: Lfm2MoeConfig, a: Array, valid: Optional[Array]):
    """The second half of a block on ``a = norm(h)`` → (its output, picks
    ``[B, T, k]`` or None, counts or None)."""
    dt = cfg.jdtype
    if "moe" not in lp:
        gate = jax.nn.silu(L.dense(lp["mlp"]["w_gate"], a, dt))
        return L.dense(lp["mlp"]["w_down"], gate * L.dense(lp["mlp"]["w_up"], a, dt), dt), None, None
    return expert_layer(lp["moe"], cfg, a, valid)


def head_logits(params: dict, cfg: Lfm2MoeConfig, x: Array) -> Array:
    """The final norm and the tied head: ``norm(x) E^T``, float32."""
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    table = params["embed_tokens"]["embedding"].astype(cfg.jdtype)
    return jnp.einsum("btd,vd->btv", x.astype(cfg.jdtype), table,
                      preferred_element_type=jnp.float32)


def lfm2_forward(
    params: dict,
    cfg: Lfm2MoeConfig,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
) -> tuple[Array, Optional[Cache], dict]:
    """ids [B, T] → (logits [B, T, vocab] float32, cache, routed). The prefill
    / scoring contract of ``llama_forward`` over a cache of
    :func:`init_lfm2_cache`: K and V as every family's (the cache's index IS
    the position; ``cache_index`` a scalar or one offset a row), and the
    convolution's state — read from ``cache["conv"]`` as each row's start,
    handed back there as each row's state after its own tokens (``pad_mask``
    says how many: rows are RIGHT-padded), the segment's page tails in
    ``cache["tail"]``. Without a cache every convolution starts from zeros.
    ``routed = {"experts": [Lr, B, T, k] int32, "counts": [4] int32}``. Only
    an ``attn_fn`` that ``takes_prior`` (``kernels/prefill_attention.py``)
    is used; any other is ignored."""
    if not getattr(attn_fn, "takes_prior", False):
        attn_fn = None
    dt = cfg.jdtype
    b, t = ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    lens = pages = None
    if cache is not None:
        cache = dict(cache)
        pages = cache["tail"].shape[2]
        lens = jnp.full((b,), t, jnp.int32) if pad_mask is None else pad_mask.sum(axis=1).astype(jnp.int32)
    zeros = jnp.zeros((b, cfg.conv_taps, cfg.dim), dt)

    x = L.embed(params["embed_tokens"], ids, dt)
    picks, counts = [], jnp.zeros((4,), jnp.int32)
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        u = L.rmsnorm(lp["op_norm"], x, cfg.norm_eps)
        if cfg.kinds[i] == CONV:
            j = cfg.conv_index(i)
            out, last, tails = conv_segment(
                lp["conv"], cfg, u, zeros if cache is None else cache["conv"][j], lens, pages or 0)
            if cache is not None:
                cache["conv"] = cache["conv"].at[j].set(last)
                if pages:
                    cache["tail"] = cache["tail"].at[j].set(tails)
        else:
            out, cache = _attn(lp["attn"], cfg, u, positions, cfg.attn_index(i), cache, cache_index,
                               pad_mask, attn_fn)
        x = x + out
        out, chosen, n = mlp_or_experts(lp, cfg, L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps), pad_mask)
        x = x + out
        if chosen is not None:
            picks.append(chosen)
            counts = counts + n
    routed = {"experts": jnp.stack(picks)} if picks else {}
    return head_logits(params, cfg, x), cache, {**routed, "counts": counts}


def decode_layer(lp: dict, cfg: Lfm2MoeConfig, i: int, x: Array, step: DecodeStep) -> Array:
    """Layer ``i`` of a decode step on ``x [B, 1, d]``: the convolution as a
    segment of ONE token over the slot's state, or attention over the pages
    (pool layer ``attn_index``); then the layer's MLP or experts."""
    u = L.rmsnorm(lp["op_norm"], x, cfg.norm_eps)
    if cfg.kinds[i] == CONV:
        out = step.advance(cfg.conv_index(i), lambda held, _: conv_segment(lp["conv"], cfg, u, held, None)[:2])
    else:
        q, k, v = qk_normed(lp["attn"], cfg, u, step.positions)
        attn = step.attend(q, k, v, cfg.attn_index(i), scope="attn.full")
        out = L.dense(lp["attn"]["wo"], attn.reshape(x.shape[0], 1, -1), cfg.jdtype)
    x = x + out
    # a row that does not advance is routed nowhere (``models/cohere2_moe.py``)
    out, chosen, n = mlp_or_experts(lp, cfg, L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps), step.valid)
    x = x + out
    if chosen is not None:
        step.note({"experts": chosen}, n)
    return x


def recurrent_refusals(state: str, per: str) -> dict:
    """What a family with recurrent ``state`` beside the pages is not served with."""
    return {"draft": f"paged speculation does not serve a family with recurrent state ({{cfg}}): a rejected draft "
                     f"token would have to roll the {state} state back, and the tick keeps no state to roll back to",
            "mesh": f"a family with {state} state ({{cfg}}) is served on one device a process: the state per slot "
                    f"and per {per} has no rule under a mesh yet"}


FAMILY = Family(
    name="lfm2_moe", config=Lfm2MoeConfig, init=init_lfm2_moe, forward=lfm2_forward,
    init_cache=init_lfm2_cache, decode_layer=decode_layer,
    head=lambda params, cfg, x: head_logits(params, cfg, x)[:, 0],
    pool_layers=lambda cfg: len(cfg.attn_layers),
    state=StateBeside(per="page", zeros=lambda cfg, rows: jnp.zeros(
        (len(cfg.conv_layers), rows, cfg.conv_taps, cfg.dim), cfg.jdtype)),
    picks=lambda cfg: {"experts": cfg.experts_per_token}, expert_tiles=expert_tiles,
    refuses=recurrent_refusals("convolution", "page"))
