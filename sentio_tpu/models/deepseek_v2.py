"""Decoder family ``deepseek_v2`` (DeepSeek-V2): sequential pre-norm blocks
whose attention keeps a LATENT in place of keys and values (MLA), a dense
SwiGLU in the leading layers and routed experts chosen inside the best few
GROUPS in every later one, shared experts added whole, an untied head.

    h = norm(x)
    c_q = norm(W_dq h);            q = W_uq c_q  -> n_heads x (q_nope 128 | q_pe 64)
    [c | k_pe] = W_dkv h;          c_kv = norm(c);  q_pe, k_pe rotated (YaRN, 64 dims)
    k_nope[h] = W_uk[h] c_kv;      v[h] = W_uv[h] c_kv                      (EXPANDED)
    scores = (q_nope . k_nope + q_pe . k_pe) * softmax_scale;  x += W_o (softmax(scores) v)
    x += MLP(norm(x))   (layer < first_k_dense_replace)   |   Experts(norm(x))   (later layers)

What a token leaves behind is ``c_kv | k_pe`` AFTER the norm and the
rotation: ``latent_dim`` (576) numbers a layer, shared by all heads — the
page pool holds that and nothing per head (``runtime/paged.py``).

Two forms of one attention. Prefill EXPANDS the cached latents to per-head
keys and values (``W_uk``, ``W_uv``) and attends as any attention does
(:func:`deepseek_v2_forward`). Decode ABSORBS ``W_uk`` into the query and
``W_uv`` into the output and attends over the latents themselves
(:func:`absorb_query`, :func:`latent_attention`, :func:`unabsorb`):

    q_lat[h] = W_uk[h]^T q_nope[h];   score = q_lat[h] . c_kv + q_pe[h] . k_pe
    o_lat[h] = sum p c_kv;            o[h] = W_uv[h] o_lat[h]

the same function (``tests/test_deepseek_v2.py`` holds them equal in
float32), 128 heads reading ONE 576-wide vector a position.

The tree keeps ``kv_b_proj`` split by head into its key half ``w_uk`` and its
value half ``w_uv``, both ``[n_heads, 128, kv_lora_rank]`` (``[out, in]`` a
head, as the published matrix lies): each form reads its half where it lies.
The rotary pairs are the checkpoint's interleaved ``(2j, 2j+1)``, rotated in
place (the published code de-interleaves q_pe and k_pe alike first, which
leaves every score as it is). Frequencies are YaRN's (:func:`yarn_inv_freq`);
cos and sin carry ``mscale / mscale_all_dim`` (1 where both are equal) and the
softmax scale carries ``yarn_mscale(factor, mscale_all_dim)`` squared.

Like ``cohere2_moe`` the config says which slice of the routed experts THIS
process holds (``experts_held`` from ``expert_offset``): the published groups
ARE the deployment — ``n_group`` devices share a layer, one group each — and
``models/moe.py::expert_layer`` computes the part of the routed sum its own
experts give. ``vocab_size`` is the rows of the embedding and of the head
held here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from sentio_tpu.models import layers as L
from sentio_tpu.models.cohere2_moe import _SCORE_BLOCK_BYTES, ROUTED_DRAFT, rope_interleaved
from sentio_tpu.models.families import DecodeStep, Family
from sentio_tpu.models.llama import Cache, LlamaConfig, _write_cache
from sentio_tpu.models.moe import expert_layer, expert_tiles

Array = jax.Array

# Seeded weights (tests, the fake-model mode, the benchmark's checkpoints).
# The head is untied, so the dense family's zero columns for the tokenizer's
# text ids carry over (``benchmark/families``) and nothing of the tied head's
# trouble (``models/cohere2_moe.py``) applies. What does: (1) unit queries and
# keys give a softmax over thousands of keys that is nearly flat, an attention
# output that is the context's average — and a reference check that cannot see
# a wrong key or latent (PERF.md section 6, PR 33): the query up-projection is
# drawn ``WQ_SCALE`` times as large, so that scores have the spread the
# ``cohere2_moe`` tree's have (2.5 x the YaRN softmax scale's m^2 1.59 = 4.0
# standard deviations; 4 there) and attention is peaked
# on a few keys that change with the query. (2) The gates are ``s x 16`` with
# ``s`` a softmax over 160 and no renormalisation, so the router's size sets
# how much of a layer the routed experts carry. It is drawn over its fan-in
# like any matrix: logits of unit size, a softmax whose six picks hold about a
# fifth of it, gates that sum to 3 to 4 — the routed sum a few times the
# shared experts', neither drowning the other. Drawn twice as large (gates
# that sum to 7) a 96-token greedy answer and the groups' evenness read the
# same (CPU at hidden 5120, two layers: 94-96 different tokens of 96 a row,
# 11.2-13.8 % of the pairs in the held group, PERF.md section 6, PR 38); the
# plain draw is kept.
WQ_SCALE = 2.5
WO_SCALE = 0.3


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class DeepseekV2Config(LlamaConfig):
    """``mlp_dim``: the dense layers' SwiGLU; ``moe_mlp_dim``: ONE routed or
    shared expert; ``n_kv_heads`` is the published count (every head has its
    own key under the expanded form) and sizes nothing here; ``max_len`` the
    positions the model declares (nothing is sized by it)."""

    vocab_size: int = 102_400
    dim: int = 5120
    n_layers: int = 8
    n_heads: int = 128
    n_kv_heads: int = 128
    mlp_dim: int = 12_288
    max_len: int = 163_840
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_kind: str = "yarn"
    rope_factor: float = 40.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    moe_mlp_dim: int = 1536
    n_experts: int = 160                # the router's width
    experts_per_token: int = 6
    n_shared_experts: int = 2
    gate_fn: str = "softmax"
    n_group: int = 8
    topk_group: int = 3
    topk_method: str = "group_limited_greedy"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    shared_combine: str = "sum"
    tie_embeddings: bool = False
    experts_held: int = 20              # the slice of the routed experts held here ...
    expert_offset: int = 0              # ... starting at this expert

    def __post_init__(self):
        stated = (self.rope_kind, self.topk_method, self.shared_combine, self.tie_embeddings,
                  self.moe_layer_freq)
        if stated != ("yarn", "group_limited_greedy", "sum", False, 1):
            raise ValueError(
                f"rope_kind, topk_method, shared_combine, tie_embeddings, moe_layer_freq = {stated}: "
                "this family is YaRN rotary, group-limited routing, summed shared experts, an untied "
                "head, every layer after the dense ones routed")
        if self.n_experts % self.n_group or not 0 < self.topk_group <= self.n_group:
            raise ValueError(f"{self.n_experts} experts in {self.n_group} groups, {self.topk_group} kept")
        if self.experts_per_token > self.topk_group * (self.n_experts // self.n_group):
            raise ValueError("more picks a token than the kept groups hold experts")
        if not 0 <= self.expert_offset <= self.n_experts - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} of {self.n_experts}")
        if not 0 <= self.first_k_dense_replace <= self.n_layers:
            raise ValueError(f"{self.first_k_dense_replace} dense layers of {self.n_layers}")

    @property
    def head_dim(self) -> int:
        """A query head's width: the part that meets a key unrotated and the rotated part."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a token leaves in the pool a layer: ``c_kv | k_pe``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5 * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2

    @property
    def rope_cos_sin_scale(self) -> float:
        return (yarn_mscale(self.rope_factor, self.rope_mscale)
                / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))

    def routed_layer(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.first_k_dense_replace

    @classmethod
    def tiny(cls, **over) -> "DeepseekV2Config":
        """CPU-test scale: every mechanism, all experts held. The scaling
        factor keeps the published size of a token's gates: 6 picks of 160
        times 16 are 0.6 under even scores, 4 picks of 16 times 2 are 0.5."""
        return cls(**{**dict(
            vocab_size=512, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, mlp_dim=128, max_len=512,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_factor=4.0, rope_original_max_len=64, moe_mlp_dim=32, n_experts=16,
            experts_per_token=4, n_shared_experts=2, n_group=4, topk_group=2, routed_scaling_factor=2.0,
            experts_held=16, expert_offset=0), **over})


def yarn_correction_dims(cfg: DeepseekV2Config) -> tuple[int, int]:
    """The two rotary dimensions between which YaRN's ramp runs: the one that
    turns ``rope_beta_fast`` times over the original length, rounded down, and
    the one that turns ``rope_beta_slow`` times, rounded up."""
    d = cfg.qk_rope_head_dim

    def at(turns: float) -> float:
        return d * math.log(cfg.rope_original_max_len / (turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    low, high = math.floor(at(cfg.rope_beta_fast)), math.ceil(at(cfg.rope_beta_slow))
    return max(low, 0), min(high, d - 1)


def yarn_inv_freq(cfg: DeepseekV2Config) -> np.ndarray:
    """``[qk_rope_head_dim / 2]`` float32: ``theta^(-2i/d)`` below the ramp
    (fast dimensions, left as trained), the same over ``rope_factor`` above it
    (slow ones, interpolated), a linear blend between."""
    d = cfg.qk_rope_head_dim
    extra = 1.0 / (cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    if cfg.rope_factor <= 1:
        return extra
    low, high = yarn_correction_dims(cfg)
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def init_deepseek_v2(rng: Array, cfg: DeepseekV2Config) -> dict:
    """Seeded float32 tree (the distributions: the head of this file)."""
    keys = iter(jax.random.split(rng, 2 + cfg.n_layers * 14))
    h, nope, rope, vd, r = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                            cfg.v_head_dim, cfg.kv_lora_rank)

    def dense(n_in, n_out, scale=1.0):
        return {"kernel": L.dense_init(next(keys), n_in, n_out, with_bias=False)["kernel"] * scale}

    def stack(n, n_in, n_out):
        return jnp.stack([L.dense_init(k, n_in, n_out, with_bias=False)["kernel"]
                          for k in jax.random.split(next(keys), n)])

    def experts(n):
        return {"w_gate": stack(n, cfg.dim, cfg.moe_mlp_dim), "w_up": stack(n, cfg.dim, cfg.moe_mlp_dim),
                "w_down": stack(n, cfg.moe_mlp_dim, cfg.dim)}

    params: dict = {
        "embed_tokens": L.embed_init(next(keys), cfg.vocab_size, cfg.dim),
        "lm_head": L.dense_init(next(keys), cfg.dim, cfg.vocab_size, with_bias=False),
        "final_norm": L.rmsnorm_init(cfg.dim),
    }
    for i in range(cfg.n_layers):
        layer = {
            "attn_norm": L.rmsnorm_init(cfg.dim),
            "attn": {
                "wq_a": dense(cfg.dim, cfg.q_lora_rank),
                "q_norm": L.rmsnorm_init(cfg.q_lora_rank),
                "wq_b": dense(cfg.q_lora_rank, h * (nope + rope), WQ_SCALE),
                "wkv_a": dense(cfg.dim, r + rope),
                "kv_norm": L.rmsnorm_init(r),
                # kv_b_proj's halves, [out, in] a head: fan-in is the latent
                "w_uk": jnp.swapaxes(stack(h, r, nope), -1, -2),
                "w_uv": jnp.swapaxes(stack(h, r, vd), -1, -2),
                "wo": dense(h * vd, cfg.dim, WO_SCALE),
            },
            "mlp_norm": L.rmsnorm_init(cfg.dim),
        }
        if cfg.routed_layer(i):
            layer["moe"] = {"router": dense(cfg.dim, cfg.n_experts),
                            **experts(cfg.experts_held), "shared": experts(cfg.n_shared_experts)}
        else:
            layer["mlp"] = {"w_gate": dense(cfg.dim, cfg.mlp_dim), "w_up": dense(cfg.dim, cfg.mlp_dim),
                            "w_down": dense(cfg.mlp_dim, cfg.dim)}
        params[f"layers_{i}"] = layer
    return params


def init_latent_cache(cfg: DeepseekV2Config, batch: int, max_len: int) -> Cache:
    """The contiguous cache of a prefill: one latent a position and layer,
    under ``k``; there is no ``v``."""
    return {"k": jnp.zeros((cfg.n_layers, batch, max_len, 1, cfg.latent_dim), cfg.jdtype), "v": None}


# ------------------------------------------------------- the attention's parts


def _rotate(cfg: DeepseekV2Config, x: Array, positions: Array) -> Array:
    out = rope_interleaved(x, positions, cfg.rope_theta, inv_freq=yarn_inv_freq(cfg))
    scale = cfg.rope_cos_sin_scale
    return out if scale == 1.0 else (out.astype(jnp.float32) * scale).astype(out.dtype)


def mla_query(ap: dict, cfg: DeepseekV2Config, h: Array, positions: Array) -> tuple[Array, Array]:
    """h [B, T, d] → (q_nope [B, T, H, nope], q_pe [B, T, H, rope] rotated)."""
    dt = cfg.jdtype
    b, t, _ = h.shape
    with jax.named_scope("mla.q"):
        c_q = L.rmsnorm(ap["q_norm"], L.dense(ap["wq_a"], h, dt), cfg.norm_eps)
        # [out, in] in the serving tree (``models/llama.py::serving_layout``), canonical otherwise
        q = L.dense_t(ap["wq_b_t"], c_q, dt) if "wq_b_t" in ap else L.dense(ap["wq_b"], c_q, dt)
        q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
        q_nope, q_pe = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
        return q_nope, _rotate(cfg, q_pe, positions)


def mla_latent(ap: dict, cfg: DeepseekV2Config, h: Array, positions: Array) -> Array:
    """h [B, T, d] → what the pool keeps, [B, T, 1, latent_dim]: the normed
    latent beside the rotated shared key."""
    dt = cfg.jdtype
    with jax.named_scope("mla.latent"):
        kv_a = L.dense(ap["wkv_a"], h, dt)
        c_kv = L.rmsnorm(ap["kv_norm"], kv_a[..., : cfg.kv_lora_rank], cfg.norm_eps)
        k_pe = _rotate(cfg, kv_a[..., None, cfg.kv_lora_rank:], positions)
        return jnp.concatenate([c_kv[..., None, :], k_pe], axis=-1).astype(dt)


def expand_latents(ap: dict, cfg: DeepseekV2Config, latents: Array) -> tuple[Array, Array, Array]:
    """latents [B, S, 1, latent_dim] → (k_nope [B, S, H, nope], k_pe [B, S,
    rope], v [B, S, H, vd]): per-head keys and values made again from what the
    pool holds."""
    dt = cfg.jdtype
    with jax.named_scope("mla.expand"):
        c_kv = latents[:, :, 0, : cfg.kv_lora_rank].astype(dt)
        k_nope = jnp.einsum("bsc,hnc->bshn", c_kv, ap["w_uk"].astype(dt))
        v = jnp.einsum("bsc,hvc->bshv", c_kv, ap["w_uv"].astype(dt))
        return k_nope, latents[:, :, 0, cfg.kv_lora_rank:].astype(dt), v


def absorb_query(ap: dict, cfg: DeepseekV2Config, q_nope: Array) -> Array:
    """q_nope [B, H, nope] → q_lat [B, H, kv_lora_rank] = W_uk[h]^T q_nope[h]."""
    with jax.named_scope("mla.absorb"):
        return jnp.einsum("bhn,hnc->bhc", q_nope, ap["w_uk"].astype(q_nope.dtype))


def unabsorb(ap: dict, cfg: DeepseekV2Config, o_lat: Array) -> Array:
    """o_lat [B, H, kv_lora_rank] → o [B, H, vd] = W_uv[h] o_lat[h]."""
    with jax.named_scope("mla.absorb"):
        return jnp.einsum("bhc,hvc->bhv", o_lat, ap["w_uv"].astype(o_lat.dtype))


def latent_attention(q_lat: Array, q_pe: Array, latents: Array, seen: Array, scale: float) -> Array:
    """The absorbed form, plain: q_lat [B, H, r], q_pe [B, H, rope] over
    latents [B, S, r + rope] where ``seen [B, S]`` → o_lat [B, H, r]. The
    VALUE of a position is the first ``r`` columns of its key. Softmax in
    float32."""
    r = q_lat.shape[-1]
    scores = (jnp.einsum("bhc,bsc->bhs", q_lat, latents[..., :r], preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,bsr->bhs", q_pe, latents[..., r:], preferred_element_type=jnp.float32))
    scores = jnp.where(seen[:, None, :], scores * scale, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bsc->bhc", weights.astype(q_lat.dtype), latents[..., :r])


def expanded_attention(q_nope: Array, q_pe: Array, k_nope: Array, k_pe: Array, v: Array,
                       q_pos: Array, key_ok: Optional[Array], scale: float, dtype) -> Array:
    """The expanded form: q_nope [B, T, H, n], q_pe [B, T, H, r] at absolute
    positions ``q_pos [B, T]`` over keys k_nope [B, S, H, n], k_pe [B, S, r]
    (ONE rotated key a position, every head's) and values v [B, S, H, vd] that
    sit AT their positions → [B, T, H * vd]. Causal by position, and where
    ``key_ok [B, S]`` allows. The queries go a block at a time so that no
    ``[B, H, T, S]`` score tensor exists whole; k_pe is never spread over the
    heads. Softmax in float32."""
    b, t, h, _ = q_nope.shape
    s = k_nope.shape[1]
    block = t
    while block > 64 and block % 2 == 0 and b * h * block * s * 4 > _SCORE_BLOCK_BYTES:
        block //= 2
    kj = jnp.arange(s)[None, None, None, :]

    def one(args):
        qn, qr, pb = args                                  # [B, blk, H, n], [B, blk, H, r], [B, blk]
        logits = (jnp.einsum("bqhn,bshn->bhqs", qn.astype(dtype), k_nope, preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bsr->bhqs", qr.astype(dtype), k_pe, preferred_element_type=jnp.float32))
        mask = kj <= pb[:, None, :, None]
        if key_ok is not None:
            mask &= key_ok[:, None, None, :]
        logits = jnp.where(mask, logits * scale, jnp.finfo(jnp.float32).min)
        weights = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqs,bshv->bqhv", weights.astype(dtype), v)

    def blocks(x):
        return x.reshape(b, t // block, block, *x.shape[2:]).swapaxes(0, 1)

    with jax.named_scope("attn.expanded"):
        out = jax.lax.map(one, (blocks(q_nope), blocks(q_pe), blocks(q_pos)))   # [nblk, B, blk, H, vd]
    return out.swapaxes(0, 1).reshape(b, t, h * v.shape[-1])


def mlp_or_experts(lp: dict, cfg: DeepseekV2Config, xm: Array, valid: Optional[Array]):
    """The second half of a block on ``xm = norm(x)`` → (its output, picks or
    None, counts or None): a dense SwiGLU, or the routed layer's share."""
    dt = cfg.jdtype
    if "moe" not in lp:
        gate = jax.nn.silu(L.dense(lp["mlp"]["w_gate"], xm, dt))
        return L.dense(lp["mlp"]["w_down"], gate * L.dense(lp["mlp"]["w_up"], xm, dt), dt), None, None
    out, experts, counts, *groups = expert_layer(lp["moe"], cfg, xm, valid)   # groups: where n_group > 1
    return out, {"experts": experts, "groups": groups[0] if groups else None}, counts


def deepseek_v2_forward(
    params: dict,
    cfg: DeepseekV2Config,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
) -> tuple[Array, Optional[Cache], dict]:
    """ids [B, T] → (logits [B, T, vocab] float32, cache, routed). The
    prefill / scoring contract of ``llama_forward`` with a LATENT cache
    (:func:`init_latent_cache`: fresh, or primed with a prior's latents; its
    index IS the position; ``cache_index`` a scalar or one offset a row) —
    every layer writes its new latents and EXPANDS the whole cache, prior
    included, to keys and values. ``routed = {"experts": [Lr, B, T, k],
    "groups": [Lr, B, T, topk_group] int32 picks of the routed layers,
    "counts": [4] int32}``. An ``attn_fn`` that ``takes_prior``
    (``kernels/prefill_attention.py``) attends in place of
    :func:`expanded_attention` wherever there is more than one query; any
    other is not this family's (keys wider than values) and is ignored."""
    if not getattr(attn_fn, "takes_prior", False):
        attn_fn = None
    dt = cfg.jdtype
    b, t = ids.shape
    if cache is not None:
        cache = dict(cache)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

    x = L.embed(params["embed_tokens"], ids, dt)
    picks: dict = {"experts": [], "groups": []}
    counts = jnp.zeros((4,), jnp.int32)
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        ap = lp["attn"]
        h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
        q_nope, q_pe = mla_query(ap, cfg, h, positions)
        latents = mla_latent(ap, cfg, h, positions)
        if cache is not None:
            latents = _write_cache(cache["k"][i], latents, cache_index)
            cache["k"] = cache["k"].at[i].set(latents)
            key_ok = None       # causal by position hides the unwritten tail
        else:
            key_ok = pad_mask
        k_nope, k_pe, v = expand_latents(ap, cfg, latents)
        if attn_fn is not None and t > 1:
            # right pads lie past every real query: position alone hides them
            attn = attn_fn(q_nope.astype(dt), k_nope, v, cache_index if cache is not None else 0,
                           q_pe.astype(dt), k_pe, sm_scale=cfg.softmax_scale).reshape(b, t, -1)
        else:
            attn = expanded_attention(q_nope, q_pe, k_nope, k_pe, v, positions, key_ok,
                                      cfg.softmax_scale, dt)
        x = x + L.dense(ap["wo"], attn, dt)
        out, chosen, n = mlp_or_experts(lp, cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps), pad_mask)
        x = x + out
        if chosen is not None:
            for name, value in chosen.items():
                picks[name].append(value)
            counts = counts + n
    routed = {name: jnp.stack(value) for name, value in picks.items() if value}
    return head_logits(params, cfg, x), cache, {**routed, "counts": counts}


def head_logits(params: dict, cfg: DeepseekV2Config, x: Array) -> Array:
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.dense(params["lm_head"], x, cfg.jdtype).astype(jnp.float32)


def decode_layer(lp: dict, cfg: DeepseekV2Config, i: int, x: Array, step: DecodeStep) -> Array:
    """Layer ``i`` of a decode step on ``x [B, 1, d]``: the step's latent goes
    into each row's current page and the query is ABSORBED, so that attention
    reads the latents themselves and no key or value is ever formed (the head
    of this file); then the layer's MLP or its share of the experts."""
    dt = cfg.jdtype
    ap = lp["attn"]
    h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
    q_nope, q_pe = mla_query(ap, cfg, h, step.positions)
    latent = mla_latent(ap, cfg, h, step.positions)[:, 0, 0]        # [B, latent_dim]
    # (the queries as a thunk: made once the latent is written, the order this step has always traced)
    o_lat = step.attend(latent, lambda: (absorb_query(ap, cfg, q_nope[:, 0]), q_pe[:, 0]), i, cfg.softmax_scale)
    attn = unabsorb(ap, cfg, o_lat.astype(dt))
    x = x + L.dense(ap["wo"], attn.reshape(x.shape[0], 1, -1), dt)
    # a row that does not advance is routed nowhere (``models/cohere2_moe.py``)
    out, chosen, n = mlp_or_experts(lp, cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps), step.valid)
    x = x + out
    if chosen is not None:
        step.note(chosen, n)
    return x


def _picks(cfg: DeepseekV2Config) -> dict:
    return {"experts": cfg.experts_per_token, **({"groups": cfg.topk_group} if cfg.n_group > 1 else {})}


FAMILY = Family(
    name="deepseek_v2", config=DeepseekV2Config, init=init_deepseek_v2, forward=deepseek_v2_forward,
    init_cache=init_latent_cache, decode_layer=decode_layer,
    head=lambda params, cfg, x: head_logits(params, cfg, x)[:, 0],
    latent=True, picks=_picks, expert_tiles=expert_tiles,
    refuses={"draft": ROUTED_DRAFT,
             "int8": "a latent pool ({cfg}) is bf16 — int8 latents have no kernel and no quality gate yet",
             "mesh": "a latent pool ({cfg}) is served on one device a process: a latent has no heads to "
                     "split over tp"})
