"""Decoder family ``cohere2_moe`` (Command A+): a PARALLEL block — one
mean-centred LayerNorm feeding attention and routed experts side by side —
with attention wider than the hidden size, layer kinds that differ inside one
model (a sliding window with interleaved rotary beside rotation-free full
attention), sigmoid-gated routed experts of which a process holds a SHARE,
averaged shared experts, and a head tied to the embedding.

    h = LN(x);  x ← x + Attn_i(h) + Experts(h)
    logits = logit_scale · LN_final(x) Eᵀ

The config says which slice of the routed experts THIS process holds
(``experts_held`` from ``expert_offset``): the deployment the benchmark
states is eight chips that share each layer (data-parallel attention, expert
parallelism, the vocabulary split), and one of them is what runs here — the
router keeps its ``n_experts`` outputs and its ``experts_per_token``, the
expert layer (``models/moe.py::expert_layer``) computes the part of the
routed sum its own experts give, and nothing stands in for the other seven
or their exchange. ``vocab_size`` is the rows of the embedding held here.

Pure functions over an explicit parameter tree, like ``models/llama.py``,
whose ``qkv_proj`` / ``serving_layout`` this family shares (projections go to
``n_heads · head_dim`` columns whatever ``dim`` is). The interleaved rotary
(``rope_gptj``: pairs ``(2j, 2j+1)``) is applied IN THE PROGRAM, as a product
with a fixed ±1 matrix that swaps each pair (exact: every output is one
input), from angles computed at the positions asked — no table of
``max_len`` positions is folded into a program.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sentio_tpu.models import layers as L
from sentio_tpu.models.families import DecodeStep, Family
from sentio_tpu.models.llama import Cache, LlamaConfig, _write_cache, init_cache, qkv_proj
from sentio_tpu.models.moe import expert_layer, expert_tiles

Array = jax.Array

SLIDING, FULL = "sliding_attention", "full_attention"

# Seeded weights (tests, the fake-model mode, the benchmark's checkpoints).
# With a head TIED to the embedding and random weights at the usual sizes a
# greedy answer collapses at published widths, and a routed layer then sees
# ONE token: a chip run read 1.3 of 16 held experts touched a layer and step
# where even routing gives 8 (PERF.md section 6, PR 33). Two causes, two
# sizes. (1) A token's own row stands in the residual stream, so its own
# logit (3 x |row|^2 = 4.9 at 0.02 a value, 4096 wide) rivals the largest of
# 32k others (5.3) and the token repeats: the embedding is drawn a quarter as
# large. (2) Queries and keys of unit size give scores of size 1, a softmax
# over 800 keys that is nearly flat, and an attention output that is the
# context's AVERAGE — the same vector at every step, voting for the same few
# tokens in every row: the query projection is drawn four times as large, so
# attention is peaked on a few keys that change with the query, as a trained
# model's is. (Attention's output drawn a tenth as large cured the collapse
# too, and blinded the reference check to the window and to int8 pages.)
# 250-token greedy answers then hold 225–240 different tokens, rows apart
# (CPU at hidden 4096, two layers).
EMBED_STD = 0.005
WQ_SCALE = 4.0
WO_SCALE = 0.3


@dataclass(frozen=True)
class Cohere2MoeConfig(LlamaConfig):
    """``dim``: hidden size; ``mlp_dim``: the width of ONE routed or shared
    expert; ``norm_eps`` is the LayerNorm's; ``max_len`` the positions the
    model declares (nothing is sized by it)."""

    vocab_size: int = 32_768
    dim: int = 4096
    n_layers: int = 4
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128                 # a FIELD: attention is n_heads * head_dim wide, not dim
    mlp_dim: int = 4096
    max_len: int = 200_000
    rope_theta: float = 50_000.0
    norm_eps: float = 1e-5
    norm_kind: str = "layernorm"        # mean-centred, scale only
    parallel_block: bool = True
    # one kind a layer, comma-joined (a string reads the same in a checkpoint's
    # JSON meta, in ``/info`` and here): SLIDING (window + rotary) or FULL
    # (causal, no rotation)
    layer_kinds: str = ",".join((SLIDING, SLIDING, SLIDING, FULL))
    sliding_window: int = 4096
    rope_kind: str = "interleaved"
    logit_scale: float = 1.0
    tie_embeddings: bool = True
    n_experts: int = 128                # the router's width
    experts_per_token: int = 8
    n_shared_experts: int = 4
    gate_fn: str = "sigmoid"
    # how ``models/moe.py::expert_layer`` picks and weighs, as this family
    # always does: over all experts at once, gates renormalised over the picks
    # and not scaled, the shared experts' outputs averaged. Constants of the
    # family and no fields: a checkpoint's meta and ``/info`` stay as they are
    n_group: ClassVar[int] = 1
    topk_group: ClassVar[int] = 1
    norm_topk_prob: ClassVar[bool] = True
    routed_scaling_factor: ClassVar[float] = 1.0
    shared_combine: ClassVar[str] = "mean"
    experts_held: int = 16              # the slice of the routed experts held here ...
    expert_offset: int = 0              # ... starting at this expert

    def __post_init__(self):
        # what this family IS, reported by /info like every field; another value is another family
        stated = (self.norm_kind, self.parallel_block, self.rope_kind, self.tie_embeddings)
        if stated != ("layernorm", True, "interleaved", True):
            raise ValueError(f"norm_kind, parallel_block, rope_kind, tie_embeddings = {stated}: this "
                             "family is a parallel block under a LayerNorm, interleaved rotary, a tied head")
        if len(self.kinds) != self.n_layers:
            raise ValueError(f"{len(self.kinds)} layer kinds for {self.n_layers} layers")
        if not set(self.kinds) <= {SLIDING, FULL}:
            raise ValueError(f"layer kinds must be {SLIDING!r} or {FULL!r}: {self.layer_kinds}")
        if not 0 <= self.expert_offset <= self.n_experts - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} of {self.n_experts}")

    @property
    def kinds(self) -> tuple[str, ...]:
        return tuple(self.layer_kinds.split(","))

    def window(self, layer: int) -> Optional[int]:
        """Keys a query of ``layer`` sees behind itself, itself included."""
        return self.sliding_window if self.kinds[layer] == SLIDING else None

    @classmethod
    def tiny(cls, **over) -> "Cohere2MoeConfig":
        """CPU-test scale: every mechanism, all experts held."""
        return cls(**{**dict(
            vocab_size=512, dim=64, n_layers=2, n_heads=8, n_kv_heads=2, head_dim=16,
            mlp_dim=32, max_len=512, rope_theta=10_000.0, layer_kinds=f"{SLIDING},{FULL}",
            sliding_window=24, n_experts=16, experts_per_token=4, n_shared_experts=2,
            experts_held=16, expert_offset=0), **over})


def init_cohere2_moe(rng: Array, cfg: Cohere2MoeConfig) -> dict:
    """Seeded float32 tree. No ``lm_head``: the head reads the embedding."""
    keys = iter(jax.random.split(rng, 1 + cfg.n_layers * 11))
    q_dim, kv_dim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def stack(key, n, in_dim, out_dim):
        return jnp.stack([L.dense_init(k, in_dim, out_dim, with_bias=False)["kernel"]
                          for k in jax.random.split(key, n)])

    def experts(n):
        return {"w_gate": stack(next(keys), n, cfg.dim, cfg.mlp_dim),
                "w_up": stack(next(keys), n, cfg.dim, cfg.mlp_dim),
                "w_down": stack(next(keys), n, cfg.mlp_dim, cfg.dim)}

    table = jax.random.normal(next(keys), (cfg.vocab_size, cfg.dim)) * EMBED_STD
    params: dict = {"embed_tokens": {"embedding": table.astype(jnp.float32)},
                    "final_norm": L.rmsnorm_init(cfg.dim)}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = {
            "norm": L.rmsnorm_init(cfg.dim),
            "attn": {
                "wq": {"kernel": L.dense_init(next(keys), cfg.dim, q_dim,
                                              with_bias=False)["kernel"] * WQ_SCALE},
                "wk": L.dense_init(next(keys), cfg.dim, kv_dim, with_bias=False),
                "wv": L.dense_init(next(keys), cfg.dim, kv_dim, with_bias=False),
                "wo": {"kernel": L.dense_init(next(keys), q_dim, cfg.dim,
                                              with_bias=False)["kernel"] * WO_SCALE},
            },
            "moe": {"router": L.dense_init(next(keys), cfg.dim, cfg.n_experts, with_bias=False),
                    **experts(cfg.experts_held), "shared": experts(cfg.n_shared_experts)},
        }
    return params


def centred_norm(params: dict, x: Array, eps: float) -> Array:
    """``(x - mean) / sqrt(var + eps) · scale`` in float32; no bias."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * params["scale"]).astype(x.dtype)


def _pair_swap(head_dim: int) -> np.ndarray:
    """``x @ R`` is ``(-x1, x0, -x3, x2, ...)``: each pair rotated a quarter."""
    r = np.zeros((head_dim, head_dim), np.float32)
    even = np.arange(0, head_dim, 2)
    r[even + 1, even] = -1.0
    r[even, even + 1] = 1.0
    return r


def rope_interleaved(x: Array, positions: Array, theta: float, inv_freq=None) -> Array:
    """x [B, T, H, D] rotated pair by pair — ``(2j, 2j+1)`` by ``pos ·
    theta^(-2j/D)``, or by ``pos · inv_freq[j]`` where a family brings its own
    frequencies — at ``positions [B, T]``."""
    d = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    angle = positions.astype(jnp.float32)[..., None] * inv_freq            # [B, T, D/2]
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)[:, :, None, :]
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)[:, :, None, :]
    swapped = jnp.einsum("bthd,de->bthe", x, jnp.asarray(_pair_swap(d), x.dtype),
                         preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + swapped * sin).astype(x.dtype)


def qk_rotated(cfg: Cohere2MoeConfig, layer: int, q: Array, k: Array, positions: Array):
    """A sliding layer's q and k are rotated; a full layer's are not."""
    if cfg.kinds[layer] == FULL:
        return q, k
    return (rope_interleaved(q, positions, cfg.rope_theta),
            rope_interleaved(k, positions, cfg.rope_theta))


# float32 scores one block of queries may take in a prefill (bytes)
_SCORE_BLOCK_BYTES = 256 << 20


def windowed_attention(q: Array, k: Array, v: Array, q_pos: Array, key_ok: Optional[Array],
                       window: Optional[int], dtype) -> Array:
    """Grouped-query attention by positions: q [B, T, H, D] at absolute
    positions ``q_pos [B, T]`` over keys k, v [B, S, Hkv, D] that sit AT
    their positions (key ``j`` is position ``j``). A query at ``p`` sees keys
    ``p - window < j <= p`` (``window`` None: ``j <= p``) where ``key_ok [B,
    S]`` allows. Keys are never expanded to query heads, and the queries go
    a block at a time so that no ``[B, H, T, S]`` score tensor exists whole
    (128 heads over 4k keys would be gigabytes). Softmax in float32."""
    b, t, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    block = t
    while block > 128 and block % 2 == 0 and b * h * block * s * 4 > _SCORE_BLOCK_BYTES:
        block //= 2
    qg = q.reshape(b, t // block, block, hkv, rep, d).astype(dtype)
    pos = q_pos.reshape(b, t // block, block)
    kj = jnp.arange(s)[None, None, None, None, :]
    scale = 1.0 / np.sqrt(d)

    def one(args):
        qb, pb = args                                   # [B, blk, Hkv, rep, D], [B, blk]
        logits = jnp.einsum("bqgrd,bsgd->bgrqs", qb, k.astype(dtype),
                            preferred_element_type=jnp.float32) * scale
        at = pb[:, None, None, :, None]
        mask = kj <= at
        if window is not None:
            mask &= kj > at - window
        if key_ok is not None:
            mask &= key_ok[:, None, None, None, :]
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        weights = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bgrqs,bsgd->bqgrd", weights.astype(dtype), v.astype(dtype))

    with jax.named_scope("attn.window" if window is not None else "attn.full"):
        out = jax.lax.map(one, (qg.swapaxes(0, 1), pos.swapaxes(0, 1)))   # [nblk, B, blk, ...]
    return out.swapaxes(0, 1).reshape(b, t, h * d)


def cohere2_forward(
    params: dict,
    cfg: Cohere2MoeConfig,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
) -> tuple[Array, Optional[Cache], dict]:
    """ids [B, T] → (logits [B, T, vocab] float32, cache, routed). The
    prefill / scoring contract of ``llama_forward`` (a fresh or primed
    contiguous cache whose index IS the position; ``cache_index`` a scalar
    or one offset a row), plus what the expert layers decided: ``routed =
    {"experts": [L, B, T, k] int32 picks, "counts": [4] int32}``. An
    ``attn_fn`` that ``takes_prior`` (``kernels/prefill_attention.py``: it
    knows a window) attends in place of :func:`windowed_attention` wherever
    there is more than one query; any other knows no window and is ignored."""
    if not getattr(attn_fn, "takes_prior", False):
        attn_fn = None
    dt = cfg.jdtype
    b, t = ids.shape
    if cache is not None:
        cache = dict(cache)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

    x = L.embed(params["embed_tokens"], ids, dt)
    picks, counts = [], jnp.zeros((4,), jnp.int32)
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        h = centred_norm(lp["norm"], x, cfg.norm_eps)
        q, k, v = qkv_proj(lp["attn"], cfg, h)
        q, k = qk_rotated(cfg, i, q, k, positions)
        if cache is not None:
            k_all = _write_cache(cache["k"][i], k.astype(dt), cache_index)
            v_all = _write_cache(cache["v"][i], v.astype(dt), cache_index)
            cache["k"] = cache["k"].at[i].set(k_all)
            cache["v"] = cache["v"].at[i].set(v_all)
            key_ok = None       # causal by position hides the unwritten tail
        else:
            k_all, v_all, key_ok = k, v, pad_mask
        if attn_fn is not None and t > 1:
            # right pads lie past every real query: position alone hides them
            attn = attn_fn(q.astype(dt), k_all.astype(dt), v_all.astype(dt),
                           cache_index if cache is not None else 0, window=cfg.window(i)).reshape(b, t, -1)
        else:
            attn = windowed_attention(q, k_all, v_all, positions, key_ok, cfg.window(i), dt)
        routed, chosen, n = expert_layer(lp["moe"], cfg, h, pad_mask)
        x = x + L.dense(lp["attn"]["wo"], attn, dt) + routed
        picks.append(chosen)
        counts = counts + n
    return head_logits(params, cfg, x), cache, {"experts": jnp.stack(picks), "counts": counts}


def head_logits(params: dict, cfg: Cohere2MoeConfig, x: Array) -> Array:
    """Final norm, then the EMBEDDING as the head, times ``logit_scale``."""
    x = centred_norm(params["final_norm"], x, cfg.norm_eps)
    logits = jnp.einsum("...d,vd->...v", x, params["embed_tokens"]["embedding"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return logits * cfg.logit_scale


def decode_layer(lp: dict, cfg: Cohere2MoeConfig, i: int, x: Array, step: DecodeStep) -> Array:
    """Layer ``i`` of a decode step on ``x [B, 1, d]``: ``h = LN(x); x +=
    Attn_i(h) + Experts(h)``. A window reaches the attention as ``window=``:
    the Pallas walk starts at its first block, the gather path masks."""
    h = centred_norm(lp["norm"], x, cfg.norm_eps)
    q, k, v = qkv_proj(lp["attn"], cfg, h)
    q, k = qk_rotated(cfg, i, q, k, step.positions)
    window = cfg.window(i)
    attn = step.attend(q, k, v, i, window=window, scope="attn.window" if window else "attn.full")
    # a row that does not advance is routed nowhere: it would touch
    # experts (bytes) for a token nobody reads
    routed, chosen, n = expert_layer(lp["moe"], cfg, h, step.valid)
    x = x + L.dense(lp["attn"]["wo"], attn.reshape(x.shape[0], 1, -1), cfg.jdtype) + routed
    step.note({"experts": chosen}, n)
    return x


ROUTED_DRAFT = "paged speculation does not serve a routed family ({cfg}) yet"
FAMILY = Family(
    name="cohere2_moe", config=Cohere2MoeConfig, init=init_cohere2_moe, forward=cohere2_forward,
    init_cache=init_cache, decode_layer=decode_layer,
    head=lambda params, cfg, x: head_logits(params, cfg, x)[:, 0],
    picks=lambda cfg: {"experts": cfg.experts_per_token}, expert_tiles=expert_tiles,
    refuses={"draft": ROUTED_DRAFT,
             "mesh": "a {cfg} model is one chip's share of each layer (``experts_held`` of its experts): a mesh "
                     "that splits the share again has no rules yet"})
