"""Decoder family ``mellum`` (Mellum2-12B-A2.5B): a SEQUENTIAL pre-norm block
under RMSNorm — grouped-query attention, then routed experts on the stream the
attention left — whose layers are of two kinds inside one model: a sliding
window beside full attention, three to one, each kind with its OWN rotary.

    u = RMSNorm(x);  x ← x + Attn_i(u) W_o
    h = RMSNorm(x);  x ← x + Σ_{e in top-8} g_e F_e(h)          no shared expert
    logits = RMSNorm(x) W_head                                  untied from the table

Rotary is the rotate-half form over the whole head on EVERY layer; what differs
by kind is the frequencies AND the amplitude. A ``sliding_attention`` layer
turns pair ``j`` by ``pos · theta^(-2j/D)``; a ``full_attention`` layer by
YaRN's frequencies (:func:`yarn_inv_freq`: the fast dimensions as trained, the
slow ones over ``rope_factor``, a linear ramp between) with cos and sin each
TIMES ``rope_attention_factor``, so that a full layer's scores carry its square.
Both are computed at the positions asked (:func:`rope_by_kind`): no table of
``max_len`` positions is folded into a program.

The router scores all ``n_experts`` by a float32 softmax, a token takes its
``experts_per_token`` best and their scores renormalised over the picks are the
gates: all of it cases ``models/moe.py::expert_layer`` decides from the config.
A process holds ``experts_held`` experts from ``expert_offset`` as in the other
routed families; the deployment the benchmark states holds every expert of a
layer on a chip, and then the expert layer IS the layer.

Pure functions over an explicit parameter tree; ``models/llama.py``'s
``qkv_proj`` / ``serving_layout`` (projections go to ``n_heads · head_dim``
columns whatever ``dim`` is) and ``models/cohere2_moe.py``'s attention by
positions under a window are shared, not copied.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sentio_tpu.models import layers as L
from sentio_tpu.models.cohere2_moe import FULL, ROUTED_DRAFT, SLIDING, windowed_attention
from sentio_tpu.models.families import DecodeStep, Family
from sentio_tpu.models.llama import Cache, LlamaConfig, _write_cache, init_cache, qkv_proj
from sentio_tpu.models.moe import expert_layer, expert_tiles

Array = jax.Array

# Seeded weights (tests, the fake-model mode, the benchmark's checkpoints). The
# head is untied, so a token's own row does not vote for itself and the table
# keeps its usual size. Attention is drawn PEAKED as ``models/cohere2_moe.py``
# draws it and for its reason: with queries of unit size a softmax over a
# thousand keys is nearly flat, the output is the context's average, and neither
# a window nor another rotary changes an average much — a reference check would
# be blind to both. The query projection is drawn four times as large and the
# output projection 0.3 times, so that a layer's attention moves with the keys
# it may see and does not drown the stream.
WQ_SCALE = 4.0
WO_SCALE = 0.3


@dataclass(frozen=True)
class MellumConfig(LlamaConfig):
    """``dim``: hidden size; ``mlp_dim``: the width of ONE routed expert (the
    published ``moe_intermediate_size``; its ``intermediate_size`` sizes
    nothing: every layer is routed); ``max_len`` the positions the model
    declares (nothing is sized by it)."""

    vocab_size: int = 98_304
    dim: int = 2304
    n_layers: int = 28
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128                 # a FIELD: attention is n_heads * head_dim = 4,096 wide, not dim
    mlp_dim: int = 896
    max_len: int = 131_072
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-6
    norm_kind: str = "rmsnorm"
    parallel_block: bool = False
    # one kind a layer, comma-joined (a string reads the same in a checkpoint's
    # JSON meta, in ``/info`` and here)
    layer_kinds: str = ",".join((SLIDING, SLIDING, SLIDING, FULL) * 7)
    sliding_window: int = 1024
    rope_kind: str = "rotate_half"
    # the FULL layers' rotary (YaRN); the sliding layers keep ``rope_theta`` plain
    rope_factor: float = 16.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 0.1 * math.log(16.0) + 1.0
    tie_embeddings: bool = False
    n_experts: int = 64                 # the router's width
    experts_per_token: int = 8
    gate_fn: str = "softmax"
    norm_topk_prob: bool = True
    experts_held: int = 64              # the slice of the routed experts held here ...
    expert_offset: int = 0              # ... starting at this expert
    # how ``models/moe.py::expert_layer`` picks and weighs, as this family
    # always does: over all experts at once, gates not scaled, no shared expert.
    # Constants of the family and no fields
    n_group: ClassVar[int] = 1
    topk_group: ClassVar[int] = 1
    routed_scaling_factor: ClassVar[float] = 1.0
    n_shared_experts: ClassVar[int] = 0

    def __post_init__(self):
        # what this family IS, reported by /info like every field; another value is another family
        stated = (self.norm_kind, self.parallel_block, self.rope_kind, self.tie_embeddings)
        if stated != ("rmsnorm", False, "rotate_half", False):
            raise ValueError(f"norm_kind, parallel_block, rope_kind, tie_embeddings = {stated}: this "
                             "family is a sequential block under an RMSNorm, rotate-half rotary, an untied head")
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

    @property
    def layer_types(self) -> list[str]:
        """The kinds as a published ``config.json`` lists them."""
        return list(self.kinds)

    @property
    def mlp_layer_types(self) -> list[str]:
        """Every layer's feed-forward is the routed one (the published list is ``sparse`` throughout)."""
        return ["sparse"] * self.n_layers

    def window(self, layer: int) -> Optional[int]:
        """Keys a query of ``layer`` sees behind itself, itself included."""
        return self.sliding_window if self.kinds[layer] == SLIDING else None

    @classmethod
    def tiny(cls, **over) -> "MellumConfig":
        """CPU-test scale: every mechanism, a window and an original length a
        test's sequence outgrows."""
        return cls(**{**dict(
            vocab_size=512, dim=64, n_layers=2, n_heads=8, n_kv_heads=2, head_dim=16,
            mlp_dim=32, max_len=512, rope_theta=10_000.0, layer_kinds=f"{SLIDING},{FULL}",
            sliding_window=24, rope_factor=4.0, rope_original_max=32,
            rope_attention_factor=0.1 * math.log(4.0) + 1.0, n_experts=16, experts_per_token=4,
            experts_held=16, expert_offset=0), **over})


def init_mellum(rng: Array, cfg: MellumConfig) -> dict:
    """Seeded float32 tree: ``init_llama``'s names with ``moe`` for ``mlp``."""
    keys = iter(jax.random.split(rng, 2 + cfg.n_layers * 8))
    q_dim, kv_dim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def dense(n_in, n_out, scale=1.0):
        return {"kernel": L.dense_init(next(keys), n_in, n_out, with_bias=False)["kernel"] * scale}

    def stack(n_in, n_out):
        return jnp.stack([L.dense_init(k, n_in, n_out, with_bias=False)["kernel"]
                          for k in jax.random.split(next(keys), cfg.experts_held)])

    params: dict = {"embed_tokens": L.embed_init(next(keys), cfg.vocab_size, cfg.dim),
                    "lm_head": dense(cfg.dim, cfg.vocab_size),
                    "final_norm": L.rmsnorm_init(cfg.dim)}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = {
            "attn_norm": L.rmsnorm_init(cfg.dim),
            "attn": {"wq": dense(cfg.dim, q_dim, WQ_SCALE), "wk": dense(cfg.dim, kv_dim),
                     "wv": dense(cfg.dim, kv_dim), "wo": dense(q_dim, cfg.dim, WO_SCALE)},
            "mlp_norm": L.rmsnorm_init(cfg.dim),
            "moe": {"router": dense(cfg.dim, cfg.n_experts),
                    "w_gate": stack(cfg.dim, cfg.mlp_dim), "w_up": stack(cfg.dim, cfg.mlp_dim),
                    "w_down": stack(cfg.mlp_dim, cfg.dim)},
        }
    return params


def plain_inv_freq(cfg: MellumConfig) -> np.ndarray:
    """``[head_dim / 2]`` float64: ``theta^(-2j/D)``, the sliding layers' frequencies."""
    d = cfg.head_dim
    return 1.0 / (cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d))


def yarn_inv_freq(cfg: MellumConfig) -> np.ndarray:
    """``[head_dim / 2]`` float32, the FULL layers' frequencies: ``theta^(-2j/D)``
    below the ramp (the dimensions that turn over ``rope_beta_fast`` times in
    the original length: left as trained), the same over ``rope_factor`` above
    it (those that turn under ``rope_beta_slow`` times: interpolated), a linear
    blend between; the ramp's ends rounded outwards to whole dimensions."""
    d, plain = cfg.head_dim, plain_inv_freq(cfg)

    def dimension(turns: float) -> float:
        return d * math.log(cfg.rope_original_max / (turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(dimension(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(dimension(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / cfg.rope_factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def rope_by_kind(cfg: MellumConfig, kind: str, positions: Array) -> tuple[Array, Array]:
    """cos and sin ``[B, T, head_dim / 2]`` float32 of a layer of ``kind`` at
    ``positions [B, T]``: the plain frequencies for a sliding layer, YaRN's
    with both times ``rope_attention_factor`` for a full one."""
    if kind == FULL:
        inv_freq, scale, scope = yarn_inv_freq(cfg), cfg.rope_attention_factor, "rope.yarn"
    else:
        inv_freq, scale, scope = plain_inv_freq(cfg).astype(np.float32), 1.0, "rope.default"
    with jax.named_scope(scope):
        angle = positions.astype(jnp.float32)[..., None] * inv_freq
        return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def rotate_half(x: Array, cos: Array, sin: Array) -> Array:
    """x [B, T, H, D] with its halves ``(x1, x2)`` turned to ``(x1 c - x2 s,
    x2 c + x1 s)``, cos and sin ``[B, T, D / 2]`` (a factor they carry scales x)."""
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def decode_tables(cfg: MellumConfig, reach: int) -> dict:
    """What a decode step's layers share: cos and sin by kind, each made when
    the first layer of its kind asks (:func:`decode_layer`) — once a step."""
    return {}


def mellum_forward(
    params: dict,
    cfg: MellumConfig,
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
    knows a window) attends wherever there is more than one query; any other
    knows no window and falls to ``cohere2_moe.windowed_attention``."""
    if not getattr(attn_fn, "takes_prior", False):
        attn_fn = None
    dt = cfg.jdtype
    b, t = ids.shape
    if cache is not None:
        cache = dict(cache)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    rope = {kind: rope_by_kind(cfg, kind, positions) for kind in sorted(set(cfg.kinds))}

    x = L.embed(params["embed_tokens"], ids, dt)
    picks, counts = [], jnp.zeros((4,), jnp.int32)
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        q, k, v = qkv_proj(lp["attn"], cfg, L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps))
        q, k = (rotate_half(a, *rope[cfg.kinds[i]]) for a in (q, k))
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
        x = x + L.dense(lp["attn"]["wo"], attn, dt)
        routed, chosen, n = expert_layer(lp["moe"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps), pad_mask)
        x = x + routed
        picks.append(chosen)
        counts = counts + n
    return head_logits(params, cfg, x), cache, {"experts": jnp.stack(picks), "counts": counts}


def head_logits(params: dict, cfg: MellumConfig, x: Array) -> Array:
    """Final RMSNorm, then the head's own matrix; float32."""
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.dense(params["lm_head"], x, cfg.jdtype).astype(jnp.float32)


def decode_layer(lp: dict, cfg: MellumConfig, i: int, x: Array, step: DecodeStep) -> Array:
    """Layer ``i`` of a decode step on ``x [B, 1, d]``: attention over the pages
    under the layer's window and rotary, then the experts on the stream it
    left. A window reaches the attention as ``window=``: the Pallas walk starts
    at its first block, the gather path masks."""
    kind, window = cfg.kinds[i], cfg.window(i)
    if kind not in step.tables:
        step.tables[kind] = rope_by_kind(cfg, kind, step.positions)
    q, k, v = qkv_proj(lp["attn"], cfg, L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps))
    q, k = (rotate_half(a, *step.tables[kind]) for a in (q, k))
    attn = step.attend(q, k, v, i, window=window, scope="attn.window" if window else "attn.full")
    x = x + L.dense(lp["attn"]["wo"], attn.reshape(x.shape[0], 1, -1), cfg.jdtype)
    # a row that does not advance is routed nowhere: it would touch
    # experts (bytes) for a token nobody reads
    routed, chosen, n = expert_layer(lp["moe"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps), step.valid)
    step.note({"experts": chosen}, n)
    return x + routed


FAMILY = Family(
    name="mellum", config=MellumConfig, init=init_mellum, forward=mellum_forward,
    init_cache=init_cache, decode_layer=decode_layer, decode_tables=decode_tables,
    head=lambda params, cfg, x: head_logits(params, cfg, x)[:, 0],
    picks=lambda cfg: {"experts": cfg.experts_per_token}, expert_tiles=expert_tiles,
    refuses={"draft": ROUTED_DRAFT,
             "mesh": "a {cfg} model holds every expert of a layer on one chip and its layers lie on a pipeline of "
                     "chips: a mesh that splits a layer has no rules yet"})
