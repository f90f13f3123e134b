"""Decoder-only LM (Llama-3 family): RMSNorm pre-norm, RoPE, GQA, SwiGLU.

The generator AND verifier of the pipeline — one set of weights serves both
(the reference made two HTTP calls to a hosted model per request:
/root/reference/src/core/llm/providers/openai.py:117, answer_verifier.py:47;
here both are forward passes on the same sharded params).

Pure functions over an explicit param pytree (see models/layers.py). The KV
cache is an explicit pytree threaded through calls, stacked over layers
([L, B, S, Hkv, D]) so one PartitionSpec shards every layer's cache: batch on
``dp``, kv-heads on ``tp``. Static shapes throughout: prefill pads to a
bucket, decode attends over the full cache window under a position mask —
one compiled program per (batch-bucket, cache-bucket).

Tensor-parallel layout is Megatron-style via the path rules in
parallel/sharding.py: wq/wk/wv/w_gate/w_up column-sharded, wo/w_down
row-sharded → two psums per block, inserted by XLA from the shardings.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from sentio_tpu.analysis.audit.registry import jit_family
from sentio_tpu.models import layers as L
from sentio_tpu.models.families import DecodeStep, Family
from sentio_tpu.parallel.sharding import LLAMA_TP_RULES

Array = jax.Array
Cache = dict  # {"k": [L,B,S,Hkv,D], "v": [L,B,S,Hkv,D]}


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14_336
    max_len: int = 8192
    rope_theta: float = 500_000.0
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def jdtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    def window(self, layer: int) -> Optional[int]:
        """Keys a query of ``layer`` sees behind itself, itself included;
        None: all of them (every layer of this family)."""
        return None

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """CPU-test scale; byte-level vocab (ByteTokenizer round-trips)."""
        return cls(
            vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            mlp_dim=128, max_len=512, rope_theta=10_000.0,
        )


def init_llama(rng: Array, cfg: LlamaConfig) -> dict:
    keys = iter(jax.random.split(rng, 2 + cfg.n_layers * 7))
    # attention is ``n_heads * head_dim`` wide, which a family may set apart from ``dim``
    q_dim, kv_dim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    params: dict = {
        "embed_tokens": L.embed_init(next(keys), cfg.vocab_size, cfg.dim),
        "lm_head": L.dense_init(next(keys), cfg.dim, cfg.vocab_size, with_bias=False),
        "final_norm": L.rmsnorm_init(cfg.dim),
    }
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = {
            "attn_norm": L.rmsnorm_init(cfg.dim),
            "attn": {
                "wq": L.dense_init(next(keys), cfg.dim, q_dim, with_bias=False),
                "wk": L.dense_init(next(keys), cfg.dim, kv_dim, with_bias=False),
                "wv": L.dense_init(next(keys), cfg.dim, kv_dim, with_bias=False),
                "wo": L.dense_init(next(keys), q_dim, cfg.dim, with_bias=False),
            },
            "mlp_norm": L.rmsnorm_init(cfg.dim),
            "mlp": {
                "w_gate": L.dense_init(next(keys), cfg.dim, cfg.mlp_dim, with_bias=False),
                "w_up": L.dense_init(next(keys), cfg.dim, cfg.mlp_dim, with_bias=False),
                "w_down": L.dense_init(next(keys), cfg.mlp_dim, cfg.dim, with_bias=False),
            },
        }
    return params


def init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> Cache:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.jdtype), "v": jnp.zeros(shape, cfg.jdtype)}


def _write_cache(cache_layer: Array, kv: Array, index: Array | int) -> Array:
    """Write kv [B,T,H,D] into cache_layer [B,S,H,D] at seq offset ``index``
    (scalar) or per-row offsets (vector [B])."""
    idx = jnp.asarray(index)
    if idx.ndim == 0:
        return jax.lax.dynamic_update_slice(cache_layer, kv, (0, idx, 0, 0))
    return jax.vmap(
        lambda row_cache, row_kv, row_idx: jax.lax.dynamic_update_slice(
            row_cache, row_kv, (row_idx, 0, 0)
        )
    )(cache_layer, kv, idx)


# the three of :func:`qkv_proj`, in its order; then a latent family's query
# up-projection (``models/deepseek_v2.py``), fused with its head reshape and
# RoPE the same way, and a Mamba block's input projection
# (``models/nemotron_h.py``), split three ways behind its matmul
SERVED_AS = {"wq": "wq_t", "wk": "wk_t", "wv": "wv_t", "wq_b": "wq_b_t", "w_in": "w_in_t"}


def serving_layout(params: dict) -> dict:
    """The tree the serving programs read: every layer's ``attn.wq``, ``wk``
    and ``wv`` ([in, out], as a checkpoint holds them) stored ``[out, in]``
    under ``wq_t``, ``wk_t``, ``wv_t``. The TPU compiler fuses each of these
    three matmuls with the head reshape and RoPE that follow and reads its
    weight column-major; a parameter lies row-major, so every call of a
    program that takes the canonical tree begins by transposing them — 0.7-0.8
    GB read and written a call at the benchmark's widths, at the head of every
    decode tick and every prefill dispatch. ``[out, in]`` row-major IS that
    order: the program reads the weight where it lies.

    A layer at a time; the three are left out of the NEW tree, so they die
    with the caller's. A host array is transposed on the host, a device array
    on its devices (a column split over ``tp`` becomes the row split of the
    transposed leaf: ``parallel/sharding.py``). Idempotent: a tree with
    nothing to turn is returned as it is, the same object. Checkpoints, the
    initialisers and ``models/convert.py`` keep the canonical names;
    :func:`qkv_proj` reads either tree. A Mamba block's ``w_in`` is turned
    the same way (``w_in_t``), and a stack of experts whose width is no
    multiple of a tile's 128 lanes is zero-padded to the next
    (``models/moe.py::lane_padded``): both or neither for a family without."""

    def as_read(w):
        if w.ndim < 2:  # a bias: one value an output column either way
            return w
        if isinstance(w, jax.Array):
            return jnp.swapaxes(w, -1, -2)
        # 64 rows at a time: numpy's own transposed copy walks the source by
        # columns, four times slower (0.12 s a [4096, 4096] bf16 matrix)
        turned = np.empty((*w.shape[:-2], w.shape[-1], w.shape[-2]), w.dtype)
        for i in range(0, w.shape[-2], 64):
            turned[..., i:i + 64] = np.swapaxes(w[..., i:i + 64, :], -1, -2)
        return turned

    from sentio_tpu.models.moe import lane_padded

    out = params
    for name, lp in params.items():
        if not isinstance(lp, dict):
            continue
        turned = {}
        # an attention block's three, and a Mamba block's one input projection
        # (``models/nemotron_h.py``: split and reshaped behind its matmul the same way)
        for block in ("attn", "mamba"):
            held = lp.get(block)
            if held and any(k in held for k in SERVED_AS):
                turned[block] = {SERVED_AS.get(k, k): jax.tree.map(as_read, w) if k in SERVED_AS else w
                                 for k, w in held.items()}
        # an expert stack whose width is no multiple of a tile's lanes: padded once, here
        if "moe" in lp:
            padded = lane_padded(lp["moe"])
            if padded is not lp["moe"]:
                turned["moe"] = padded
        if turned:
            if out is params:
                out = dict(params)
            out[name] = {**lp, **turned}
    return out


def qkv_proj(lp: dict, cfg: LlamaConfig, x: Array) -> tuple[Array, Array, Array]:
    """x [B, T, d] → q [B, T, H, D], k and v [B, T, Hkv, D] of one attention
    block, before RoPE — from the ``[out, in]`` leaves of
    :func:`serving_layout` where the tree holds them, else from the canonical
    three. The same bf16 products summed in fp32 over the same terms."""
    dt = cfg.jdtype
    b, t, _ = x.shape
    dense, names = (L.dense_t, SERVED_AS.values()) if "wq_t" in lp else (L.dense, SERVED_AS)
    return tuple(
        dense(lp[name], x, dt).reshape(b, t, heads, cfg.head_dim)
        for name, heads in zip(names, (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads)))


def _attn(
    lp: dict,
    cfg: LlamaConfig,
    x: Array,
    positions: Array,
    cos: Array,
    sin: Array,
    layer: int,
    cache: Optional[Cache],
    cache_index: Array,
    pad_mask: Optional[Array],
    attn_fn=None,
) -> tuple[Array, Optional[Cache]]:
    dt = cfg.jdtype
    b, t, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    q, k, v = qkv_proj(lp, cfg, x)
    q = L.apply_rope(q, positions, cos, sin)
    k = L.apply_rope(k, positions, cos, sin)

    # kernels apply to multi-token causal attention. One that ``takes_prior``
    # (kernels/prefill_attention.py: the engine's choice for its prefill
    # programs) is handed the cache as it lies and each row's first position:
    # grouped by index, causal by position. The others (flash, ring) take
    # keys spread to the query heads and a query block that starts at
    # position 0 (prefill writes at slot 0, training has no cache). Decode
    # (t == 1) is the masked XLA path
    use_kernel = attn_fn is not None and t > 1
    takes_prior = use_kernel and getattr(attn_fn, "takes_prior", False)

    if cache is not None:
        # write this step's k/v into the cache window at cache_index, which is
        # a scalar (aligned prefill) or [B] vector (ragged decode: coalesced
        # sequences of different lengths each write at their own slot)
        k_cache = _write_cache(cache["k"][layer], k.astype(dt), cache_index)
        v_cache = _write_cache(cache["v"][layer], v.astype(dt), cache_index)
        cache["k"] = cache["k"].at[layer].set(k_cache)
        cache["v"] = cache["v"].at[layer].set(v_cache)
        k_full, v_full = k_cache, v_cache
        kv_lens = None  # causal mask already hides the uninitialized tail
    else:
        k_full, v_full = k, v
        # right-padded batches → per-row valid lengths for the kernel
        kv_lens = pad_mask.sum(axis=1).astype(jnp.int32) if pad_mask is not None else None

    if takes_prior:
        # right pads lie past every real query, so position alone hides them
        out = attn_fn(q, k_full, v_full, cache_index if cache is not None else 0).reshape(b, t, h * hd)
        return L.dense(lp["wo"], out, dt), cache

    k_full = L.repeat_kv(k_full, h // hkv)
    v_full = L.repeat_kv(v_full, h // hkv)
    if use_kernel:
        out = attn_fn(q, k_full, v_full, kv_lens).reshape(b, t, h * hd)
    else:
        if cache is not None:
            # query i (absolute pos = positions[:, i]) attends keys j <= pos_i
            kj = jnp.arange(k_full.shape[1])[None, None, None, :]
            mask = kj <= positions[:, None, :, None]  # [B,1,T,S]
        else:
            mask = L.causal_mask(t)
            if pad_mask is not None:
                mask = mask & pad_mask[:, None, None, :]
        out = L.attention(q, k_full, v_full, mask, dt).reshape(b, t, h * hd)
    return L.dense(lp["wo"], out, dt), cache


def _mlp(lp: dict, cfg: LlamaConfig, x: Array) -> Array:
    dt = cfg.jdtype
    gate = jax.nn.silu(L.dense(lp["w_gate"], x, dt))
    up = L.dense(lp["w_up"], x, dt)
    return L.dense(lp["w_down"], gate * up, dt)


def block_forward(
    lp: dict,
    cfg: LlamaConfig,
    x: Array,
    positions: Array,
    cos: Array,
    sin: Array,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
) -> Array:
    """One pre-norm transformer block on activations x [B, T, D] — the
    cache-free (training / scoring) path, factored out so the pipeline-parallel
    executor (parallel/pipeline.py) can scan it over a stage's layer stack."""
    attn_out, _ = _attn(
        lp["attn"], cfg, L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps),
        positions, cos, sin, 0, None, 0, pad_mask, attn_fn,
    )
    x = x + attn_out
    return x + _mlp(lp["mlp"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps))


def stack_layer_params(params: dict, cfg: LlamaConfig) -> dict:
    """Rearrange per-layer subtrees ``layers_i`` into one stacked pytree with
    a leading layer dim: {"embed_tokens", "lm_head", "final_norm", "layers"}
    where every leaf of ``layers`` is [n_layers, ...]. The stacked form is
    what ``lax.scan`` consumes (one compiled block for L layers) and what the
    pipeline executor shards over the ``pp`` mesh axis (leading dim = stage)."""
    per_layer = [params[f"layers_{i}"] for i in range(cfg.n_layers)]
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_layer)
    return {
        "embed_tokens": params["embed_tokens"],
        "lm_head": params["lm_head"],
        "final_norm": params["final_norm"],
        "layers": stacked,
    }


def unstack_layer_params(stacked: dict, cfg: LlamaConfig) -> dict:
    """Inverse of :func:`stack_layer_params`."""
    params = {
        "embed_tokens": stacked["embed_tokens"],
        "lm_head": stacked["lm_head"],
        "final_norm": stacked["final_norm"],
    }
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = jax.tree.map(lambda leaf: leaf[i], stacked["layers"])
    return params


def llama_forward(
    params: dict,
    cfg: LlamaConfig,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
) -> tuple[Array, Optional[Cache]]:
    """ids [B, T] → logits [B, T, vocab] (float32) and the updated cache.

    * Training / scoring: ``cache=None`` → causal attention over T.
    * Prefill: pass a fresh cache, ``positions = arange(T)``, index 0.
    * Decode: T == 1, ``positions = [[cur]]``, ``cache_index = cur``; with a
      ragged batch, ``positions = lens[:, None]`` and ``cache_index = lens``
      ([B] vector) so each row writes/reads at its own offset.
    * ``attn_fn`` (see sentio_tpu.kernels): flash/ring kernel used for the
      multi-token causal paths (training + prefill), or the prefill kernel
      that knows a prior (``takes_prior``); decode stays XLA.
    """
    dt = cfg.jdtype
    b, t = ids.shape
    if cache is not None:
        cache = dict(cache)  # never mutate the caller's pytree
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    rope_len = cache["k"].shape[2] if cache is not None else max(t, cfg.max_len)
    cos, sin = L.rope_frequencies(cfg.head_dim, rope_len, cfg.rope_theta)

    x = L.embed(params["embed_tokens"], ids, dt)
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        if cache is None:
            x = block_forward(lp, cfg, x, positions, cos, sin, pad_mask, attn_fn)
            continue
        attn_out, cache = _attn(
            lp["attn"], cfg, L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps),
            positions, cos, sin, i, cache, cache_index, pad_mask, attn_fn,
        )
        x = x + attn_out
        x = x + _mlp(lp["mlp"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.dense(params["lm_head"], x, dt)
    return logits.astype(jnp.float32), cache


def decode_layer(lp: dict, cfg: LlamaConfig, i: int, x: Array, step: DecodeStep, ffn=None) -> Array:
    """Layer ``i`` of a decode step on ``x [B, 1, d]``: :func:`llama_forward`'s
    block over the pages. ``ffn(lp, cfg, xm, step)``: the second half of a
    family that shares the block (``models/moe.py``); else the dense SwiGLU."""
    dt = cfg.jdtype
    cos, sin = step.tables
    xn = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
    q, k, v = qkv_proj(lp["attn"], cfg, xn)
    q = L.apply_rope(q, step.positions, cos, sin)
    k = L.apply_rope(k, step.positions, cos, sin)
    out = step.attend(q, k, v, i)
    x = x + L.dense(lp["attn"]["wo"], out.reshape(x.shape[0], 1, -1), dt)
    xm = L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps)
    return x + (_mlp(lp["mlp"], cfg, xm) if ffn is None else ffn(lp, cfg, xm, step))


def decode_head(params: dict, cfg: LlamaConfig, x: Array) -> Array:
    """A decode step's logits ``[B, V]`` float32 of ``x [B, 1, d]``."""
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.dense(params["lm_head"], x, cfg.jdtype)[:, 0].astype(jnp.float32)


FAMILY = Family(
    name="llama", config=LlamaConfig, init=init_llama, forward=llama_forward, init_cache=init_cache,
    decode_layer=decode_layer, head=decode_head, mesh_rules=LLAMA_TP_RULES,
    decode_tables=lambda cfg, reach: L.rope_frequencies(cfg.head_dim, max(reach, cfg.max_len), cfg.rope_theta))


@jit_family("llama.loss", static_argnames=("cfg",))
def llama_loss(params: dict, cfg: LlamaConfig, ids: Array, mask: Array) -> Array:
    """Mean next-token cross-entropy over unpadded positions — the training
    objective for fine-tuning and for the multi-chip dry-run train step."""
    logits, _ = llama_forward(params, cfg, ids[:, :-1], pad_mask=mask[:, :-1])
    targets = ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[:, :, None], axis=-1)[..., 0]
    weights = mask[:, 1:].astype(jnp.float32)
    return (nll * weights).sum() / jnp.maximum(weights.sum(), 1.0)
