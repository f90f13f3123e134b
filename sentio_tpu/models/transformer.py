"""Bidirectional transformer encoder (BERT/XLM-R family).

Backbone for both the bi-encoder embedder (replacing the reference's remote
Jina embeddings API, /root/reference/src/core/embeddings/providers/jina.py:33)
and the cross-encoder reranker (replacing api.jina.ai/v1/rerank,
jina_reranker.py:120-154). Post-LN residual blocks with learned positions and
token-type embeddings so weights of the public BERT/XLM-R/bge checkpoint
family convert directly (see models/convert.py).

Pure functions over an explicit param pytree; see models/layers.py for the
rationale. All shapes static; mask handles padding, so one compiled program
per (batch-bucket, seq-bucket).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from sentio_tpu.models import layers as L

Array = jax.Array


@dataclass(frozen=True)
class EncoderConfig:
    vocab_size: int = 32_000
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    n_types: int = 2
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def jdtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    @classmethod
    def tiny(cls) -> "EncoderConfig":
        """CPU-test scale (the deterministic 'fake backend' of SURVEY.md §4,
        but a real model with random weights rather than a mock)."""
        return cls(vocab_size=512, dim=64, n_layers=2, n_heads=2, mlp_dim=128, max_len=128)

    @classmethod
    def base(cls) -> "EncoderConfig":
        return cls(vocab_size=250_002, dim=1024, n_layers=24, n_heads=16, mlp_dim=4096, max_len=8192)


def init_encoder(rng: Array, cfg: EncoderConfig) -> dict:
    keys = iter(jax.random.split(rng, 4 + cfg.n_layers * 6))
    params: dict = {
        "embed_tokens": L.embed_init(next(keys), cfg.vocab_size, cfg.dim),
        "embed_positions": L.embed_init(next(keys), cfg.max_len, cfg.dim),
        "embed_types": L.embed_init(next(keys), cfg.n_types, cfg.dim),
        "embed_norm": L.layernorm_init(cfg.dim),
    }
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = {
            "attn": {
                "wq": L.dense_init(next(keys), cfg.dim, cfg.dim),
                "wk": L.dense_init(next(keys), cfg.dim, cfg.dim),
                "wv": L.dense_init(next(keys), cfg.dim, cfg.dim),
                "wo": L.dense_init(next(keys), cfg.dim, cfg.dim),
            },
            "attn_norm": L.layernorm_init(cfg.dim),
            "mlp": {
                "w_in": L.dense_init(next(keys), cfg.dim, cfg.mlp_dim),
                "w_out": L.dense_init(next(keys), cfg.mlp_dim, cfg.dim),
            },
            "mlp_norm": L.layernorm_init(cfg.dim),
        }
    return params


# subtrees the forwards use in float32 whatever ``cfg.dtype`` says: a LayerNorm
# (found by its ``scale``) and the cross-encoder's pooler and scalar head
_FLOAT32_PARTS = ("pooler", "head")


def serving_dtypes(params: dict, cfg: EncoderConfig, owned: bool = False) -> tuple[dict, int, int]:
    """→ (the tree a serving class holds, leaves cast, bytes given back).

    Every leaf in the dtype the forward USES it in — ``cfg.jdtype`` for the
    embedding tables and the layers' ``kernel`` / ``bias``, float32 for the
    norms, the pooler and the head — so that no program converts a weight at
    use: ``astype`` of the dtype held emits nothing, and a cast at use is a
    cast on EVERY call (a float32 token table was converted whole, 1.0 GB
    read and 0.5 written, to look up one query's rows). The values are the
    ones the forward computed with before. Whatever the weights came from:
    ``init_encoder`` / ``init_cross_encoder`` (float32), a ``cli convert``
    checkpoint, the benchmark's bf16 one. Training keeps float32 masters and
    never comes here (eval/train_encoder.py).

    Cast leaf by leaf, the largest first, each wide leaf let go before the
    next is made: beside the float32 tree the transient is one leaf in the
    narrow dtype, which is what a forward held before. ``owned`` says the
    caller made the tree and keeps no other reference, so its containers are
    reused; a tree someone else holds is left as it was."""
    if not owned:
        params = jax.tree_util.tree_map(lambda leaf: leaf, params)  # fresh containers
    float32 = jnp.dtype(jnp.float32)
    todo: list[tuple[int, dict, str, jnp.dtype]] = []

    def walk(node: dict, want: jnp.dtype) -> None:
        if "scale" in node:
            want = float32
        for key, child in node.items():
            if isinstance(child, dict):
                walk(child, float32 if key in _FLOAT32_PARTS else want)
            elif jnp.issubdtype(child.dtype, jnp.floating) and child.dtype != want:
                todo.append((child.nbytes, node, key, want))

    walk(params, cfg.jdtype)
    given_back = 0
    for nbytes, node, key, want in sorted(todo, key=lambda item: -item[0]):
        node[key] = jax.block_until_ready(node[key].astype(want))
        given_back += nbytes - node[key].nbytes
    return params, len(todo), given_back


def param_summary(params: dict) -> tuple[str, int]:
    """→ (the dtype most of the tree's bytes are held in, its bytes)."""
    held: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(params):
        held[str(leaf.dtype)] = held.get(str(leaf.dtype), 0) + leaf.nbytes
    return max(held, key=held.get), sum(held.values())


def encoder_forward(
    params: dict,
    cfg: EncoderConfig,
    ids: Array,
    mask: Array,
    type_ids: Optional[Array] = None,
    attn_fn=None,
) -> Array:
    """ids/mask: [B, T] (mask True = real token). Returns hidden [B, T, D].
    ``attn_fn`` (see sentio_tpu.kernels.encoder_attn_fn): bidirectional
    flash kernel taking (q, k, v, kv_lens); right-padded masks reduce to
    per-row lengths, so kernels see lengths instead of a [B,T] mask."""
    dt = cfg.jdtype
    b, t = ids.shape
    positions = jnp.arange(t)[None, :]
    x = (
        L.embed(params["embed_tokens"], ids, dt)
        + L.embed(params["embed_positions"], positions, dt)
    )
    if type_ids is not None:
        x = x + L.embed(params["embed_types"], type_ids, dt)
    x = L.layernorm(params["embed_norm"], x)

    attn_mask = (mask[:, None, None, :]).astype(bool)  # [B,1,1,T] keys masked
    kv_lens = mask.astype(jnp.int32).sum(axis=1) if attn_fn is not None else None
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        x = _block(lp, cfg, x, attn_mask, attn_fn, kv_lens)
    return x


def _block(lp: dict, cfg: EncoderConfig, x: Array, attn_mask: Array,
           attn_fn=None, kv_lens: Optional[Array] = None) -> Array:
    dt = cfg.jdtype
    b, t, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim

    q = L.dense(lp["attn"]["wq"], x, dt).reshape(b, t, h, hd)
    k = L.dense(lp["attn"]["wk"], x, dt).reshape(b, t, h, hd)
    v = L.dense(lp["attn"]["wv"], x, dt).reshape(b, t, h, hd)
    if attn_fn is not None:
        attn_out = attn_fn(q, k, v, kv_lens).reshape(b, t, d)
    else:
        attn_out = L.attention(q, k, v, attn_mask, dt).reshape(b, t, d)
    x = L.layernorm(lp["attn_norm"], x + L.dense(lp["attn"]["wo"], attn_out, dt))

    mlp = L.dense(lp["mlp"]["w_out"], jax.nn.gelu(L.dense(lp["mlp"]["w_in"], x, dt)), dt)
    return L.layernorm(lp["mlp_norm"], x + mlp)


def mean_pool(hidden: Array, mask: Array) -> Array:
    """Masked mean over tokens → L2-normalized embedding [B, D], float32."""
    m = mask.astype(jnp.float32)[:, :, None]
    summed = (hidden.astype(jnp.float32) * m).sum(axis=1)
    counts = jnp.maximum(m.sum(axis=1), 1.0)
    pooled = summed / counts
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def cls_pool(hidden: Array) -> Array:
    """First-token representation [B, D] (cross-encoder head input)."""
    return hidden[:, 0, :].astype(jnp.float32)
