"""Pallas paged attention (decode): attention over a page-table KV cache.

The continuous-batching engine (runtime/paged.py) stores KV in ONE pool of
fixed-size pages for all layers, ``[L, P, page, Hkv, D]``; at decode each
row attends over its own scattered page list in one layer of it. The XLA
path gathers those pages into a contiguous window first — an HBM round-trip
proportional to the whole window. This kernel instead reads the pool where
it lies:

* the page table, the row lengths and the layer index ride **scalar
  prefetch** (``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index_map
  picks layer AND *physical* page to DMA for grid step (row b, logical
  block i) — ``pool[layer, page_table[b, i]]`` — and only pages the row
  actually owns ever leave HBM. The kernel takes the whole pool: a
  ``pool[layer]`` operand would be materialised by XLA (a custom call
  cannot fuse its operand), a copy of every page of the layer per call.
  The layer is a traced int32, not a constant closed over by the
  index_map, so every layer of a decode program shares one kernel body;
* grid ``(B, NB)`` with the page axis sequential, carrying the classic
  online-softmax (m, l, acc) recurrence in fp32 VMEM scratch;
* GQA stays folded: q is viewed [Hkv, rep, D] and both dots batch over the
  kv-head axis, so pages are never expanded to query heads;
* pages past a row's length are skipped wholesale (``pl.when``), the
  current page masks per-position (key pos ≤ len — the new token's KV was
  scattered at index ``len`` before the call).

Two kernel variants share the grid/recurrence:

* **bf16 pages** (``paged_attention``) — K/V page blocks DMA as-is;
* **int8 pages** (``paged_attention_quant``) — the BlockSpecs DMA int8
  page blocks PLUS their bf16 per-vector scales (stored page-minor,
  ``[L, P, Hkv, page]``, so a scale block is one lane-dense tile and needs no
  in-kernel transpose; Mosaic has no float16 vector type on v5e) through
  the same scalar-prefetch index_map, and dequantization happens
  in-register in VMEM: q·(s·K) folds as (q·K)·s on the kv-head-batched score dot, and
  p·(s·V) as (p·s)·V on the value dot, so quantized pages never
  round-trip through a dense bf16 gather in HBM. Page reads shrink to
  ~half the bytes of bf16 — the point of quantizing a bandwidth-bound
  decode.

Runs in interpret mode on CPU (tests); on TPU it is the decode fast path
once windows are long enough to beat the fused XLA gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)

__all__ = ["paged_attention", "paged_attention_quant", "make_paged_attn_impl"]


def _paged_kernel(
    pt_ref,    # [B, NB] int32 scalar-prefetch — page table
    lens_ref,  # [B] int32 scalar-prefetch — current token index per row
    layer_ref,  # [1] int32 scalar-prefetch — read by the index_maps only
    q_ref,     # [Hkv, rep, D]
    k_ref,     # [page, Hkv, D] — the layer's physical page chosen by index_map
    v_ref,     # [page, Hkv, D]
    o_ref,     # [Hkv, rep, D]
    m_ref,     # [Hkv, rep, 1] fp32 scratch
    l_ref,     # [Hkv, rep, 1] fp32 scratch
    acc_ref,   # [Hkv, rep, D] fp32 scratch
    *,
    page: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    cur = lens_ref[b]  # the new token sits at absolute index ``cur``

    @pl.when(i * page <= cur)
    def _block():
        q = q_ref[:]  # [Hkv, rep, D]
        # [page, Hkv, D] → [Hkv, page, D]: Mosaic's tpu.matmul requires the
        # batch dims of both operands at the SAME index ("batch dims must be
        # equal" compile error on real chips otherwise; interpret mode on CPU
        # accepted the mismatched layout)
        k = k_ref[:].swapaxes(0, 1)
        # s[g, r, p] = q[g, r, :] · k[g, p, :]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * sm_scale  # [Hkv, rep, page]

        pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos <= cur, s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(jnp.where(m_new > NEG_INF / 2, s - m_new, NEG_INF))
        alpha = jnp.exp(jnp.where(m_new > NEG_INF / 2, m_prev - m_new, 0.0))

        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        v = v_ref[:].swapaxes(0, 1)  # [Hkv, page, D], same batch-dim rule
        # acc[g, r, :] += p[g, r, :] @ v[g, :, :]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[:] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _row_block(bb, i, pt, ln, layer):
    return (bb, 0, 0, 0)


def _page_block(bb, i, pt, ln, layer):
    return (layer[0], pt[bb, i], 0, 0, 0)


def _scale_block(bb, i, pt, ln, layer):
    return (layer[0], pt[bb, i], 0, 0)


def _walk_pool(kernel, q, pools, pool_specs, layer, page_table, lens, interpret):
    """The grid both variants share: (row, logical block) over ``pools``
    (whole-pool operands with their BlockSpecs), q [B, H, D] → [B, H, D]."""
    b, h, d = q.shape
    hkv = pools[0].shape[3]
    rep = h // hkv
    row = pl.BlockSpec((None, hkv, rep, d), _row_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, page_table.shape[1]),
        in_specs=[row, *pool_specs],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((hkv, rep, 1), jnp.float32),
            pltpu.VMEM((hkv, rep, 1), jnp.float32),
            pltpu.VMEM((hkv, rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            kernel, page=pools[0].shape[2], sm_scale=1.0 / float(np.sqrt(d))),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32), lens.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        # [B, H, D] → [B, Hkv, rep, D]: group query heads under their kv head
        q.reshape(b, hkv, rep, d), *pools,
    )
    return out.reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: jax.Array,           # [B, H, D] — one decode token per row
    k_pages: jax.Array,     # [L, P, page, Hkv, D] — the page pool, all layers
    v_pages: jax.Array,     # [L, P, page, Hkv, D]
    layer: jax.Array,       # int32 scalar — the layer whose pages are read
    page_table: jax.Array,  # [B, NB] int32 physical page ids
    lens: jax.Array,        # [B] int32 — index of the current token
    *,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over one layer of the paged pool → [B, H, D]."""
    _, _, page, hkv, d = k_pages.shape
    pages = pl.BlockSpec((None, None, page, hkv, d), _page_block)
    return _walk_pool(_paged_kernel, q, (k_pages, v_pages), (pages, pages),
                      layer, page_table, lens, interpret)


def _paged_kernel_quant(
    pt_ref,    # [B, NB] int32 scalar-prefetch — page table
    lens_ref,  # [B] int32 scalar-prefetch — current token index per row
    layer_ref,  # [1] int32 scalar-prefetch — read by the index_maps only
    q_ref,     # [Hkv, rep, D]
    kq_ref,    # [page, Hkv, D] int8 — the layer's physical page chosen by index_map
    ks_ref,    # [Hkv, page] bf16 — per-vector absmax scales for that page
    vq_ref,    # [page, Hkv, D] int8
    vs_ref,    # [Hkv, page] bf16
    o_ref,     # [Hkv, rep, D]
    m_ref,     # [Hkv, rep, 1] fp32 scratch
    l_ref,     # [Hkv, rep, 1] fp32 scratch
    acc_ref,   # [Hkv, rep, D] fp32 scratch
    *,
    page: int,
    sm_scale: float,
):
    """Online-softmax over int8 pages, dequantized in-register.

    The scale never expands to [page, D]: q·(s_p·K_p) == (q·K_p)·s_p per key
    vector, so the score dot runs on the raw int8 block (cast to f32 on the
    VPU) and the scalar scale multiplies the [Hkv, rep, page] score tile.
    Same fold on the value side: p·(s·V) == (p·s)·V."""
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    cur = lens_ref[b]  # the new token sits at absolute index ``cur``

    @pl.when(i * page <= cur)
    def _block():
        q = q_ref[:].astype(jnp.float32)  # [Hkv, rep, D]
        # [page, Hkv, D] → [Hkv, page, D]: batch dims of both matmul
        # operands must sit at the SAME index (see _paged_kernel)
        k = kq_ref[:].astype(jnp.float32).swapaxes(0, 1)   # [Hkv, page, D]
        ks = ks_ref[:].astype(jnp.float32)                 # [Hkv, page]
        # s[g, r, p] = (q[g, r, :] · kq[g, p, :]) * ks[g, p] — the (q·K)·s
        # fold: one scalar multiply per score instead of page*D dequants
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * ks[:, None, :] * sm_scale  # [Hkv, rep, page]

        pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos <= cur, s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(jnp.where(m_new > NEG_INF / 2, s - m_new, NEG_INF))
        alpha = jnp.exp(jnp.where(m_new > NEG_INF / 2, m_prev - m_new, 0.0))

        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=2, keepdims=True)
        v = vq_ref[:].astype(jnp.float32).swapaxes(0, 1)   # [Hkv, page, D]
        vs = vs_ref[:].astype(jnp.float32)                 # [Hkv, page]
        # acc[g, r, :] += (p[g, r, :] * vs[g, :]) @ vq[g, :, :] — the (p·s)·V
        # fold on the value dot
        pv = p * vs[:, None, :]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            pv, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[:] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_quant(
    q: jax.Array,           # [B, H, D] — one decode token per row
    k_pages_q: jax.Array,   # [L, P, page, Hkv, D] int8 — the page pool, all layers
    k_scales: jax.Array,    # [L, P, Hkv, page] bf16 per-vector absmax scales
    v_pages_q: jax.Array,   # [L, P, page, Hkv, D] int8
    v_scales: jax.Array,    # [L, P, Hkv, page] bf16
    layer: jax.Array,       # int32 scalar — the layer whose pages are read
    page_table: jax.Array,  # [B, NB] int32 physical page ids
    lens: jax.Array,        # [B] int32 — index of the current token
    *,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over one layer of the int8-quantized paged pool →
    [B, H, D].

    Same grid/scalar-prefetch walk as :func:`paged_attention`; the int8
    payload and its scale pages DMA per grid step and dequantize in VMEM.
    """
    _, _, page, hkv, d = k_pages_q.shape
    pages = pl.BlockSpec((None, None, page, hkv, d), _page_block)
    scales = pl.BlockSpec((None, None, hkv, page), _scale_block)
    return _walk_pool(
        _paged_kernel_quant, q, (k_pages_q, k_scales, v_pages_q, v_scales),
        (pages, scales, pages, scales), layer, page_table, lens, interpret)


def make_paged_attn_impl(interpret: bool | None = None, mesh=None):
    """Adapter with the ``paged_decode_forward(attn_impl=...)`` signature:
    (q [B,1,H,D], k_pages, v_pages, layer, page_table, lens, n_rep) →
    [B,1,H,D], the pools whole as ``runtime.paged.init_pool`` made them.

    Representation-aware: a plain array routes to the bf16 kernel, a
    ``{"q", "s"}`` pytree (the ``kv_quant="int8"`` pool) routes to the int8
    kernel — so one engine attn seam serves both pool representations.

    With a ``mesh`` the kernel runs INSIDE ``shard_map`` over ``tp``: the
    pool is kv-head-sharded there (``runtime.paged.init_pool``) and query
    heads shard the same way (wq is column-sharded), so each device walks
    the page table over its own heads. A bare ``pallas_call`` under GSPMD
    cannot be partitioned — XLA would gather the whole pool around it.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def impl(q, k_pages, v_pages, layer, page_table, lens, n_rep):
        if isinstance(k_pages, dict):
            out = paged_attention_quant(
                q[:, 0], k_pages["q"], k_pages["s"],
                v_pages["q"], v_pages["s"],
                layer, page_table, lens, interpret=interpret,
            )
        else:
            out = paged_attention(
                q[:, 0], k_pages, v_pages, layer, page_table, lens,
                interpret=interpret,
            )
        return out[:, None]

    if mesh is None:
        return impl

    from jax.sharding import PartitionSpec as P

    from sentio_tpu.parallel.mesh import AXIS_TP

    heads = P(None, None, AXIS_TP, None)         # q/out [B, 1, H, D]
    pages = P(None, None, None, AXIS_TP, None)   # [L, P, page, Hkv, D]
    scales = P(None, None, AXIS_TP, None)        # [L, P, Hkv, page]

    def pool_spec(pool):
        return {"q": pages, "s": scales} if isinstance(pool, dict) else pages

    def sharded_impl(q, k_pages, v_pages, layer, page_table, lens, n_rep):
        return jax.shard_map(
            functools.partial(impl, n_rep=n_rep), mesh=mesh,
            in_specs=(heads, pool_spec(k_pages), pool_spec(v_pages),
                      P(), P(), P()),
            out_specs=heads, check_vma=False,
        )(q, k_pages, v_pages, jnp.asarray(layer, jnp.int32), page_table, lens)

    return sharded_impl
