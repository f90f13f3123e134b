"""Pallas latent attention (decode): the ABSORBED form of multi-head latent
attention over a page-table pool of latents.

The pool of a latent family (``runtime/paged.py::init_pool``) holds ONE
vector a token and layer, ``c_kv | k_pe``, and nothing per head. A page lies
LATENT-MAJOR, ``[L, P, r + rope, page]``: the positions of a page are the
128 lanes of a tile and the 576 numbers of a latent its rows, so a page is
whole tiles as it lies. (Position-major, 576 is four and a half tiles of
lanes: XLA pads every latent to 640 in HBM and the chip's DMA cannot slice
the 576 back out — what the compiler said to this kernel's first form.)

A decode query arrives absorbed (``models/deepseek_v2.py::absorb_query``):
``q_lat [H, r]`` meets the latent itself, ``q_pe [H, rope]`` the rotated key
beside it, and the VALUE of a position is the first ``r`` numbers of its key:

    s[h, t] = q_lat[h] . c_kv[t] + q_pe[h] . k_pe[t]
    o_lat[h] = sum_t softmax(s)[h, t] c_kv[t]

so one copy of a page serves the scores and the output (``q . page`` as it
lies, ``p . page^T`` for the output), and every one of the H heads reads the
same ``(r + rope) x page`` matrix: per position and layer 1,152 B are moved
for ``H x (r + rope + r) x 2`` operations — at 128 heads 241 operations a
byte, the v5e's ridge, where the grouped-query walk
(``kernels/paged_attention.py``) is a few operations a byte.

The walk is that kernel's: the pool whole in HBM (``memory_space=pl.ANY``),
the page table, the row lengths and the layer as scalar prefetch, grid
``(B,)``, inside row ``b`` a loop over the blocks the row HOLDS
(``blocks_walked``), each ONE copy by the kernel's own DMA into a ring of
VMEM buffers that runs across rows, and one step of the online softmax in
float32 scratch. There is no second pool, no kv head to mask and no window.
Runs in interpret mode on the CPU (tests); on a TPU it is the decode path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sentio_tpu.kernels.paged_attention import (
    NEG_INF, _PAGE_BUFFER_BYTES, _PAGES_IN_FLIGHT, _packs, _vmem_bytes)

__all__ = ["latent_attention", "make_latent_attn_impl", "latent_untiled"]


def latent_untiled(page: int, rank: int, rope: int) -> str | None:
    """Why the chip's DMA cannot bring the pages of this geometry, or None
    where it can: a page is the matrix ``[rank + rope, page]``, copied whole;
    its positions must fill the lanes of a tile, and the latent and the
    rotated key beside it whole 16-row tiles of bf16, so that both are plain
    slices of the copy."""
    rows = 8 * _packs(jnp.bfloat16)
    if page % 128:
        return f"a page of {page} positions does not fill the 128 lanes of a tile"
    if rank % rows or rope % rows:
        return f"a latent of {rank} + {rope} is not whole {rows}-row tiles of bf16"
    return None


def _latent_kernel(
    pt_ref,     # [B, NB] int32 scalar-prefetch — page table
    lens_ref,   # [B] int32 scalar-prefetch — current token index per row
    layer_ref,  # [1] int32 scalar-prefetch — the layer whose pages are read
    ql_ref,     # [H, r] — row b's absorbed queries
    qp_ref,     # [H, rope] — row b's rotated queries
    pool,       # [L, P, r + rope, page] in HBM
    o_ref,      # [H, r]
    m_ref, l_ref, acc_ref, buf, sems, walk,
    *, page: int, rank: int, depth: int, sm_scale: float,
):
    """Row ``b``: one loop step a block the row holds (the module docstring;
    the ring and its pointer are ``kernels/paged_attention.py::_walk_kernel``'s)."""
    b, rows = pl.program_id(0), pl.num_programs(0)
    nb = pt_ref.shape[1]
    layer = layer_ref[0]

    def end(row):
        return jnp.clip(lens_ref[row] // page, 0, nb - 1) + 1

    def copy(row, j, slot):
        return pltpu.make_async_copy(pool.at[layer, pt_ref[row, j]], buf.at[slot], sems.at[slot])

    def fetch_next():
        row, j, issued = walk[0], walk[1], walk[2]

        @pl.when(row < rows)
        def _():
            copy(row, j, issued % depth).start()
            last = j + 1 >= end(row)
            walk[0] = jnp.where(last, row + 1, row)
            walk[1] = jnp.where(last, 0, j + 1)
            walk[2] = issued + 1

    @pl.when(b == 0)
    def _first_row():
        for n in range(4):
            walk[n] = 0
        jax.lax.fori_loop(0, depth - 1, lambda _, c: (fetch_next(), c)[1], 0)

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    cur = lens_ref[b]  # the new token sits at absolute index ``cur``
    q_lat, q_pe = ql_ref[:], qp_ref[:]
    h = q_lat.shape[0]

    def page_step(j, carry):
        # the slot this fetch fills is the one the previous step computed on
        fetch_next()
        slot = walk[3] % depth
        copy(b, j, slot).wait()
        c_kv = buf[slot, :rank, :]                 # [r, page]: keys AND values
        k_pe = buf[slot, rank:, :]                 # [rope, page]
        s = (jnp.dot(q_lat, c_kv, preferred_element_type=jnp.float32)
             + jnp.dot(q_pe, k_pe, preferred_element_type=jnp.float32))
        pos = jax.lax.broadcasted_iota(jnp.int32, (h, page), 1) + j * page
        s = jnp.where(pos <= cur, s * sm_scale, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # o_lat[h, :] += p[h, :] . c_kv^T — the page as it lies, no relayout
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(c_kv.dtype), c_kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        walk[3] = walk[3] + 1
        return carry

    jax.lax.fori_loop(0, end(b), page_step, 0)
    o_ref[:] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def latent_attention(
    q_lat: jax.Array,       # [B, H, r] — one absorbed decode query per row and head
    q_pe: jax.Array,        # [B, H, rope] — its rotated part
    pages: jax.Array,       # [L, P, r + rope, page] — the latent pool, all layers
    layer: jax.Array,       # int32 scalar — the layer whose pages are read
    page_table: jax.Array,  # [B, NB] int32 physical page ids
    lens: jax.Array,        # [B] int32 — index of the current token
    *,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Absorbed decode attention over one layer of the latent pool → o_lat
    [B, H, r] (``models/deepseek_v2.py::unabsorb`` turns it into values)."""
    b, h, rank = q_lat.shape
    rope = q_pe.shape[-1]
    layers, num_pages, width, page = pages.shape
    assert width == rank + rope, (pages.shape, rank, rope)
    if not interpret:  # the interpreter has no tiles; the chip's DMA has
        why = latent_untiled(page, rank, rope)
        if why:
            raise ValueError(f"latent attention on this device: {why}")
    page_bytes = _vmem_bytes((width, page), pages.dtype)
    depth = int(max(2, min(_PAGES_IN_FLIGHT, _PAGE_BUFFER_BYTES // page_bytes)))

    def row(last):
        return pl.BlockSpec((None, h, last), lambda bb, *_: (bb, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row(rank), row(rope), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row(rank),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, rank), jnp.float32),
            pltpu.VMEM((depth, width, page), pages.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, page=page, rank=rank, depth=depth, sm_scale=float(sm_scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q_lat.dtype),
        # rows in order: the ring of page buffers runs across them
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_lat, q_pe, pages)


def make_latent_attn_impl(interpret: bool | None = None):
    """Adapter with the signature ``runtime/paged.py::_latent_attn_xla`` has:
    (q_lat [B, H, r], q_pe [B, H, rope], pages, layer, page_table, lens,
    sm_scale) → o_lat [B, H, r]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def impl(q_lat, q_pe, pages, layer, page_table, lens, sm_scale):
        return latent_attention(q_lat, q_pe, pages, layer, page_table, lens,
                                sm_scale=float(sm_scale), interpret=interpret)

    return impl
