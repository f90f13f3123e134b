"""Pallas page write (decode): one token's K and V rows into the pool, in place.

Every decode sub-step writes each row's new key and value vectors into its
current page before the attention walk reads the pool. As an XLA scatter that
write is the compiler's to place: where a pool is small enough for the chip's
nearer memory (tens of megabytes: keys and values in one layer of four, or
two of fourteen) the v5e compiler moves the WHOLE pool there for the scatter
and back for the walk, every sub-step — 170 MB moved to write 64 KB
(PERF.md §5, PR 42). This kernel takes the write out of the compiler's hands:

* the pools are ALIASED to the outputs (``input_output_aliases``), and the
  outputs are declared to live in HBM (``pltpu.HBM(shape, dtype)``: the
  custom call then carries that memory space for both ends of the alias). The
  call makes nothing, no XLA operation touches the pool between the write and
  the walk, which reads the pool from HBM by its own DMA too, and the
  scheduler has nothing to place. (``pl.ANY`` alone is not enough: the v5e
  compiler then moves one pool into nearer memory for the call and back,
  both when the call states its cost — tests/test_chip_compile.py);
* the layer index, the rows' page ids and their offsets ride **scalar
  prefetch**, as the walk's table does; the layer is a traced int32, so every
  layer of a decode program shares one kernel body;
* row ``b``'s vectors ``[Hkv, D]`` go to ``pool[layer, page_ids[b],
  offsets[b]]`` by a DMA of their own, K and V of all rows started together
  and waited for together. A position is an index of an UNTILED axis of the
  pool ``[L, P, page, Hkv, D]`` (its tile covers the last two), so a
  position's vectors are whole tiles of the array as XLA lays it out — the
  same bytes the walk reads as ``[page * Hkv, D]`` matrices — and the DMA
  writes them alone: nothing is read, merged and written back.

Rows of different slots never share a page. Rows that do not advance all name
the scratch page 0, position 0: their writes land on each other in no order,
on a position whose content nobody uses.

Which pools take this path is :func:`page_write_path`'s to say, from the
pool's shape, dtype and placement alone. Runs in interpret mode on the CPU
(tests); on a TPU it is what the engine binds where the rule says so.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["page_write", "page_write_path", "make_page_write_impl", "NEARER_MEMORY_BYTES"]

# what the v5e compiler may place an array in beside HBM (``S(1)`` in the
# compiled text): an array of K or V under it is one the compiler moves there
# for an XLA scatter and back, a larger one is left where it lies
NEARER_MEMORY_BYTES = 128 * 1024 * 1024


def page_write_path(pool, mesh=None) -> str:
    """``"pallas"`` where a decode step writes ``pool`` (one array of K or V as
    ``runtime/paged.py::init_pool`` made it) through :func:`page_write`,
    ``"xla"`` where ``_page_write``'s scatter stays: a static fact of the
    operand (an array or its ``ShapeDtypeStruct``). The kernel takes a plain
    bf16 pool on one device that the compiler could place in nearer memory; an
    int8 ``{"q", "s"}`` pool, a pool under a mesh, a latent pool (four dims:
    it has its own write), a pool of ONE row a position (Mosaic refuses the
    half-sublane slice) and every pool too large to be moved keep the scatter,
    whose program is unchanged."""
    if isinstance(pool, dict) or mesh is not None or len(pool.shape) != 5 or pool.dtype != jnp.bfloat16:
        return "xla"
    # a position's vectors have to be whole tiles for the DMA to write them
    # alone: whole 32-bit sublanes (two rows of bf16) of whole 128-lane rows
    if pool.shape[-2] % 2 or pool.shape[-1] % 128:
        return "xla"
    return "pallas" if 2 * math.prod(pool.shape) < NEARER_MEMORY_BYTES else "xla"


def _write_kernel(ids_ref, offsets_ref, layer_ref, *refs, pools: int):
    """All rows' vectors into all ``pools``: one DMA a row a pool, HBM to HBM,
    every one started before the first is waited for."""
    vals, outs, sems = refs[:pools], refs[2 * pools:3 * pools], refs[3 * pools]
    layer = layer_ref[0]
    copies = [pltpu.make_async_copy(val.at[b], out.at[layer, ids_ref[b], offsets_ref[b]], sems.at[n])
              for n, (val, out) in enumerate(zip(vals, outs)) for b in range(val.shape[0])]
    for copy in copies:
        copy.start()
    for copy in copies:
        copy.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_write(pools, layer, page_ids, offsets, vals, *, interpret: bool = False):
    """Write ``vals[n][b]`` ``[Hkv, D]`` at ``pools[n][layer, page_ids[b],
    offsets[b]]`` for every row ``b`` of every pool ``n`` (K and V: one call
    for both) → the pools, updated in place. ``pools`` ``[L, P, page, Hkv, D]``
    each, ``vals`` ``[B, Hkv, D]`` each with the POOL's last two axes (a
    lane-packed pool's ``[Hkv / pack, D * pack]``), ``layer`` an int32 scalar,
    ``page_ids`` and ``offsets`` ``[B]`` int32."""
    pools, vals = tuple(pools), tuple(vals)
    for pool, val in zip(pools, vals):
        if val.shape[1:] != pool.shape[-2:] or val.dtype != pool.dtype:
            raise ValueError(f"page write: rows of {val.dtype}{list(val.shape[1:])} into a pool of "
                             f"{pool.dtype}{list(pool.shape[-2:])} positions")
    n = len(pools)
    return pl.pallas_call(
        functools.partial(_write_kernel, pools=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n + [pl.BlockSpec(memory_space=pltpu.HBM)] * n,
            out_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * n,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n,))],
        ),
        # DECLARED in HBM, and the aliased operands with them: left to choose
        # (``pl.ANY`` and a plain shape) the compiler places a pool in nearer
        # memory for THIS call as it did for the scatter
        out_shape=[pltpu.HBM(pool.shape, pool.dtype) for pool in pools],
        # operands count from the scalars: ids, offsets, layer, the vals, the pools
        input_output_aliases={3 + n + i: i for i in range(n)},
        name="page_write",
        interpret=interpret,
    )(
        page_ids.astype(jnp.int32), offsets.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), *vals, *pools,
    )


def make_page_write_impl(interpret: bool | None = None):
    """Adapter with the ``paged_decode_forward(write_impl=...)`` signature:
    (k_pages, v_pages, layer, page_ids, offsets, k [B, Hkv, D], v) → (k_pages,
    v_pages). The update takes the pool's last two axes, never the pool the
    update's."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def impl(k_pages, v_pages, layer, page_ids, offsets, k, v):
        rows = [val.reshape(val.shape[0], *pool.shape[-2:]) for val, pool in ((k, k_pages), (v, v_pages))]
        return tuple(page_write((k_pages, v_pages), layer, page_ids, offsets, rows, interpret=interpret))

    return impl
