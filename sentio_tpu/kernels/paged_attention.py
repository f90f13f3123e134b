"""Pallas paged attention (decode): attention over a page-table KV cache.

The continuous-batching engine (runtime/paged.py) stores KV in ONE pool of
fixed-size pages for all layers, ``[L, P, page, Hkv, D]``; at decode each
row attends over its own scattered page list in one layer of it. The XLA
path gathers those pages into a contiguous window first — an HBM round-trip
proportional to the whole window. This kernel instead reads the pool where
it lies, and its time follows what the rows HOLD:

* the kernel takes the whole pool, left in HBM (``memory_space=pl.ANY``): a
  ``pool[layer]`` operand would be materialised by XLA (a custom call cannot
  fuse its operand), a copy of every page of the layer per call. The page
  table, the row lengths and the layer index ride **scalar prefetch**; the
  layer is a traced int32, so every layer of a decode program shares one
  kernel body;
* grid ``(B,)``, and inside row ``b`` a loop over the blocks the row holds —
  ``lens[b] // page + 1``, one for a free slot (``blocks_walked``). Each
  block is ONE copy by the kernel's own DMA (``pool[layer, table[b, j]]`` →
  VMEM) and one step of the online-softmax (m, l, acc) recurrence in fp32
  scratch; a table cell past a row's length is never read, copied or
  stepped over. (Until PR 29 the grid was ``(B, NB)``: a step a table CELL.)
* the copies run in a ring of ``pages_in_flight`` VMEM buffers that does not
  stop at a row's end: the fetch pointer is that many pages ahead of the
  compute, so the next row's first pages land while this row's last are
  computed;
* a page ``[page, Hkv, D]`` is copied as the matrix ``[page * Hkv, D]`` it is
  in memory (keys of all kv heads interleaved) and goes to the MXU as it
  lies: ``q [H, D] · Kᵀ`` gives every query head its scores against every kv
  head's keys, and the columns of another head's keys are masked like
  positions past the length (key pos ≤ len — the new token's KV was
  scattered at index ``len`` before the call). That spends vector work on
  scores nobody wants (Hkv times the needed) and none on a relayout: a
  ``[page, Hkv, D] → [Hkv, page, D]`` swap of every page cost more than its
  copy (PERF.md §5, PR 29). GQA stays folded: pages are never expanded to
  query heads.

ONE walk and ONE block arithmetic serve both representations:

* **bf16 pages** (``paged_attention``);
* **int8 pages** (``paged_attention_quant``) — the int8 page PLUS its bf16
  per-vector scales (stored page-minor, ``[L, P, Hkv, page]``, so a scale
  block is one lane-dense tile; Mosaic has no float16 vector type on v5e)
  are copied together. The page is the same matrix cast to q's dtype (exact:
  an int8 is a bf16); q·(s·K) folds as (q·K)·s and p·(s·V) as (p·s)·V, the
  scales spread over the columns by a matmul against a 0/1 matrix, so
  quantized pages never round-trip through a dense bf16 gather in HBM and
  no page is dequantized element by element. Page reads shrink to ~half
  the bytes of bf16.

The DMA engine moves whole tiles of the pool AS IT LIES IN HBM, and XLA lays
a pool out by its shape. Where a position's kv heads do not fill 32-bit
sublanes XLA stores the page head-major and the walk reads it so
(``_head_major``: a bitcast either way). Where a page cannot fill tiles at
all (``untiled``: head_dim under 128, int8 scale pages under 128 positions
or of one kv head) the walk refuses and the engine serves the geometry
through the XLA gather path: nothing pads or copies a pool on the way to the
kernel.

Runs in interpret mode on CPU (tests); on TPU it is the decode path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)

__all__ = ["paged_attention", "paged_attention_quant", "make_paged_attn_impl",
           "blocks_walked", "first_block", "pages_in_flight", "untiled", "lane_packing"]


# VMEM the call's scratch may take with no limit of its own: under the 16 MiB
# a kernel is given on every TPU generation when it asks for nothing, with
# room for q and out; and how much of that the ring of page buffers may take
_UNASKED_VMEM_BYTES = 12 * 1024 * 1024
_PAGE_BUFFER_BYTES = 10 * 1024 * 1024
# pages in VMEM at once: one computed, two on their way. A ring 2, 3 and 8
# deep were timed on the chip at both cells' geometries (PERF.md §5, PR 29):
# 3 is the smallest that read as fast as 8
_PAGES_IN_FLIGHT = 3
# a position no row reaches: marks a score against another kv head's key
_NEVER = 1 << 30
# a window is a constant of the model's CONFIGURATION (``sliding_window``, or
# None for a layer that sees all): static in the kernel, one variant a value
ConfigWindow = int | None


def _packs(dtype) -> int:
    """Rows of ``dtype`` one 32-bit sublane packs: 2 of bf16, 4 of int8."""
    return max(1, 4 // jnp.dtype(dtype).itemsize)


def _roundup(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(shape, dtype) -> int:
    """Bytes a block takes in VMEM: its last two dims pad to the dtype's
    tile (8 sublanes of 32 bits — 16 rows of bf16, 32 of int8 — by 128
    lanes). An upper bound: Mosaic may pick a smaller tile for few rows."""
    *lead, rows, lanes = shape
    return (int(np.prod(lead, dtype=np.int64)) * jnp.dtype(dtype).itemsize
            * _roundup(rows, 8 * _packs(dtype)) * _roundup(lanes, 128))


def _page_bytes(pools) -> int:
    """VMEM one page of every pool takes (K, V and, quantized, their scales)."""
    return sum(_vmem_bytes(pool.shape[2:], pool.dtype) for pool in pools)


def pages_in_flight(pools) -> int:
    """How many pages of ``pools`` the walk keeps in VMEM at once:
    ``_PAGES_IN_FLIGHT``, or two (one computed while one lands) where three
    overrun the buffer budget. From static shapes alone — the same rule for
    every geometry."""
    return int(max(2, min(_PAGES_IN_FLIGHT,
                          _PAGE_BUFFER_BYTES // _page_bytes(pools))))


def first_block(lens, page: int, window: int | None):
    """The first block the walk reads for rows at ``lens``: block 0, or with
    a ``window`` (the keys a query sees behind itself, itself included) the
    block that holds position ``lens - window + 1``, the oldest it sees."""
    if window is None:
        return lens * 0
    return (lens - (window - 1)).clip(0) // page


def blocks_walked(lens, page: int, nb: int, window: int | None = None):
    """Blocks the walk copies and computes for rows at ``lens`` (a numpy or
    jax array): the new token sits at index ``lens``, so ``lens // page +
    1``, never past the table, less the blocks wholly behind a ``window``. A
    free slot (``lens`` 0) costs its one block of the scratch page. The
    host's counter (``runtime/paged.py``) counts by this same rule."""
    return (lens // page).clip(0, nb - 1) + 1 - first_block(lens, page, window).clip(0, nb - 1)


def _head_major(hkv: int, dtype) -> bool:
    """Whether a page of ``hkv`` kv heads is read as ``[Hkv * page, D]``
    (row = head * page + position) and not ``[page * Hkv, D]`` (row =
    position * Hkv + head). XLA lays a pool out in HBM by its shape: where
    the kv heads of a position fill whole 32-bit sublanes (or there is one)
    the pool lies position-major as its shape says, and the second view is a
    bitcast; where they do not (2 heads of int8: a device's share of 8 under
    ``tp=4``) XLA stores the page head-major, the first view is the bitcast,
    and ANY kernel that asks for ``[page, Hkv, D]`` blocks is handed a copy
    of the pool by XLA first (the grid-of-cells kernel was, PERF.md §5).
    ``tests/test_chip_compile.py`` holds that neither view is a copy."""
    return hkv > 1 and hkv % _packs(dtype) != 0


def untiled(page: int, hkv: int, head_dim: int, quant: bool) -> str | None:
    """Why the chip's DMA cannot bring the pages of this geometry (``hkv``
    kv heads on ONE device), or None where it can. The DMA engine moves
    whole tiles of the pool as it lies in HBM — 8 sublanes of 32 bits by
    128 lanes. Where a page does not fill them XLA does not even store the
    pool in the order of its shape (see ``_head_major``), and a page is not
    a slice of it: such a pool needs another layout from ``init_pool``, not
    a padded copy a call. The engine reads this when it is built and serves
    such a geometry through the XLA gather path; the kernel raises."""
    dtype = jnp.int8 if quant else jnp.bfloat16
    if head_dim % 128:
        return f"head_dim {head_dim} is not a multiple of the 128 lanes of a tile"
    if (page * hkv) % (8 * _packs(dtype)):
        return (f"a page of {page} x {hkv} {jnp.dtype(dtype).name} vectors is not "
                f"a whole number of {8 * _packs(dtype)}-row tiles")
    if quant and page % 128:
        return f"a scale page of {page} positions does not fill the 128 lanes of a tile"
    if quant and hkv % 2:
        return f"the scale pages of {hkv} kv head(s) do not fill a 32-bit sublane"
    return None


def lane_packing(hkv: int, head_dim: int) -> int:
    """How many kv heads share a row of a LANE-PACKED pool (``runtime/paged.py
    ::init_pool``): heads narrower than a tile's 128 lanes lie side by side,
    ``128 / head_dim`` to a row, where the kv heads make whole rows; 1 (the
    pool as its shape says) for every other geometry. The walk then reads
    ``[page * Hkv / pack, D * pack]`` matrices that ARE whole tiles; a query
    head is handed over in the lanes of its own kv head, zeros in the others
    (``make_paged_attn_impl``), so every score and every output is the
    unpacked one: the other heads' lanes add exact zeros."""
    pack = 128 // head_dim if 0 < head_dim < 128 and 128 % head_dim == 0 else 1
    return pack if hkv % pack == 0 else 1


def _walk_kernel(
    pt_ref,     # [B, NB] int32 scalar-prefetch — page table
    lens_ref,   # [B] int32 scalar-prefetch — current token index per row
    layer_ref,  # [1] int32 scalar-prefetch — the layer whose pages are read
    q_ref,      # [H, D] — row b's query heads
    *refs,      # pools (HBM) | o_ref | m, l, acc, pos, (spread) | page buffers | sems, walk
    quant: bool,
    page: int,
    hkv: int,
    head_major: bool,
    depth: int,
    sm_scale: float,
    window: int | None,
):
    """Row ``b`` of the walk: one loop step a block the row HOLDS (with a
    ``window``: a block that holds a key the row's query still sees).

    The pools stay in HBM; every page is brought by the kernel's own DMA
    into a ring of ``depth`` VMEM buffers. The ring runs ACROSS rows: the
    fetch pointer (``walk``: row, block, pages issued, pages consumed) lives
    in SMEM for the whole call and is ``depth - 1`` pages ahead of the
    compute, so the next row's first pages land while this row's last are
    computed. Every row holds at least one block (``blocks_walked``), so the
    pointer steps from a row's last block to the next row's first with no
    search, and every block computed holds a key every head may see (its
    first): the running maximum is finite from the first block on.

    A page goes to the MXU as the matrix ``[page * Hkv, D]`` it is in
    memory: ``q [H, D] · Kᵀ`` scores every query head against every kv
    head's keys, and ``pos`` — the position each column's key holds, for the
    query heads of ITS kv head, ``_NEVER`` for the others — masks another
    head's columns like positions past the length. int8 pages are the same
    matrix cast to q's dtype; their scales ``[Hkv, page]`` are spread over
    the columns by one more matmul against a 0/1 matrix (``spread``), so
    q·(s·K) folds as (q·K)·s and p·(s·V) as (p·s)·V and no page is
    dequantized element by element."""
    n_pools = 4 if quant else 2
    pools = refs[:n_pools]
    o_ref, m_ref, l_ref, acc_ref, pos_ref = refs[n_pools:n_pools + 5]
    rest = refs[n_pools + 5:]
    spread_ref, rest = (rest[0], rest[1:]) if quant else (None, rest)
    buffers, (sems, walk) = rest[:n_pools], rest[n_pools:]
    b, rows = pl.program_id(0), pl.num_programs(0)
    nb = pt_ref.shape[1]
    layer = layer_ref[0]
    h = q_ref.shape[0]
    rep = h // hkv

    def first(row):  # the first block of a row's walk, and one past its last
        if window is None:
            return 0
        return jnp.minimum(first_block(lens_ref[row], page, window), nb - 1)

    def end(row):
        return jnp.clip(lens_ref[row] // page, 0, nb - 1) + 1

    def copies(row, j, slot):
        pid = pt_ref[row, j]
        return [pltpu.make_async_copy(pool.at[layer, pid], buf.at[slot], sems.at[n, slot])
                for n, (pool, buf) in enumerate(zip(pools, buffers))]

    def fetch_next():
        row, j, issued = walk[0], walk[1], walk[2]

        @pl.when(row < rows)
        def _():
            for copy in copies(row, j, issued % depth):
                copy.start()
            last = j + 1 >= end(row)
            walk[0] = jnp.where(last, row + 1, row)
            # the next row's first block; past the last row nothing is fetched
            walk[1] = jnp.where(last, first(jnp.minimum(row + 1, rows - 1)), j + 1)
            walk[2] = issued + 1

    def column(shape):
        """(kv head, position in the page) of each column of a page matrix."""
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return (col // page, col % page) if head_major else (col % hkv, col // hkv)

    @pl.when(b == 0)
    def _first_row():
        for n in range(4):
            walk[n] = 0
        if window is not None:
            walk[1] = first(0)
        jax.lax.fori_loop(0, depth - 1, lambda _, c: (fetch_next(), c)[1], 0)
        col_head, col_pos = column(pos_ref.shape)
        own = jax.lax.broadcasted_iota(jnp.int32, pos_ref.shape, 0) // rep
        pos_ref[:] = jnp.where(col_head == own, col_pos, _NEVER)
        if quant:  # spread[p, c] = 1 where column c holds position p
            _, col_pos = column(spread_ref.shape)
            at = jax.lax.broadcasted_iota(jnp.int32, spread_ref.shape, 0)
            spread_ref[:] = jnp.where(col_pos == at, 1.0, 0.0).astype(spread_ref.dtype)

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    cur = lens_ref[b]  # the new token sits at absolute index ``cur``
    q = q_ref[:]

    def over_columns(scales):
        """[Hkv, page] scales → [H, page * Hkv]: query head h's row holds, at
        every column, the scale of ITS kv head at that column's position
        (another head's columns are masked, whatever they read)."""
        own = jax.lax.broadcasted_iota(jnp.int32, (h, page), 0) // rep
        mine = jnp.zeros((h, page), jnp.float32)
        for g in range(hkv):
            mine = jnp.where(own == g, scales[g:g + 1, :].astype(jnp.float32), mine)
        return jax.lax.dot_general(
            mine.astype(q.dtype), spread_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def page_step(j, carry):
        # the slot this fetch fills is the one the previous step computed on
        fetch_next()
        slot = walk[3] % depth
        for copy in copies(b, j, slot):
            copy.wait()
        if quant:
            kq_buf, ks_buf, vq_buf, vs_buf = buffers
            k, v = kq_buf[slot].astype(q.dtype), vq_buf[slot].astype(q.dtype)
        else:
            k, v = buffers[0][slot], buffers[1][slot]
        # s[h, c] = q[h, :] · k[c, :] — the page as it lies, no relayout
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if quant:
            s = s * over_columns(ks_buf[slot])
        seen = pos_ref[:] <= cur - j * page
        if window is not None:  # _NEVER stays unseen: it passes only this half
            seen &= pos_ref[:] > cur - window - j * page
        s = jnp.where(seen, s * sm_scale, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            p = p * over_columns(vs_buf[slot])
        # p is 0 against another head's keys: acc[h, :] += p[h, :] @ v[:, :]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        walk[3] = walk[3] + 1
        return carry

    jax.lax.fori_loop(first(b), end(b), page_step, 0)
    o_ref[:] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def _walk_pool(q, pools, layer, page_table, lens, interpret, window=None, sm_scale=None):
    """The walk both representations share: grid ``(B,)``, ``pools`` whole
    in HBM — K and V pages ``[L, P, page, Hkv, D]`` and, quantized (four
    pools: K pages, K scales, V pages, V scales), their scales ``[L, P, Hkv,
    page]`` — q [B, H, D] → [B, H, D]."""
    b, h, d = q.shape
    quant = len(pools) == 4
    layers, num_pages, page, hkv, _ = pools[0].shape
    if not interpret:  # the interpreter has no tiles; the chip's DMA has
        why = untiled(page, hkv, d, quant)
        if why:
            raise ValueError(f"paged attention on this device: {why}")
    head_major = _head_major(hkv, pools[0].dtype)

    def matrix(pages):
        # a page as the matrix it is in HBM: a bitcast (see ``_head_major``)
        if head_major:
            pages = pages.transpose(0, 1, 3, 2, 4)
        return pages.reshape(layers, num_pages, page * hkv, d)

    pools = tuple(matrix(pool) if pool.ndim == 5 else pool for pool in pools)
    depth = pages_in_flight(pools)
    tables = [((h, page * hkv), jnp.int32)] + [((page, page * hkv), q.dtype)] * quant
    # the page ring and the column tables (which grow with the page size
    # squared): only where a very large page size overruns what a kernel is
    # given unasked does the call ask for more VMEM
    scratch = depth * _page_bytes(pools) + sum(_vmem_bytes(*t) for t in tables)
    vmem_limit = None if scratch <= _UNASKED_VMEM_BYTES else scratch + (6 << 20)
    row = pl.BlockSpec((None, h, d), lambda bb, *_: (bb, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row, *[pl.BlockSpec(memory_space=pl.ANY)] * len(pools)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
            *[pltpu.VMEM(shape, dtype) for shape, dtype in tables],
            *[pltpu.VMEM((depth, *pool.shape[2:]), pool.dtype) for pool in pools],
            pltpu.SemaphoreType.DMA((len(pools), depth)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _walk_kernel, quant=quant, page=page, hkv=hkv, head_major=head_major,
            depth=depth, sm_scale=sm_scale or 1.0 / float(np.sqrt(d)), window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # rows in order: the ring of page buffers runs across them
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32), lens.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), q, *pools,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "window", "sm_scale"))
def paged_attention(
    q: jax.Array,           # [B, H, D] — one decode token per row
    k_pages: jax.Array,     # [L, P, page, Hkv, D] — the page pool, all layers
    v_pages: jax.Array,     # [L, P, page, Hkv, D]
    layer: jax.Array,       # int32 scalar — the layer whose pages are read
    page_table: jax.Array,  # [B, NB] int32 physical page ids
    lens: jax.Array,        # [B] int32 — index of the current token
    *,
    interpret: bool = False,
    window: int | None = None,
    sm_scale: float | None = None,
) -> jax.Array:
    """Decode attention over one layer of the paged pool → [B, H, D]. With a
    ``window`` a row's query sees keys ``lens - window < j <= lens`` and the
    walk starts at the block that holds the oldest of them. ``sm_scale``:
    the softmax scale, ``D ** -0.5`` unless given (a lane-packed pool's rows
    are wider than a head)."""
    return _walk_pool(q, (k_pages, v_pages), layer, page_table, lens, interpret, window, sm_scale)


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
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
    window: int | None = None,
) -> jax.Array:
    """Decode attention over one layer of the int8-quantized paged pool →
    [B, H, D]. The same walk as :func:`paged_attention`; a page's int8
    payload and its scale page are copied together and dequantized in VMEM.
    """
    return _walk_pool(q, (k_pages_q, k_scales, v_pages_q, v_scales),
                      layer, page_table, lens, interpret, window)


def make_paged_attn_impl(interpret: bool | None = None, mesh=None):
    """Adapter with the ``paged_decode_forward(attn_impl=...)`` signature:
    (q [B,1,H,D], k_pages, v_pages, layer, page_table, lens, n_rep) →
    [B,1,H,D], the pools whole as ``runtime.paged.init_pool`` made them.

    Representation-aware: a plain array routes to the bf16 kernel, a
    ``{"q", "s"}`` pytree (the ``kv_quant="int8"`` pool) routes to the int8
    kernel — so one engine attn seam serves both pool representations. A
    LANE-PACKED pool (rows wider than q's heads: :func:`lane_packing`) is
    walked as the ``Hkv / pack`` heads of ``D * pack`` its shape says: each
    query head goes in with its vector in the lanes of its own kv head and
    zeros in the others, and comes out of those same lanes.

    With a ``mesh`` the kernel runs INSIDE ``shard_map`` over ``tp``: the
    pool is kv-head-sharded there (``runtime.paged.init_pool``) and query
    heads shard the same way (wq is column-sharded), so each device walks
    the page table over its own heads. A bare ``pallas_call`` under GSPMD
    cannot be partitioned — XLA would gather the whole pool around it.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def impl(q, k_pages, v_pages, layer, page_table, lens, n_rep, window: ConfigWindow = None):
        if isinstance(k_pages, dict):
            out = paged_attention_quant(
                q[:, 0], k_pages["q"], k_pages["s"],
                v_pages["q"], v_pages["s"],
                layer, page_table, lens, interpret=interpret, window=window,
            )
        elif k_pages.shape[-1] != q.shape[-1]:
            b, _, h, d = q.shape
            pack = k_pages.shape[-1] // d
            place = (jnp.arange(h) // n_rep) % pack          # a head's place in its kv head's row
            lanes = jax.nn.one_hot(place, pack, dtype=q.dtype)[None, :, :, None]
            out = paged_attention(
                (q[:, 0][:, :, None, :] * lanes).reshape(b, h, pack * d), k_pages, v_pages,
                layer, page_table, lens, interpret=interpret, window=window, sm_scale=d ** -0.5,
            ).reshape(b, h, pack, d)
            out = jnp.take_along_axis(out, place[None, :, None, None], axis=2)[:, :, 0]
        else:
            out = paged_attention(
                q[:, 0], k_pages, v_pages, layer, page_table, lens,
                interpret=interpret, window=window,
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

    def sharded_impl(q, k_pages, v_pages, layer, page_table, lens, n_rep,
                     window: ConfigWindow = None):
        return jax.shard_map(
            functools.partial(impl, n_rep=n_rep, window=window), mesh=mesh,
            in_specs=(heads, pool_spec(k_pages), pool_spec(v_pages),
                      P(), P(), P()),
            out_specs=heads, check_vma=False,
        )(q, k_pages, v_pages, jnp.asarray(layer, jnp.int32), page_table, lens)

    return sharded_impl
