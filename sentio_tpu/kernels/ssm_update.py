"""Pallas state update of the state-space families (decode): one token a row,
in place in HBM — Mamba-2's (:func:`ssm_update`) and Mamba-1's
(:func:`selective_update`) over ONE walk of the advancing rows.

A decode sub-step advances each Mamba layer's state by one token and reads
``y`` from the new state. Left to XLA that is loop fusions over EVERY slot's
state, the halted slots' too, a select against the mask of rows that advance
and a write of all of it: XLA has no way to skip a row by a mask, and it will
not fuse a reduce into an in-place update (PERF.md §5, PR 46: 100 MB a Mamba-2
block where the rows that advance need 44; PR 49: the Mamba-1 slots' 68 MB
placed in nearer memory at the head of every sub-step and copied back at its
end). The walk (:func:`_walk`) does the minimum a one-token update needs:

* a row that ADVANCES has its state read from HBM once, in blocks of at most
  ``BLOCK_BYTES`` (the next two blocks' reads and the last blocks' writes in
  flight while a block is computed), updated in float32 in VMEM, written back
  to the same place, and ``y`` reduced from the block while it is there;
* a row that does NOT advance moves no byte: the kernel compacts the mask to
  the list of advancing slots (scalar work) and walks that list alone. Its
  state keeps every bit and its ``y`` is zero;
* the state is ALIASED to the output and the output DECLARED in HBM, the layer
  a traced int32 in scalar prefetch, as ``kernels/page_write.py`` does and for
  its reasons: the call makes nothing, and all Mamba layers of a program share
  one kernel body.

The two recurrences are different mathematics, so each brings its own block
computation to the walk:

* **Mamba-2** (``models/nemotron_h.py``; ``state["ssm"]`` ``[Lm, B, H, P, N]``):
  ``S <- S * decay + (x * dt) (x) B`` with ONE scalar decay a head, a block
  some heads of a row (512 KB a DMA). ``B`` and ``C`` come as the groups they
  are (``[B, G, N]``), not repeated to the heads first. ``x * dt`` arrives
  with ``P`` on the sublanes (``[B, P, H]``) and ``y`` leaves so: a head's
  column then broadcasts along the lanes of its ``[P, N]`` state with no
  transpose in the kernel. The reduction over ``n`` is over LANES. As lane
  reductions a vreg it binds the kernel (my chip runs, PR 46: 127 µs at 16
  rows where the same walk that only copies takes 104, and packing the vregs
  before the rotates 209); it goes to the matrix unit instead, ``S' [P, N]``
  against the groups' ``C`` laid out a head a row (``[H, N]``, contracted over
  ``n``) at ``HIGHEST`` precision — float32 in, float32 out — and lane ``h``
  of the product is head ``h``'s ``y``: 104 µs, what the DMAs alone take.
* **Mamba-1** (``models/jamba.py``; ``state["ssm"]`` ``[Lm, B, N, inner]``, ``S``
  transposed, channels on the lanes): ``S <- exp(D (x) A) * S + (D x) (x) B``
  with a decay a channel AND state column, ``A = -exp(a_log)``: a block is a
  row's whole ``[N, inner]`` (320 KB at the published 16 x 5120), the ``exp``
  made in the kernel in float32 — ``models/jamba.py::mamba1_step``'s terms,
  one for one — and the reduction over ``n`` is over SUBLANES: adds of whole
  vregs, then one reduction inside a vreg a lane tile.

Which states take this path is :func:`ssm_update_path`'s to say, from the
state's shape, dtype and placement alone. Runs in interpret mode on the CPU
(tests); on a TPU it is what the engine binds where the rule says so.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_update", "selective_update", "ssm_update_path", "make_ssm_update_impl", "BLOCK_BYTES"]

# one DMA of the walk: heads of a row's state, as many as fit (a Mamba-1 row's
# whole state); and how many blocks the walk holds in VMEM, read and to be
# written each: the block in work and the reads AHEAD of it. (My chip runs,
# PR 46, 16 rows: two buffers 111 µs, three 104, four of half the size 104.5;
# a walk that only copies 104.)
BLOCK_BYTES = 512 * 1024
BUFFERS = 3
AHEAD = BUFFERS - 1
# lanes of a Mamba-1 row's state one pass of the arithmetic takes: 16 sublanes
# of them are 16 vregs an operand, of the chip's 64
CHUNK_LANES = 1024


def ssm_update_path(state, mesh=None) -> str:
    """``"pallas"`` where a decode step updates ``state`` (``state["ssm"]`` as
    ``runtime/paged.py::init_pool`` made it: ``[Lm, B, H, P, N]``, a matrix a
    head, or ``[Lm, B, N, inner]``, a decay a channel and column) through
    :func:`ssm_update` / :func:`selective_update`, ``"xla"`` where the model's
    own arithmetic and the masked ``.at[j].set`` stay: a static fact of the
    operand (an array or its ``ShapeDtypeStruct``). The kernels take a float32
    state on one device whose last two axes — a head's ``[P, N]``, a row's
    ``[N, inner]`` — are whole float32 tiles (8 sublanes, 128 lanes) and no
    more than a DMA of the walk; any other dtype or rank, a state under a mesh
    and the narrow rehearsal widths keep the XLA form, whose program is
    unchanged."""
    if mesh is not None or len(state.shape) not in (4, 5) or state.dtype != jnp.float32:
        return "xla"
    p, n = state.shape[-2:]
    return "pallas" if p % 8 == 0 and n % 128 == 0 and 4 * p * n <= BLOCK_BYTES else "xla"


def _head_block(heads: int, per_head_bytes: int) -> int:
    """Heads a DMA: the most that divide ``heads`` inside ``BLOCK_BYTES``."""
    return max(k for k in range(1, heads + 1) if heads % k == 0 and k * per_head_bytes <= BLOCK_BYTES)


def _walk(mask_ref, layer_ref, state_ref, out_ref, order, in_buf, out_buf, sems, *, nblk, part, begin, row_start,
          block, row_end):
    """The walk both recurrences share. ``mask_ref [B]`` is compacted to the
    list of advancing rows in ``order`` (SMEM); each such row's ``nblk``
    blocks — ``part(ref, layer, row, k)``, what one buffer holds — are read
    into the ring ``in_buf``, handed to the caller, and written from the ring
    ``out_buf`` to the same place of ``out_ref``: ``AHEAD`` reads and up to
    ``BUFFERS`` writes in flight while a block is computed. ``sems[0]`` counts
    the reads, ``sems[1]`` the writes, one a buffer. The caller's part:
    ``begin(count)`` once, before the first read is started; ``held =
    row_start(i, b)`` for the ``i``-th advancing row, slot ``b``; ``held =
    block(b, k, src, dst, held)`` with a block read in ``src`` and ``dst`` to
    fill; ``row_end(b, held)``. A row that does not advance is never named."""
    rows = mask_ref.shape[0]
    layer = layer_ref[0]

    def compact(b, n):
        @pl.when(mask_ref[b] != 0)
        def _():
            order[n] = b

        return n + (mask_ref[b] != 0).astype(jnp.int32)

    count = jax.lax.fori_loop(0, rows, compact, jnp.int32(0))
    blocks = count * nblk
    begin(count)

    def fetch(i, k, slot):
        return pltpu.make_async_copy(part(state_ref, layer, order[i], k), in_buf.at[slot], sems.at[0, slot])

    def flush(i, k, slot):
        return pltpu.make_async_copy(out_buf.at[slot], part(out_ref, layer, order[i], k), sems.at[1, slot])

    def flushed(slot):  # any block's write: a wait counts bytes, not places
        return pltpu.make_async_copy(out_buf.at[slot], part(out_ref, layer, 0, 0), sems.at[1, slot])

    for ahead in range(AHEAD):                                          # the first reads, before any block is waited for
        @pl.when(ahead < blocks)
        def _():
            fetch(ahead // nblk, ahead % nblk, ahead % BUFFERS).start()

    def row(i, carry):
        b = order[i]
        held = row_start(i, b)
        for k in range(nblk):
            at = i * nblk + k
            slot = at % BUFFERS

            @pl.when(i + (k + AHEAD) // nblk < count)
            def _():
                fetch(i + (k + AHEAD) // nblk, (k + AHEAD) % nblk, (at + AHEAD) % BUFFERS).start()

            fetch(i, k, slot).wait()

            @pl.when(at >= BUFFERS)                                     # the write that last used this buffer
            def _():
                flushed(slot).wait()

            held = block(b, k, in_buf.at[slot], out_buf.at[slot], held)
            flush(i, k, slot).start()
        row_end(b, held)
        return carry

    jax.lax.fori_loop(0, count, row, 0)
    for back in range(1, BUFFERS + 1):                                  # the writes still in flight
        @pl.when(blocks >= back)
        def _():
            flushed((blocks - back) % BUFFERS).wait()


def _update_kernel(mask_ref, layer_ref, decay_ref, xdt_ref, b_ref, c_ref, state_ref, out_ref, y_ref,
                   order, in_buf, out_buf, sems):
    """Mamba-2 over the walk: a block is ``hb`` heads of a row, each a ``[P,
    N]`` matrix under one scalar decay; ``y`` a head a lane, from the matrix
    unit."""
    _, p, heads = xdt_ref.shape
    hb = in_buf.shape[1]
    rep = heads // b_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (p, heads), 1)

    def begin(count):
        y_ref[...] = jnp.zeros_like(y_ref)

    def row_start(i, b):
        xdt = xdt_ref[b]                                               # [P, H]
        # the groups' C a head a row: lane h of ``S' [P, N] x this [H, N]`` is head h's y
        c_heads = jnp.concatenate([jnp.broadcast_to(c_ref[b, g:g + 1, :], (rep, c_ref.shape[2]))
                                   for g in range(heads // rep)])
        return xdt, c_heads, jnp.zeros((p, heads), jnp.float32)

    def block(b, k, src, dst, held):
        xdt, c_heads, acc = held
        for h in range(hb):
            head = k * hb + h
            g = head // rep
            new = src[h] * decay_ref[b, head] + xdt[:, head:head + 1] * b_ref[b, g:g + 1, :]
            dst[h] = new
            over_c = jax.lax.dot_general(new, c_heads, (((1,), (1,)), ((), ())),
                                         precision=jax.lax.Precision.HIGHEST,
                                         preferred_element_type=jnp.float32)          # [P, H]
            acc = jnp.where(lane == head, over_c, acc)
        return xdt, c_heads, acc

    def row_end(b, held):
        y_ref[b] = held[2]

    _walk(mask_ref, layer_ref, state_ref, out_ref, order, in_buf, out_buf, sems, nblk=heads // hb,
          part=lambda ref, layer, row, k: ref.at[layer, row, pl.ds(k * hb, hb)],
          begin=begin, row_start=row_start, block=block, row_end=row_end)


def _selective_kernel(mask_ref, layer_ref, b_ref, c_ref, dt_ref, dx_ref, alog_ref, state_ref, out_ref, y_ref,
                      order, in_buf, out_buf, sems, a_buf, a_sem, *, chunk: int):
    """Mamba-1 over the walk: a block is a row's whole ``[N, inner]`` state,
    the decay ``exp(D (x) A)`` a number a channel and state column, made here
    in float32, ``chunk`` lanes a pass; ``y`` a sum over the ``N`` sublanes.
    ``A = -exp(a_log)`` is made once a call, from a copy started with the
    first rows' reads, and only where a row advances; ``b_ref`` and ``c_ref``
    are scalars (SMEM), laid along the sublanes by a select a column."""
    n, inner = a_buf.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    a_copy = pltpu.make_async_copy(alog_ref, a_buf, a_sem.at[0])

    def begin(count):
        y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(count > 0)
        def _():
            a_copy.start()

    def column(ref, b):                                                 # ref[b, :] down the sublanes: [N, 1]
        col = jnp.zeros((n, 1), jnp.float32)
        for j in range(n):
            col = jnp.where(sub == j, ref[b, j], col)
        return col

    def row_start(i, b):
        @pl.when(i == 0)
        def _():
            a_copy.wait()
            for c in range(0, inner, chunk):
                a_buf[:, c:c + chunk] = -jnp.exp(a_buf[:, c:c + chunk])

        return column(b_ref, b), column(c_ref, b)

    def block(b, k, src, dst, held):
        bcol, ccol = held
        for c in range(0, inner, chunk):
            cols = slice(c, c + chunk)
            new = (jnp.exp(dt_ref[pl.ds(b, 1), cols] * a_buf[:, cols]) * src[:, cols]
                   + dx_ref[pl.ds(b, 1), cols] * bcol)
            dst[:, cols] = new
            y_ref[pl.ds(b, 1), cols] = jnp.sum(new * ccol, axis=0, keepdims=True)
        return held

    _walk(mask_ref, layer_ref, state_ref, out_ref, order, in_buf, out_buf, sems, nblk=1,
          part=lambda ref, layer, row, k: ref.at[layer, row],
          begin=begin, row_start=row_start, block=block, row_end=lambda b, held: None)


def _walk_call(kernel, state, layer, advancing, *, smem, vmem, hbm, y_shape, block, scratch=(), interpret):
    """The ``pallas_call`` of a walk: the mask and the layer in scalar
    prefetch, the ``smem`` and ``vmem`` operands there, ``hbm`` ones left
    where they lie, then ``state`` — ALIASED to the first output, which is
    DECLARED in HBM (``kernels/page_write.py``) — and ``y``; the walk's order,
    its two rings of ``block``-shaped buffers and their semaphores, then the
    kernel's own ``scratch``."""
    f32 = jnp.float32
    ring = pltpu.VMEM((BUFFERS, *block), f32)
    operands = (advancing.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
                *(a.astype(f32) for a in (*smem, *vmem, *hbm)), state)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(),
            in_specs=[*(pl.BlockSpec(memory_space=pltpu.SMEM) for _ in smem),
                      *(pl.BlockSpec(memory_space=pltpu.VMEM) for _ in vmem),
                      *(pl.BlockSpec(memory_space=pl.ANY) for _ in (*hbm, state))],
            out_specs=[pl.BlockSpec(memory_space=pltpu.HBM), pl.BlockSpec(memory_space=pltpu.VMEM)],
            scratch_shapes=[pltpu.SMEM(advancing.shape, jnp.int32), ring, ring,
                            pltpu.SemaphoreType.DMA((2, BUFFERS)), *scratch],
        ),
        out_shape=[pltpu.HBM(state.shape, state.dtype), jax.ShapeDtypeStruct(y_shape, f32)],
        input_output_aliases={len(operands) - 1: 0},                    # operands count from the scalars
        name="ssm_update",
        interpret=interpret,
    )(*operands)


def _refuse(state, rank: int, other: str):
    """What a kernel does not take: a state the rule keeps in XLA (static: a
    fact of the operand), and the other recurrence's rank."""
    if ssm_update_path(jax.ShapeDtypeStruct(state.shape, state.dtype)) != "pallas":
        raise ValueError(f"ssm update: a state of {state.dtype}{list(state.shape)} is not float32 in whole tiles")
    if state.ndim != rank:
        raise ValueError(f"ssm update: a state of rank {state.ndim} is {other}'s")


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_update(state, layer, advancing, decay, xdt, bmat, cmat, *, interpret: bool = False):
    """Mamba-2: ``state[layer, b] <- state[layer, b] * decay[b, h] + xdt[b, h,
    p] * bmat[b, g(h), n]`` for every row ``b`` with ``advancing[b]``, in
    place → (the state, ``y [B, H, P]`` = the new state over ``cmat[b, g(h),
    n]``, zero for a row that does not advance). ``state`` ``[Lm, B, H, P, N]``
    float32, ``layer`` an int32 scalar, ``advancing`` ``[B]`` bool, ``decay``
    ``[B, H]``, ``xdt`` ``[B, H, P]``, ``bmat`` and ``cmat`` ``[B, G, N]``, all
    float32; head ``h`` is of group ``h // (H / G)``."""
    _refuse(state, 5, "selective_update")
    _, rows, heads, p, n = state.shape
    groups = bmat.shape[1]
    if heads % groups or decay.shape != (rows, heads) or xdt.shape != (rows, heads, p) \
            or bmat.shape != (rows, groups, n) or cmat.shape != bmat.shape:
        raise ValueError(f"ssm update: decay {decay.shape}, x dt {xdt.shape}, B {bmat.shape}, C {cmat.shape} "
                         f"over a state of {state.shape}")
    new, y = _walk_call(_update_kernel, state, layer, advancing, smem=(decay,), vmem=(xdt.transpose(0, 2, 1), bmat, cmat),
                        hbm=(), y_shape=(rows, p, heads), block=(_head_block(heads, 4 * p * n), p, n),
                        interpret=interpret)
    return new, y.transpose(0, 2, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_update(state, layer, advancing, dt, dx, bmat, cmat, a_log, *, interpret: bool = False):
    """Mamba-1: ``state[layer, b, n, c] <- exp(dt[b, c] * -exp(a_log[n, c])) *
    state[layer, b, n, c] + dx[b, c] * bmat[b, n]`` for every row ``b`` with
    ``advancing[b]``, in place → (the state, ``y [B, inner]`` = the new state
    summed over ``n`` against ``cmat[b, n]``, zero for a row that does not
    advance). ``state`` ``[Lm, B, N, inner]`` float32 (``S`` transposed:
    channels on the lanes), ``layer`` an int32 scalar, ``advancing`` ``[B]``
    bool, ``dt`` (after the softplus) and ``dx`` = ``dt * x`` ``[B, inner]``,
    ``bmat`` and ``cmat`` ``[B, N]``, ``a_log`` the layer's ``[N, inner]``, all
    float32."""
    _refuse(state, 4, "ssm_update")
    _, rows, n, inner = state.shape
    if dt.shape != (rows, inner) or dx.shape != dt.shape or bmat.shape != (rows, n) or cmat.shape != bmat.shape \
            or a_log.shape != (n, inner):
        raise ValueError(f"selective update: dt {dt.shape}, dt x {dx.shape}, B {bmat.shape}, C {cmat.shape}, "
                         f"a_log {a_log.shape} over a state of {state.shape}")
    chunk = max(c for c in range(128, min(inner, CHUNK_LANES) + 1, 128) if inner % c == 0)
    return _walk_call(functools.partial(_selective_kernel, chunk=chunk), state, layer, advancing,
                      smem=(bmat, cmat), vmem=(dt, dx), hbm=(a_log,), y_shape=(rows, inner), block=(n, inner),
                      scratch=(pltpu.VMEM((n, inner), jnp.float32), pltpu.SemaphoreType.DMA((1,))),
                      interpret=interpret)


def make_ssm_update_impl(interpret: bool | None = None):
    """Adapter with the ``paged_decode_forward(ssm_impl=...)`` signature:
    (state, layer, advancing, the recurrence's terms) → (state, y). The
    state's RANK says which recurrence: ``[Lm, B, H, P, N]`` and (decay, x dt,
    B, C) are :func:`ssm_update`'s, ``[Lm, B, N, inner]`` and (dt, dt x, B, C,
    a_log) :func:`selective_update`'s."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def update(state, layer, advancing, *terms):
        return (selective_update if state.ndim == 4 else ssm_update)(state, layer, advancing, *terms,
                                                                     interpret=interpret)

    return update
