"""Pallas Mamba-2 state update (decode): one token a row, in place in HBM.

A decode sub-step advances each Mamba block's state by one token,
``S <- S * decay + (x * dt) (x) B``, and reads ``y = S C`` from the new state.
Left to XLA that is two loop fusions a block over the block's slice of
``state["ssm"]`` ``[Lm, B, H, P, N]`` float32: one recomputes the sum and
reduces it to ``y``, the other recomputes it again, selects against the mask of
rows that advance and writes — every slot's state read twice and written once,
the halted slots' too (PERF.md §5, PR 46: 100 MB a block where the rows that
advance need 44). XLA has no way to skip a row by a mask, and it will not fuse
a reduce into an in-place update. This kernel does the minimum a one-token
update needs:

* a row that ADVANCES has its ``[H, P, N]`` state read from HBM once, in blocks
  of heads (512 KB a DMA; the next two blocks' reads and the last blocks'
  writes in flight while a block is computed), updated in float32 in VMEM, written back
  to the same place, and ``y[h, p] = sum_n S'[h, p, n] C[g(h), n]`` reduced from
  the block while it is there. ``B`` and ``C`` come as the groups they are
  (``[B, G, N]``), not repeated to the heads first;
* a row that does NOT advance moves no byte: the kernel compacts the mask to
  the list of advancing slots (scalar work) and walks that list alone. Its
  state keeps every bit and its ``y`` is zero;
* the state is ALIASED to the output and the output DECLARED in HBM, the layer
  a traced int32 in scalar prefetch, as ``kernels/page_write.py`` does and for
  its reasons: the call makes nothing, and all Mamba blocks of a program share
  one kernel body.

``x * dt`` arrives with ``P`` on the sublanes (``[B, P, H]``) and ``y`` leaves
so: a head's column then broadcasts along the lanes of its ``[P, N]`` state
with no transpose in the kernel. The reduction over ``n`` is over LANES. As
lane reductions a vreg it binds the kernel (my chip runs, PR 46: 127 µs at 16
rows where the same walk that only copies takes 104, and packing the vregs
before the rotates 209); it goes to the matrix unit instead, ``S' [P, N]``
against the groups' ``C`` laid out a head a row (``[H, N]``, contracted over
``n``) at ``HIGHEST`` precision — float32 in, float32 out — and lane ``h`` of
the product is head ``h``'s ``y``: 104 µs, what the DMAs alone take.

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

__all__ = ["ssm_update", "ssm_update_path", "make_ssm_update_impl", "BLOCK_BYTES"]

# one DMA of the walk: heads of a row's state, as many as fit; and how many
# blocks the walk holds in VMEM, read and to be written each: the block in
# work and the reads AHEAD of it. (My chip runs, PR 46, 16 rows: two buffers
# 111 µs, three 104, four of half the size 104.5; a walk that only copies 104.)
BLOCK_BYTES = 512 * 1024
BUFFERS = 3
AHEAD = BUFFERS - 1


def ssm_update_path(state, mesh=None) -> str:
    """``"pallas"`` where a decode step updates ``state`` (``state["ssm"]``
    ``[Lm, B, H, P, N]`` as ``runtime/paged.py::init_pool`` made it) through
    :func:`ssm_update`, ``"xla"`` where ``mamba_step``'s arithmetic and the
    masked ``.at[j].set`` stay: a static fact of the operand (an array or its
    ``ShapeDtypeStruct``). The kernel takes a float32 state on one device
    whose ``[P, N]`` a head is whole float32 tiles (8 sublanes, 128 lanes)
    and no more than a DMA of the walk; any other dtype, a state under a mesh
    and the narrow rehearsal widths keep the XLA form, whose program is
    unchanged."""
    if mesh is not None or len(state.shape) != 5 or state.dtype != jnp.float32:
        return "xla"
    p, n = state.shape[-2:]
    return "pallas" if p % 8 == 0 and n % 128 == 0 and 4 * p * n <= BLOCK_BYTES else "xla"


def _head_block(heads: int, per_head_bytes: int) -> int:
    """Heads a DMA: the most that divide ``heads`` inside ``BLOCK_BYTES``."""
    return max(k for k in range(1, heads + 1) if heads % k == 0 and k * per_head_bytes <= BLOCK_BYTES)


def _update_kernel(mask_ref, layer_ref, decay_ref, xdt_ref, b_ref, c_ref, state_ref, out_ref, y_ref,
                   order, in_buf, out_buf, sems, *, hb: int):
    """Walk the advancing rows' head blocks: read, update, reduce, write back.
    ``sems[0]`` counts the reads, ``sems[1]`` the writes, one a buffer."""
    rows, p, heads = xdt_ref.shape
    rep = heads // b_ref.shape[1]
    nblk = heads // hb
    layer = layer_ref[0]

    def compact(b, n):
        @pl.when(mask_ref[b] != 0)
        def _():
            order[n] = b

        return n + (mask_ref[b] != 0).astype(jnp.int32)

    count = jax.lax.fori_loop(0, rows, compact, jnp.int32(0))
    blocks = count * nblk
    y_ref[...] = jnp.zeros_like(y_ref)

    def fetch(i, k, slot):
        return pltpu.make_async_copy(state_ref.at[layer, order[i], pl.ds(k * hb, hb)], in_buf.at[slot],
                                     sems.at[0, slot])

    def flush(i, k, slot):
        return pltpu.make_async_copy(out_buf.at[slot], out_ref.at[layer, order[i], pl.ds(k * hb, hb)],
                                     sems.at[1, slot])

    def flushed(slot):  # any block's write: a wait counts bytes, not places
        return pltpu.make_async_copy(out_buf.at[slot], out_ref.at[layer, 0, pl.ds(0, hb)], sems.at[1, slot])

    for ahead in range(AHEAD):                                          # the first reads, before any block is waited for
        @pl.when(ahead < blocks)
        def _():
            fetch(ahead // nblk, ahead % nblk, ahead % BUFFERS).start()

    lane = jax.lax.broadcasted_iota(jnp.int32, (p, heads), 1)

    def row(i, carry):
        b = order[i]
        xdt = xdt_ref[b]                                               # [P, H]
        # the groups' C a head a row: lane h of ``S' [P, N] x this [H, N]`` is head h's y
        c_heads = jnp.concatenate([jnp.broadcast_to(c_ref[b, g:g + 1, :], (rep, c_ref.shape[2]))
                                   for g in range(heads // rep)])
        acc = jnp.zeros((p, heads), jnp.float32)
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

            for h in range(hb):
                head = k * hb + h
                g = head // rep
                new = in_buf[slot, h] * decay_ref[b, head] + xdt[:, head:head + 1] * b_ref[b, g:g + 1, :]
                out_buf[slot, h] = new
                over_c = jax.lax.dot_general(new, c_heads, (((1,), (1,)), ((), ())),
                                             precision=jax.lax.Precision.HIGHEST,
                                             preferred_element_type=jnp.float32)          # [P, H]
                acc = jnp.where(lane == head, over_c, acc)
            flush(i, k, slot).start()
        y_ref[b] = acc
        return carry

    jax.lax.fori_loop(0, count, row, 0)
    for back in range(1, BUFFERS + 1):                                  # the writes still in flight
        @pl.when(blocks >= back)
        def _():
            flushed((blocks - back) % BUFFERS).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_update(state, layer, advancing, decay, xdt, bmat, cmat, *, interpret: bool = False):
    """``state[layer, b] <- state[layer, b] * decay[b, h] + xdt[b, h, p] *
    bmat[b, g(h), n]`` for every row ``b`` with ``advancing[b]``, in place →
    (the state, ``y [B, H, P]`` = the new state over ``cmat[b, g(h), n]``,
    zero for a row that does not advance). ``state`` ``[Lm, B, H, P, N]``
    float32, ``layer`` an int32 scalar, ``advancing`` ``[B]`` bool, ``decay``
    ``[B, H]``, ``xdt`` ``[B, H, P]``, ``bmat`` and ``cmat`` ``[B, G, N]``, all
    float32; head ``h`` is of group ``h // (H / G)``."""
    if ssm_update_path(jax.ShapeDtypeStruct(state.shape, state.dtype)) != "pallas":   # static: a fact of the operand
        raise ValueError(f"ssm update: a state of {state.dtype}{list(state.shape)} is not float32 in whole tiles")
    _, rows, heads, p, n = state.shape
    groups = bmat.shape[1]
    if heads % groups or decay.shape != (rows, heads) or xdt.shape != (rows, heads, p) \
            or bmat.shape != (rows, groups, n) or cmat.shape != bmat.shape:
        raise ValueError(f"ssm update: decay {decay.shape}, x dt {xdt.shape}, B {bmat.shape}, C {cmat.shape} "
                         f"over a state of {state.shape}")
    hb = _head_block(heads, 4 * p * n)
    f32 = jnp.float32
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    new, y = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pltpu.HBM), vmem],
            scratch_shapes=[pltpu.SMEM((rows,), jnp.int32), pltpu.VMEM((BUFFERS, hb, p, n), f32),
                            pltpu.VMEM((BUFFERS, hb, p, n), f32), pltpu.SemaphoreType.DMA((2, BUFFERS))],
        ),
        # DECLARED in HBM, and the aliased operand with it (``kernels/page_write.py``)
        out_shape=[pltpu.HBM(state.shape, state.dtype), jax.ShapeDtypeStruct((rows, p, heads), f32)],
        # operands count from the scalars: mask, layer, decay, x dt, B, C, the state
        input_output_aliases={6: 0},
        name="ssm_update",
        interpret=interpret,
    )(
        advancing.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1), decay.astype(f32),
        xdt.astype(f32).transpose(0, 2, 1), bmat.astype(f32), cmat.astype(f32), state,
    )
    return new, y.transpose(0, 2, 1)


def make_ssm_update_impl(interpret: bool | None = None):
    """Adapter with the ``paged_decode_forward(ssm_impl=...)`` signature:
    (state ``[Lm, B, H, P, N]``, layer, advancing, decay, x dt, B, C) → (state,
    y)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return functools.partial(ssm_update, interpret=interpret)
