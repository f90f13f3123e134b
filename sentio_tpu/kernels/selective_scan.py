"""Pallas kernel for a selective scan: a state-space recurrence whose decay
is a number per channel AND state column, over one prefill segment.

    S_t = exp(D_t (x) A) * S_{t-1} + (D_t x_t) (x) B_t        S [N, inner] float32
    y_t = sum_n S_t[n] C_t[n]

No chunk of it is a matrix product, so the plain compiled form is a loop whose
every token is one or two small fusions (``models/jamba.py::selective_scan``):
20 thousand device operations a 512-token segment of 26 layers, which a
serving process pays in time — 326 µs a layer where 51 µs of bandwidth would
do — and which a profiler window over such ticks does not survive (PERF.md
section 6, PR 48: the window's 1.2 M device events never came back). This
kernel is ONE call a layer: grid ``(rows, inner / TILE, T / SNAP)``, the last
axis sequential; a ``[N, TILE]`` tile of the state stays in VMEM over the
whole segment while ``D``, ``D x`` and ``y`` stream through in blocks of
``SNAP`` tokens, the steps of a block unrolled, ``exp(D_t (x) A)`` made inside
the step. The state at the end of EVERY block of ``SNAP`` tokens is written
out (``[rows, T / SNAP, N, inner]``: 10 MB a row and layer at the published
widths), which is where a caller takes the boundaries it snapshots from.

On the CPU the same kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# channels a program holds: 16 x 1280 float32 is 20 vregs of state and 20 of A
TILE = 1280

__all__ = ["selective_scan_kernel", "scan_tile"]


def scan_tile(inner: int) -> int:
    """Channels a grid step holds: the widest multiple of 128 lanes that
    divides ``inner`` up to ``TILE``; a width that is no multiple of 128 whole."""
    if inner % 128:
        return inner
    return max(k for k in range(128, min(inner, TILE) + 1, 128) if inner % k == 0)


def _kernel(step_ref, dx_ref, a_ref, b_ref, c_ref, start_ref, y_ref, states_ref, s_ref, *, snap: int):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[:] = start_ref[:]

    s, a = s_ref[:], a_ref[:]
    for i in range(snap):
        # a row [1, TILE] against a column [N, 1]: the decay and what the token adds, [N, TILE]
        s = jnp.exp(step_ref[i:i + 1, :] * a) * s + dx_ref[i:i + 1, :] * b_ref[i]
        y_ref[i:i + 1, :] = jnp.sum(s * c_ref[i], axis=0, keepdims=True)
    s_ref[:] = s
    states_ref[:] = s


@functools.partial(jax.jit, static_argnames=("snap", "interpret"))
def selective_scan_kernel(x, step, a, bmat, cmat, start, *, snap: int, interpret: bool = False):
    """x and ``step`` ``[B, T, inner]`` (``step`` 0 at a pad position), ``a``
    ``[N, inner]`` negative, B and C ``[B, T, N]``, ``start`` ``[B, N,
    inner]``, all float32, ``T`` whole blocks of ``snap`` → (y ``[B, T,
    inner]``, the state after every block ``[B, T / snap, N, inner]``)."""
    b, t, inner = x.shape
    n = a.shape[0]
    tile = scan_tile(inner)
    rows = pl.BlockSpec((None, snap, tile), lambda bi, di, ti: (bi, ti, di))
    cols = pl.BlockSpec((None, snap, n, 1), lambda bi, di, ti: (bi, ti, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, snap=snap),
        grid=(b, inner // tile, t // snap),
        in_specs=[rows, rows, pl.BlockSpec((n, tile), lambda bi, di, ti: (0, di)), cols, cols,
                  pl.BlockSpec((None, n, tile), lambda bi, di, ti: (bi, 0, di))],
        out_specs=[rows, pl.BlockSpec((None, None, n, tile), lambda bi, di, ti: (bi, ti, 0, di))],
        out_shape=[jax.ShapeDtypeStruct((b, t, inner), jnp.float32),
                   jax.ShapeDtypeStruct((b, t // snap, n, inner), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(step, step * x, a, bmat[..., None], cmat[..., None], start)
