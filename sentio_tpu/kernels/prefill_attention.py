"""Pallas flash attention for a prefill segment over a prior.

The prefill programs (``runtime/paged.py``: ``prefill_scatter``,
``prior_prefill_scatter``) attend a segment of ``T`` new tokens over a
contiguous cache that holds each row's prior at positions ``[0, q_start[b])``
and the segment itself from ``q_start[b]`` on. The XLA forms of that
(``models/layers.py::attention`` behind ``repeat_kv``, ``models/cohere2_moe.py
::windowed_attention``, ``models/deepseek_v2.py::expanded_attention``) write a
float32 ``[.., T, S]`` score tensor to HBM, mask it, softmax it and read it
again, over the whole prior BUCKET. This kernel keeps the scores in VMEM:

* grid ``(B, Hkv, T / block_q, S / block_k)``, the key dimension sequential,
  carrying the running max, the normaliser and a float32 accumulator (online
  softmax); QK and PV on the MXU in the inputs' dtype with float32
  accumulation, the weights cast to the values' dtype before PV;
* grouped queries by INDEX: the ``H / Hkv`` query heads of one kv head come
  as one block of rows and meet that head's keys once — nothing is repeated;
* causal by POSITION: row ``b``'s query ``i`` sits at ``q_start[b] + i`` and
  sees key ``j <= q_start[b] + i`` (and ``j > q_start[b] + i - window`` where a
  static ``window`` is given). ``q_start`` is scalar-prefetched, so the key
  block's index map ends the walk at the row's own last block — blocks past it
  are neither fetched nor computed — and blocks wholly under the first query
  take no per-element mask;
* a second score term for a family whose keys are wider than its values
  (``models/deepseek_v2.py``): ``q_pe [B, T, H, R]`` against ONE rotated key a
  position ``k_pe [B, S, R]``, whose block ignores the head.

On the CPU the same kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sentio_tpu.kernels.paged_attention import ConfigWindow

# a masked score: far below any real one, and finite, so that a row whose
# first block shows it nothing carries no NaN (what it summed meanwhile is
# wiped by ``alpha`` = exp(MASKED - real) = 0 at its first real key)
MASKED = -0.7 * float(np.finfo(np.float32).max)
# rows of one score tile (a kv head's query heads x block_q), its keys, and
# the score rows of one grid step (the kv heads a key block brings x ROWS).
# Measured on a v5e (PERF.md section 5, PR 41): key blocks of 1024 take 0.58 of
# the time of 512 and 0.31 of 256 at 32 / 8 heads over a 40-page prior (the
# accumulator's rescale and a step's fixed cost are paid once a block); 2048
# wastes the part of a block past a 16-page prior; the rows move it by 5 %
ROWS, BLOCK_K, STEP_ROWS = 1024, 1024, 2048

# like a window (``ConfigWindow``), the softmax scale is a constant of a model's
# CONFIGURATION (``cfg.softmax_scale``): static in the kernel, one variant a value
ConfigScale = Optional[float]

__all__ = ["prefill_attention", "prefill_untiled", "make_prefill_attn_fn"]


def _kernel(
    qs_ref,    # [B] int32 in SMEM: each row's first query position
    *refs,
    second: bool,
    heads: int,
    group: int,
    block_q: int,
    block_k: int,
    n_queries: int,
    n_keys: int,
    sm_scale: float,
    window: Optional[int],
):
    if second:
        q_ref, k_ref, v_ref, q2_ref, k2_ref, o_ref, m_ref, l_ref, acc_ref, rel_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, rel_ref = refs
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    dv, d = acc_ref.shape[-1], q_ref.shape[-1]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASKED)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # key column less query row of a score tile, made once a block of
        # queries: a masked step then pays one compare and one select
        shape = rel_ref.shape
        rel_ref[:] = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                      - jax.lax.broadcasted_iota(jnp.int32, shape, 0) % block_q)

    q_lo = qs_ref[b] + qi * block_q            # this block's first query position ...
    q_hi = jnp.minimum(q_lo + block_q, qs_ref[b] + n_queries) - 1   # ... and its last real one
    first, last = _walk(q_lo, q_hi, block_k, n_keys, window)
    kb = first + ki                            # the key block this step holds
    k_lo = kb * block_k
    k_hi = k_lo + block_k - 1
    clear = k_hi <= q_lo                       # every key seen by every query ...
    if window is not None:
        clear &= k_lo > q_hi - window          # ... and inside every query's window

    def block(masked: bool):
        if masked:   # one mask for the block's kv heads: key position <= query position
            rel = rel_ref[:]
            seen = rel <= q_lo - k_lo
            if window is not None:
                seen &= rel > q_lo - k_lo - window
            # a key no query of this block sees has weight 0, and 0 x NaN is
            # NaN: what lies there (the unwritten tail, the part of the last
            # block past the array) is kept out of PV
            v_ok = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0) <= q_hi
        for j in range(heads):
            s = jax.lax.dot_general(q_ref[j], k_ref[:, j * d:(j + 1) * d], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if second:
                s += jax.lax.dot_general(q2_ref[j], k2_ref[:], (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
            s *= sm_scale                                        # [rows, block_k]
            v = v_ref[:, j * dv:(j + 1) * dv]
            if masked:
                s = jnp.where(seen, s, MASKED)
                v = jnp.where(v_ok, v, jnp.zeros_like(v))
            m_prev = m_ref[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[j] = acc_ref[j] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[j] = m_new

    run = kb <= last
    pl.when(run & clear)(functools.partial(block, False))
    pl.when(run & jnp.logical_not(clear))(functools.partial(block, True))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        for j in range(heads):
            # no row's normaliser is 0: a query sees its own key at least
            out = (acc_ref[j] / l_ref[j]).astype(o_ref.dtype)
            for g in range(group):   # rows (g, i) -> the columns of query head (j, g) at query i
                at = (j * group + g) * dv
                o_ref[:, at:at + dv] = out[g * block_q:(g + 1) * block_q]


def _walk(q_lo, q_hi, block_k: int, n_keys: int, window: Optional[int]):
    """(first, last) key block a query block at positions ``q_lo..q_hi``
    reads: the last is the one that holds ``q_hi`` (never past the array), the
    first the one that holds the oldest key its window keeps."""
    last = jnp.minimum(q_hi, n_keys - 1) // block_k
    first = 0 if window is None else jnp.maximum(q_lo - window + 1, 0) // block_k
    return first, last


def prefill_untiled(head_dim: int, v_head_dim: int) -> str:
    """Why the chip cannot take a head of its own as a block of the ``[..,
    H * D]`` rows q, k and v are read as — "" where it can. A head's columns
    must be whole lane tiles (128)."""
    if head_dim % 128 or v_head_dim % 128:
        return (f"head widths {head_dim} / {v_head_dim} are not multiples of 128: a head is "
                "no block of columns the DMA can bring")
    return ""


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "window", "block_q", "block_k", "interpret"))
def prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_start: jax.Array,
    q_pe: Optional[jax.Array] = None,
    k_pe: Optional[jax.Array] = None,
    *,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """q ``[B, T, H, D]`` at positions ``q_start[b] + i`` over k ``[B, S, Hkv,
    D]``, v ``[B, S, Hkv, Dv]`` that sit AT their positions → ``[B, T, H,
    Dv]``. With ``q_pe [B, T, H, R]`` and ``k_pe [B, S, R]`` the score is ``q ·
    k[h // group] + q_pe · k_pe``. ``sm_scale`` defaults to ``D ** -0.5``.
    Block sizes follow ``T``, ``S`` and the head counts where not given."""
    b, t, h, d = q.shape
    s, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    group = h // hkv
    second = q_pe is not None
    if sm_scale is None:
        sm_scale = float(d) ** -0.5
    # the query heads of one kv head are one score tile's rows: a block of
    # queries is as long as ROWS allow, a multiple of the bf16 sublane tile
    if block_q is None:
        block_q = max(min(ROWS // group, 512) // 16 * 16, 16)   # (a group of 20 would ask for 51)
    block_q = min(block_q, -(-t // 16) * 16)
    block_k = min(block_k or BLOCK_K, -(-s // 16) * 16)
    # keys and values are read as ``[B, S, Hkv * D]`` rows: a block holds the
    # columns of ``heads`` kv heads, served one after another — as many as
    # keep a grid step's work near STEP_ROWS score rows (a step has a fixed
    # cost, and every head served is unrolled code)
    heads = max(n for n in range(1, hkv + 1)
                if hkv % n == 0 and n * group * block_q <= max(STEP_ROWS, group * block_q))
    nq, nk = -(-t // block_q), -(-s // block_k)
    t_pad, rows = nq * block_q, group * block_q

    def rows_of(x):
        """[B, T, H, W] → [B, Hkv, nq, group * block_q, W]: a kv head's query
        heads a block of queries under each other (one pass over the queries,
        which are T long)."""
        w = x.shape[-1]
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
        x = x.reshape(b, nq, block_q, hkv, group, w).transpose(0, 3, 1, 4, 2, 5)
        return x.reshape(b, hkv, nq, rows, w)

    def q_map(bi, hi, qi, ki, qs):
        return bi, hi, qi, 0, 0

    def key_block(bi, qi, ki, qs):
        q_lo = qs[bi] + qi * block_q
        q_hi = jnp.minimum(q_lo + block_q, qs[bi] + t) - 1
        first, last = _walk(q_lo, q_hi, block_k, s, window)
        return jnp.minimum(first + ki, last)   # past the walk: the block held already

    def kv_map(bi, hi, qi, ki, qs):
        return bi, key_block(bi, qi, ki, qs), hi

    operands = [rows_of(q), k.reshape(b, s, hkv * d), v.reshape(b, s, hkv * dv)]
    in_specs = [
        pl.BlockSpec((None, heads, None, rows, d), q_map),
        pl.BlockSpec((None, block_k, heads * d), kv_map),
        pl.BlockSpec((None, block_k, heads * dv), kv_map),
    ]
    if second:
        r = q_pe.shape[-1]
        operands += [rows_of(q_pe), k_pe]
        in_specs += [
            pl.BlockSpec((None, heads, None, rows, r), q_map),
            pl.BlockSpec((None, block_k, r),     # ONE key a position, whatever the head
                         lambda bi, hi, qi, ki, qs: (bi, key_block(bi, qi, ki, qs), 0)),
        ]
    kernel = functools.partial(
        _kernel, second=second, heads=heads, group=group, block_q=block_q, block_k=block_k,
        n_queries=t, n_keys=s, sm_scale=float(sm_scale), window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv // heads, nq, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, block_q, heads * group * dv),
                                   lambda bi, hi, qi, ki, qs: (bi, qi, hi)),
            scratch_shapes=[
                pltpu.VMEM((heads, rows, 1), jnp.float32),    # running max
                pltpu.VMEM((heads, rows, 1), jnp.float32),    # normaliser
                pltpu.VMEM((heads, rows, dv), jnp.float32),   # output accumulator
                pltpu.VMEM((rows, block_k), jnp.int32),       # key column less query row
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t_pad, h * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="prefill_attention",
    )(jnp.broadcast_to(q_start.astype(jnp.int32), (b,)), *operands)
    return out[:, :t].reshape(b, t, h, dv)


def make_prefill_attn_fn(interpret: Optional[bool] = None):
    """The ``attn_fn`` the prefill forwards take where the engine chose the
    kernel (``runtime/paged.py``): ``fn(q, k, v, q_start, q_pe=None,
    k_pe=None, sm_scale=None, window=None) → [B, T, H, Dv]`` over the cache as
    ``_write_cache`` leaves it; ``q_start`` is the forward's ``cache_index`` (a
    scalar, or one a row). ``fn.takes_prior`` tells a forward that this one
    knows a prior (``kernels.flash_attn_fn`` and the ring do not)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def fn(q, k, v, q_start, q_pe=None, k_pe=None, *, sm_scale: ConfigScale = None,
           window: ConfigWindow = None):
        with jax.named_scope("attn.prefill"):
            return prefill_attention(q, k, v, jnp.asarray(q_start, jnp.int32), q_pe, k_pe,
                                     sm_scale=sm_scale, window=window, interpret=interpret)

    fn.takes_prior = True
    return fn
