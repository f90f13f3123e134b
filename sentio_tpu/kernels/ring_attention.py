"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Long-context support the reference entirely lacks (it truncates context to
~2000 tokens, /root/reference/src/core/graph/nodes.py:296-338 there;
SURVEY.md §5 "long-context — absent"). Here sequences shard over the ``sp``
axis and attention runs as a ring: each device holds its local Q shard
permanently, while K/V shards rotate around the ring via
``jax.lax.ppermute`` (XLA lowers it to ICI send/recv on TPU). After
``sp`` steps every Q block has seen every K/V block, with O(T/sp) activation
memory per device and compute/communication overlap left to XLA's scheduler.

Numerical form: the flash-attention online-softmax recurrence carried
ACROSS ring steps — running max ``m``, normalizer ``l``, fp32 accumulator —
so the result is exactly softmax(QKᵀ)V regardless of arrival order.

Causality with a sharded sequence: chunk ``c`` (its global offset =
src_index · T_local) is fully visible to later chunks, causal-masked on the
diagonal chunk, and fully masked for earlier chunks (contributes
exp(-inf) = 0 but still rides the ring to keep the permute schedule static).

``ring_attention`` is the shard_map-internal function (use inside your own
shard_map with axis ``sp``); :func:`ring_attention_sharded` wraps it for
standalone [B, T, H, D] arrays on a mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sentio_tpu.parallel.mesh import AXIS_DP, AXIS_SP

NEG_INF = float(np.finfo(np.float32).min)

__all__ = ["ring_attention", "ring_attention_sharded"]


def _chunk_attend(q, k, v, q_offset, k_offset, causal: bool, sm_scale: float,
                  kv_lens=None):
    """Scores of local q [B,T,H,D] against one k/v chunk, with the global
    causal mask derived from the two chunk offsets. ``kv_lens`` [B] masks
    keys at global positions >= the row's true length (right-padded
    batches). Returns the raw masked score matrix [B,H,T,S] in fp32; the
    online-softmax recurrence over chunks lives in the caller's ring step."""
    s = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    sk = k.shape[1]
    k_pos = k_offset + jnp.arange(sk)[None, :]
    if causal:
        t = q.shape[1]
        q_pos = q_offset + jnp.arange(t)[:, None]
        s = jnp.where((k_pos <= q_pos)[None, None, :, :], s, NEG_INF)
    if kv_lens is not None:
        valid = k_pos[0][None, :] < kv_lens[:, None]  # [B, S]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    return s


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_lens: Optional[jax.Array] = None,
    *,
    axis_name: str = AXIS_SP,
    causal: bool = True,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Inside shard_map: q/k/v are the LOCAL sequence shards [B, T_loc, H, D]
    (kv heads already expanded to H); ``kv_lens`` [B] (replicated) masks
    right-padding by GLOBAL key position. Returns the local output shard."""
    b, t_loc, h, d = q.shape
    sp = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = sm_scale if sm_scale is not None else 1.0 / float(np.sqrt(d))
    perm = [(i, (i + 1) % sp) for i in range(sp)]  # send k/v to the right

    q32 = q.astype(jnp.float32)
    m0 = jnp.full((b, h, t_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, t_loc, d), jnp.float32)

    def step(carry, step_idx):
        k_chunk, v_chunk, m, l, acc = carry
        # the chunk we hold at step i originated on device (my_idx - i) % sp
        src_idx = (my_idx - step_idx) % sp
        s = _chunk_attend(
            q32, k_chunk.astype(jnp.float32), v_chunk.astype(jnp.float32),
            q_offset=my_idx * t_loc, k_offset=src_idx * t_loc,
            causal=causal, sm_scale=scale, kv_lens=kv_lens,
        )
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        safe = m_new > NEG_INF / 2
        p = jnp.exp(jnp.where(safe, s - m_new, NEG_INF))
        alpha = jnp.exp(jnp.where(safe, m - m_new, 0.0))
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum(
            "bhts,bshd->bhtd", p, v_chunk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc = acc * alpha + pv
        # rotate k/v around the ring (last rotation returns them home; XLA
        # overlaps it with the next step's compute where profitable)
        k_next = jax.lax.ppermute(k_chunk, axis_name, perm)
        v_next = jax.lax.ppermute(v_chunk, axis_name, perm)
        return (k_next, v_next, m_new, l, acc), None

    (_, _, _, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(sp)
    )
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, T_loc, H, D]


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    sp_axis: str = AXIS_SP,
    batch_axes: tuple[str, ...] = (AXIS_DP,),
) -> jax.Array:
    """Standalone entry: global [B, T, H, D] arrays, batch over dp, sequence
    over sp. T must divide by the sp axis size."""
    t = q.shape[1]
    sp = mesh.shape[sp_axis]
    if t % sp != 0:
        raise ValueError(f"sequence length {t} not divisible by sp={sp}")
    batch_spec = batch_axes[0] if len(batch_axes) == 1 else batch_axes
    spec = P(batch_spec, sp_axis, None, None)

    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=sp_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    q = jax.device_put(q, NamedSharding(mesh, spec))
    k = jax.device_put(k, NamedSharding(mesh, spec))
    v = jax.device_put(v, NamedSharding(mesh, spec))
    return fn(q, k, v)
