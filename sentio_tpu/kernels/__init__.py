"""Pallas/TPU kernels: flash attention (the encoders' bidirectional one and
the causal one of training), the prefill's flash attention over a prior, ring
(sequence-parallel) attention; the decode kernels (``paged_attention``,
``latent_attention``) are built by the engine from their own modules, and a
state-space family's (``ssm_update``, ``selective_scan``) are taken by the
engine or by the family's module from theirs.

Every kernel has an XLA counterpart (models/layers.py:attention) so the
whole framework runs on CPU; the kernels are SELECTED on TPU from what the
code can observe (backend, mesh, head counts) — a kernel that fails to
compile or run there is an error, never a reason to carry on with XLA.

Two adapter signatures reach a decoder's forward as ``attn_fn``:

* ``flash_attn_fn`` / ``make_mesh_attn_fn`` / ``make_ring_attn_fn``: ``(q, k,
  v, kv_lens) → [B, T, H, D]``, causal, keys spread to the query heads, the
  query block at position 0 (training, whole-prompt prefill under a mesh);
* ``make_prefill_attn_fn`` (``prefill_attention.py``; it carries
  ``takes_prior``): ``(q, k, v, q_start, q_pe, k_pe, sm_scale=, window=)``
  over the contiguous cache as it lies — grouped by index, causal by each
  row's own first position, the walk ending at the row's own prior, a second
  score term for keys wider than values, a static window. This is what
  ``runtime/paged.py`` binds into its ``forward_fn`` for the two prefill
  programs of every family, where it chooses the decode kernel.
"""

from __future__ import annotations

import jax

from sentio_tpu.kernels.flash_attention import attention_auto, flash_attention
from sentio_tpu.kernels.prefill_attention import make_prefill_attn_fn
from sentio_tpu.kernels.ring_attention import ring_attention, ring_attention_sharded

__all__ = [
    "flash_attention",
    "attention_auto",
    "make_prefill_attn_fn",
    "ring_attention",
    "ring_attention_sharded",
    "flash_attn_fn",
    "encoder_attn_fn",
    "make_ring_attn_fn",
    "make_mesh_attn_fn",
    "default_attn_fn",
    "default_encoder_attn_fn",
]


def flash_attn_fn(q, k, v, kv_lens=None):
    """Causal flash attention adapter for ``llama_forward(attn_fn=...)``."""
    interpret = jax.default_backend() != "tpu"
    return flash_attention(q, k, v, kv_lens, causal=True, interpret=interpret)


def encoder_attn_fn(q, k, v, kv_lens=None):
    """Bidirectional flash adapter for encoder forwards: right-padded keys
    are masked by ``kv_lens``, no causal constraint."""
    interpret = jax.default_backend() != "tpu"
    return flash_attention(q, k, v, kv_lens, causal=False, interpret=interpret)


def make_ring_attn_fn(axis_name: str):
    """Ring-attention adapter for use INSIDE shard_map over ``axis_name``
    (sequence axis). kv_lens masks right-padding by global key position."""

    def fn(q, k, v, kv_lens=None):
        return ring_attention(q, k, v, kv_lens, axis_name=axis_name, causal=True)

    return fn


def make_mesh_attn_fn(mesh, causal: bool = True):
    """Kernel attention that runs INSIDE shard_map over the mesh — the
    sharded replacement for the old "no kernels under a mesh" gate:

    * heads shard over ``tp`` (matching the Megatron column sharding of
      wq/wk/wv, so no resharding at the kernel boundary);
    * with sp > 1 the sequence shards over ``sp`` and the inner kernel is
      the ppermute ring (long-context path); otherwise each shard runs
      flash attention on its local heads;
    * batch shards over ``dp`` when divisible, else replicates (serving
      batches are small; training batches always divide).

    Returns an ``attn_fn(q, k, v, kv_lens)`` for multi-token causal blocks
    (prefill / training); encoders pass ``causal=False`` (sp must be 1).
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from sentio_tpu.parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP

    sp = mesh.shape[AXIS_SP]
    tp = mesh.shape[AXIS_TP]
    dp = mesh.shape[AXIS_DP]
    if sp > 1 and not causal:
        raise ValueError("sequence-parallel ring attention is causal-only")
    interpret = jax.default_backend() != "tpu"

    def fn(q, k, v, kv_lens=None):
        b, t, h, _ = q.shape
        if h % tp != 0 or t % sp != 0:
            # callers test divisibility before choosing this kernel; an
            # indivisible shape here is an error, never a quiet XLA path
            raise ValueError(f"heads {h} % tp {tp} or seq {t} % sp {sp} != 0")
        batch_axis = AXIS_DP if (dp > 1 and b % dp == 0) else None
        spec = P(batch_axis, AXIS_SP if sp > 1 else None,
                 AXIS_TP if tp > 1 else None, None)
        lens_spec = P(batch_axis)
        if kv_lens is None:
            kv_lens = jnp.full((b,), t, jnp.int32)

        if sp > 1:
            def inner(q, k, v, lens):
                return ring_attention(q, k, v, lens, axis_name=AXIS_SP,
                                      causal=True)
        else:
            def inner(q, k, v, lens):
                return flash_attention(q, k, v, lens, causal=causal,
                                       interpret=interpret)

        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(spec, spec, spec, lens_spec),
            out_specs=spec, check_vma=False,
        )(q, k, v, kv_lens)

    return fn


def default_attn_fn():
    """Flash on TPU, None (XLA fallback) elsewhere."""
    if jax.default_backend() == "tpu":
        return flash_attn_fn
    return None


def default_encoder_attn_fn():
    """Bidirectional flash on TPU, None (XLA fallback) elsewhere."""
    if jax.default_backend() == "tpu":
        return encoder_attn_fn
    return None


def select_encoder_attn_fn(mesh, n_heads: int):
    """THE policy for encoder attention kernels (embedder + cross-encoder —
    one definition so the sites cannot drift): no mesh → plain flash on TPU;
    mesh on TPU with sp == 1 and heads divisible by tp → flash inside
    shard_map; anything else → None (XLA attention under GSPMD)."""
    from sentio_tpu.parallel.mesh import AXIS_SP, AXIS_TP

    if mesh is None:
        return default_encoder_attn_fn()
    if (jax.default_backend() == "tpu" and mesh.shape[AXIS_SP] == 1
            and n_heads % mesh.shape[AXIS_TP] == 0):
        return make_mesh_attn_fn(mesh, causal=False)
    return None
