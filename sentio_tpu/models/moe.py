"""Mixture-of-Experts decoder: Llama geometry with routed SwiGLU experts.

The reference delegates all model compute to hosted APIs (SURVEY.md §0) and
has no model families of its own; this family exists so the framework's
generator seam can serve sparse models at the same per-token FLOP cost as a
much smaller dense model — the standard scale path on TPU pods.

Design (GShard/Switch-style, static shapes throughout — XLA-friendly):

* Each block keeps the Llama attention (reused from models/llama.py) and
  replaces the dense SwiGLU with ``n_experts`` SwiGLU experts plus a linear
  router. Top-``experts_per_token`` routing with renormalized gates.
* Dispatch/combine are one-hot einsums over a fixed per-expert capacity
  ``C = ceil(G·k/E · capacity_factor)`` — tokens over capacity are dropped
  (their residual stream passes through untouched), which keeps every shape
  static under jit.
* Expert parallelism is pure sharding: expert-indexed weights carry the
  ``ep`` mesh axis on their leading dim (MOE_EP_RULES in
  parallel/sharding.py), token activations stay on the data axes, and XLA
  lowers the dispatch/combine einsums to all_to_all-style collectives over
  ICI. No manual collectives here — mesh geometry is the comm layer.
* The router computes in float32 (softmax stability) and adds the Switch
  load-balance auxiliary loss so training keeps experts utilized.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from sentio_tpu.models import layers as L
from sentio_tpu.models import llama
from sentio_tpu.models.families import DecodeStep, Family
from sentio_tpu.models.llama import Cache, LlamaConfig, _attn, init_cache  # noqa: F401
from sentio_tpu.parallel.sharding import MOE_EP_RULES

Array = jax.Array


@dataclass(frozen=True)
class MoeConfig(LlamaConfig):
    n_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01

    @classmethod
    def tiny(cls) -> "MoeConfig":
        """CPU-test scale, byte-level vocab."""
        return cls(
            vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            mlp_dim=128, max_len=512, rope_theta=10_000.0,
            n_experts=4, experts_per_token=2,
        )


def init_moe(rng: Array, cfg: MoeConfig) -> dict:
    keys = iter(jax.random.split(rng, 2 + cfg.n_layers * 8))
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    params: dict = {
        "embed_tokens": L.embed_init(next(keys), cfg.vocab_size, cfg.dim),
        "lm_head": L.dense_init(next(keys), cfg.dim, cfg.vocab_size, with_bias=False),
        "final_norm": L.rmsnorm_init(cfg.dim),
    }

    def expert_stack(key, in_dim, out_dim):
        ws = [
            L.dense_init(k, in_dim, out_dim, with_bias=False)["kernel"]
            for k in jax.random.split(key, cfg.n_experts)
        ]
        return jnp.stack(ws)  # [E, in, out]

    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = {
            "attn_norm": L.rmsnorm_init(cfg.dim),
            "attn": {
                "wq": L.dense_init(next(keys), cfg.dim, cfg.dim, with_bias=False),
                "wk": L.dense_init(next(keys), cfg.dim, kv_dim, with_bias=False),
                "wv": L.dense_init(next(keys), cfg.dim, kv_dim, with_bias=False),
                "wo": L.dense_init(next(keys), cfg.dim, cfg.dim, with_bias=False),
            },
            "mlp_norm": L.rmsnorm_init(cfg.dim),
            "moe": {
                "router": L.dense_init(next(keys), cfg.dim, cfg.n_experts, with_bias=False),
                "w_gate": expert_stack(next(keys), cfg.dim, cfg.mlp_dim),
                "w_up": expert_stack(next(keys), cfg.dim, cfg.mlp_dim),
                "w_down": expert_stack(next(keys), cfg.mlp_dim, cfg.dim),
            },
        }
    return params


def expert_capacity(cfg: MoeConfig, n_tokens: int) -> int:
    import math

    per_expert = n_tokens * cfg.experts_per_token / cfg.n_experts
    return max(1, math.ceil(per_expert * cfg.capacity_factor))


def route_topk(
    logits: Array, k: int, capacity: int, valid: Optional[Array] = None
) -> tuple[Array, Array, Array]:
    """GShard-style top-k dispatch with fixed capacity.

    logits [G, E] (float32) → (dispatch [G, E, C] bool, combine [G, E, C]
    float32, aux scalar). Tokens beyond an expert's capacity in choice-
    priority order are dropped (combine weight 0). Gates of the kept choices
    are renormalized over the *selected* experts. ``valid`` [G] bool masks
    padding tokens out entirely: they take no capacity slots and contribute
    nothing to the load-balance aux statistics.
    """
    g, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vmask = (
        jnp.ones((g,), jnp.float32) if valid is None else valid.astype(jnp.float32)
    )

    remaining = probs
    counts = jnp.zeros((e,), jnp.float32)
    dispatch = jnp.zeros((g, e, capacity), bool)
    combine = jnp.zeros((g, e, capacity), jnp.float32)
    gate_total = jnp.zeros((g,), jnp.float32)

    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                  # [G]
        gate = jnp.take_along_axis(probs, idx[:, None], 1)[:, 0]
        # padding tokens choose nothing: zeroed one-hots take no buffer
        # positions and advance no expert counts
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32) * vmask[:, None]
        # position of each token within its chosen expert's buffer
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot) + counts[None, :]
        pos = (pos_in_expert * onehot).sum(-1)                # [G]
        keep = (pos < capacity) & (vmask > 0)
        pos_oh = jax.nn.one_hot(
            jnp.clip(pos, 0, capacity - 1).astype(jnp.int32), capacity,
            dtype=jnp.float32,
        )                                                     # [G, C]
        slot = onehot[:, :, None] * pos_oh[:, None, :]        # [G, E, C]
        slot = slot * keep[:, None, None]
        dispatch = dispatch | (slot > 0)
        combine = combine + slot * gate[:, None, None]
        gate_total = gate_total + gate * keep
        counts = counts + onehot.sum(0)
        remaining = remaining * (1.0 - onehot)

    # renormalize kept gates so each token's expert mix sums to 1
    combine = combine / jnp.maximum(gate_total[:, None, None], 1e-9)

    # Switch aux loss over REAL tokens only: E * sum_e (fraction ASSIGNED to
    # e, pre-drop — capacity clipping must not cap the imbalance signal) *
    # (mean router prob of e)
    n_valid = jnp.maximum(vmask.sum(), 1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    mean_prob = (probs * vmask[:, None]).sum(0) / n_valid
    aux = (frac * mean_prob).sum() * e
    return dispatch, combine, aux


def moe_mlp(
    mp: dict, cfg: MoeConfig, x: Array, pad_mask: Optional[Array] = None
) -> tuple[Array, Array]:
    """Routed SwiGLU over x [B, T, D] → (out [B, T, D], aux loss scalar).
    ``pad_mask`` [B, T] keeps padding tokens from consuming expert capacity
    or skewing the load-balance statistics."""
    dt = cfg.jdtype
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    capacity = expert_capacity(cfg, b * t)

    logits = L.dense(mp["router"], flat, jnp.float32)          # [G, E] f32
    valid = None if pad_mask is None else pad_mask.reshape(b * t)
    dispatch, combine, aux = route_topk(
        logits, cfg.experts_per_token, capacity, valid
    )

    # dispatch tokens to per-expert buffers: [E, C, D]
    expert_in = jnp.einsum(
        "gec,gd->ecd", dispatch.astype(dt), flat.astype(dt)
    )
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, mp["w_gate"].astype(dt)))
    up = jnp.einsum("ecd,edf->ecf", expert_in, mp["w_up"].astype(dt))
    expert_out = jnp.einsum("ecf,efd->ecd", gate * up, mp["w_down"].astype(dt))

    out = jnp.einsum("gec,ecd->gd", combine.astype(dt), expert_out)
    return out.reshape(b, t, d), aux


# --------------------------------------------------- the share of a layer


def routed_scores(logits: Array, gate_fn: str) -> Array:
    """Router logits [G, E] (float32) → the scores a token ranks ALL experts
    by: ``sigmoid`` of each logit or a ``softmax`` over them."""
    if gate_fn == "sigmoid":
        return jax.nn.sigmoid(logits)
    if gate_fn == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    raise ValueError(f"gate_fn must be 'sigmoid' or 'softmax', got {gate_fn!r}")


# The grouped matmul's tiles. ROWS (a pair of token and pick is a row): the
# kernel computes a whole row tile for every expert that has a row in it, so
# the tile follows the pairs an expert sees under even routing — a decode
# step's two or three want 32 rows, not 256 (at 256 the MXU work of a step,
# 1 GFLOP a weight tile, takes as long as streaming the tile) — between
# ``_GMM_ROWS_MIN`` and ``_GMM_ROWS_MAX``. K and N of one ``[K, N]`` expert
# matrix: the weights stream, so the tile is the largest the matrix and the
# VMEM a kernel is given unasked allow (:func:`expert_tile`; it was one
# constant, ``(4096, 512)``, swept at 4096 x 4096 experts alone, until PR 43).
# ``python -m sentio_tpu.eval.expert_mlp_timing`` times the candidates at
# given widths; PERF.md section 5 has its table at the three families'.
_GMM_ROWS_MIN, _GMM_ROWS_MAX = 32, 256
_GMM_VMEM = 16 * 2 ** 20   # bytes of scoped VMEM a Pallas call gets on the chip without asking


def row_tile(pairs: int, n_experts: int) -> int:
    """Rows a tile for ``pairs`` rows routed evenly over ``n_experts``: the
    next power of two over an expert's share, inside the two bounds."""
    share = max(-(-pairs // n_experts), 1)
    return min(max(1 << (share - 1).bit_length(), _GMM_ROWS_MIN), _GMM_ROWS_MAX)


def tile_vmem(rows: int, tk: int, tn: int, lhs_item: int = 2, rhs_item: int = 2) -> int:
    """Bytes of VMEM the grouped matmul holds at a ``[rows, tk] x [tk, tn]``
    tile: the weight tile twice (the next one arrives while this one is
    multiplied), the rows' block twice and a third time as the value the
    kernel loads (the v5e compiler keeps a block larger than its registers in
    VMEM: a ``[32, 5120] x [5120, 768]`` tile is refused at 16.04 MiB where
    two copies would leave it at 15.8), the output block twice, and the
    float32 accumulator. Held against every tile the v5e compiler took or
    refused in PR 43 (``tests/test_chip_compile.py``)."""
    return 2 * tk * tn * rhs_item + 3 * rows * tk * lhs_item + 2 * rows * tn * lhs_item + 4 * rows * tn


def expert_tile(k: int, n: int, rows: int, lhs_item: int = 2, rhs_item: int = 2) -> tuple[int, int]:
    """The ``(tk, tn)`` of a ``[K, N]`` expert matrix under a row tile of
    ``rows``, from the shapes alone. The contraction stays WHOLE wherever a
    ``K x 128``-lane slab fits ``_GMM_VMEM`` — a split contraction fetches an
    expert's slab again on every visit and a tile that does not divide K
    masks its rest on the VPU every step — and is halved, on a divisor, only
    while it does not. ``tn`` is then the widest multiple of 128 lanes that
    divides N and fits: the whole matrix where it fits, one contiguous DMA an
    expert. A matrix narrower than 128 lanes, or no multiple of them, is its
    own tile (the contraction is then what gives way: at ``[2688, 1856]`` a
    third of K)."""
    lanes = [d for d in range(128, n + 1, 128) if n % d == 0] or [n]

    def fits(tk: int) -> bool:
        return tile_vmem(rows, tk, lanes[0], lhs_item, rhs_item) <= _GMM_VMEM

    tk = k
    while not fits(tk) and tk % 256 == 0:
        tk //= 2
    if not fits(tk):
        # a K with no half on a lane multiple (2,688 = 21 x 128): its largest
        # divisor of whole 128s that fits
        tk = max((d for d in range(128, tk, 128) if tk % d == 0 and fits(d)), default=tk)
    fit = [d for d in lanes if tile_vmem(rows, tk, d, lhs_item, rhs_item) <= _GMM_VMEM]
    return tk, max(fit, default=lanes[0])


def lane_padded(mp: dict) -> dict:
    """A routed layer's tree with its experts' WIDTH (``w_up`` / ``w_gate``
    ``[E, D, F]``, ``w_down`` ``[E, F, D]``; ``shared`` the same) zero-padded
    to the next multiple of a tile's 128 lanes, where it is wider than one
    tile and no multiple: at ``F`` = 1,856 = 14.5 tiles the chip's compiler
    copies a whole stack into a tiled layout at the head of EVERY program
    that reads it (0.64 GB a routed layer read and written, my compile for a
    described v5e, PR 44). The padded columns of ``w_up`` give ``act(0) = 0``
    and meet zero rows of ``w_down``: the same numbers. ``mp`` itself where
    nothing is padded (the three families before it: 768, 1,536, 12,288)."""
    width = mp["w_down"].shape[-2]
    pad = -width % 128
    shared = lane_padded(mp["shared"]) if "shared" in mp else None
    if width <= 128 or not pad:
        return mp if shared is mp.get("shared") else {**mp, "shared": shared}
    wider = jnp.pad if isinstance(mp["w_down"], jax.Array) else np.pad
    out = {**mp, "w_down": wider(mp["w_down"], ((0, 0), (0, pad), (0, 0)))}
    for name in ("w_gate", "w_up"):
        if name in mp:
            out[name] = wider(mp[name], ((0, 0), (0, 0), (0, pad)))
    if shared is not None:
        out["shared"] = shared
    return out


def expert_matmul(lhs: Array, rhs: Array, sizes: Array, rows: int = _GMM_ROWS_MAX,
                  interpret: bool = False) -> Array:
    """Grouped matmul ``lhs [M, K]`` (rows sorted by expert, ``sizes [E]``
    of them each, the rest belonging to none; ``M`` a multiple of the row
    tile ``rows``) x ``rhs [E, K, N]`` → ``[M, N]`` in ``lhs``'s dtype,
    summed in float32: the megablox kernel
    (``jax.experimental.pallas.ops.tpu.megablox``), which visits the row
    tiles of the experts that HAVE rows and no other — an expert nothing was
    routed to is not read, and a row past the last group is not computed (it
    comes back as whatever the buffer held: the caller masks it). In a device
    trace each call is one ``gmm`` custom call (the kernel's own jit name)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tile = (rows, *expert_tile(lhs.shape[1], rhs.shape[2], rows, lhs.dtype.itemsize, rhs.dtype.itemsize))
    return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=tile,
               interpret=interpret)


def expert_tiles(mp: dict, cfg, tokens: int) -> dict:
    """What :func:`expert_layer` over ``tokens`` tokens hands the grouped
    matmul for each of a routed layer's three matrices (``mp``: the layer's
    tree, or its shapes): the tile ``[rows, tk, tn]`` and the grid steps an
    expert with rows in ONE row tile costs, ``ceil(K / tk) * ceil(N / tn)``.
    Decided when the program is traced, so the engine states it once
    (``stats()["expert_tiles"]``, ``/info``)."""
    rows = row_tile(tokens * cfg.experts_per_token, cfg.n_experts)
    item = jnp.dtype(cfg.jdtype).itemsize          # the layer casts rows and matrices to it
    tiles = {}
    for name in ("w_gate", "w_up", "w_down"):
        if name not in mp:   # a family of ungated experts has two matrices
            continue
        _, k, n = mp[name].shape
        tk, tn = expert_tile(k, n, rows, item, item)
        tiles[name] = {"tile": [rows, tk, tn], "steps_per_expert": -(-k // tk) * -(-n // tn)}
    return tiles


def grouped_matmul(lhs: Array, rhs: Array, sizes: Array, rows: int = _GMM_ROWS_MAX) -> Array:
    """:func:`expert_matmul` on a TPU; ``jax.lax.ragged_dot`` (the same
    products, XLA's own lowering) elsewhere — selected by backend, like the
    attention kernels."""
    if jax.default_backend() == "tpu":
        return expert_matmul(lhs, rhs, sizes, rows)
    return jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=jnp.float32).astype(lhs.dtype)


def group_limited(scores: Array, n_group: int, topk_group: int) -> tuple[Array, Array]:
    """Scores [G, E] over experts that lie in ``n_group`` equal groups → (the
    scores with every expert outside a token's best ``topk_group`` groups
    zeroed, those groups [G, topk_group] int32). A group's score is the MAX of
    its experts' (``group_limited_greedy``): a token's picks then reach at
    most ``topk_group`` of the devices that hold a group each."""
    g, e = scores.shape
    best = scores.reshape(g, n_group, e // n_group).max(-1)
    _, groups = jax.lax.top_k(best, topk_group)
    kept = jnp.zeros((g, n_group), bool).at[jnp.arange(g)[:, None], groups].set(True)
    return jnp.where(jnp.repeat(kept, e // n_group, axis=1), scores, 0.0), groups.astype(jnp.int32)


def _relu2(x: Array) -> Array:
    """``relu(x)^2``: what an UNGATED expert puts between its two matrices."""
    return jnp.square(jax.nn.relu(x))


def expert_layer(
    mp: dict, cfg, x: Array, valid: Optional[Array] = None
) -> tuple[Array, ...]:
    """The routed-expert layer as ONE chip of a deployment runs it:
    x [B, T, D] → (out [B, T, D], picks [B, T, k] int32, counts [4] int32).

    Every token is routed over ALL ``cfg.n_experts`` experts (the router is
    as wide as published): ``cfg.gate_fn`` scores, the ``experts_per_token``
    largest picked — among all experts, or with ``cfg.n_group`` over one
    inside the token's best ``cfg.topk_group`` groups (:func:`group_limited`;
    such a family gets those groups ``[B, T, topk_group]`` as a fourth
    result). Where the family has an expert bias (``mp["bias"] [E]``,
    ``models/lfm2_moe.py``) the picks are the largest of ``score + bias``
    and the bias goes no further. A pick's gate is its score, renormalised
    over the picks where ``cfg.norm_topk_prob`` (over their sum plus
    ``cfg.norm_topk_eps`` in a family that states one), times
    ``cfg.routed_scaling_factor``. Of ``Σ w_e
    F_e(x)`` this computes the part whose expert is HELD here —
    ``mp["w_gate"]``, ``w_up`` ``[experts_held, D, F]`` and ``w_down``
    ``[experts_held, F, D]`` are experts ``expert_offset ..`` — and leaves
    the others' part out: what seven absent chips would add is no part of
    this program, nor is their traffic. With every expert held that IS the
    layer. A family of UNGATED experts (``models/nemotron_h.py``) has no
    ``w_gate``, there or in ``shared``: an expert is ``W_down relu(W_up x)^2``,
    two grouped matmuls. ``mp["shared"]`` (stacks of ``n_shared_experts`` experts every
    chip holds) adds their AVERAGE or their SUM (``cfg.shared_combine``:
    ``mean`` / ``sum``), once.

    Nothing is dropped at any load: the pairs of token and pick are sorted
    by expert and go through three grouped matmuls whose cost is the pairs
    routed here — no capacity, no ``[tokens, experts, capacity]`` tensor. A
    decode step reads each expert its rows picked once, and the others not
    at all. ``valid [B, T]`` keeps pad positions and rows that do not
    advance out of the experts (their picks are still reported).

    ``counts``: pairs routed (valid tokens x picks), pairs held here, held
    experts, held experts that at least one pair touched — the engine's
    ``sentio_tpu_moe_*`` counters, summed on the device.
    """
    dt = cfg.jdtype
    b, t, d = x.shape
    g, k, held = b * t, cfg.experts_per_token, cfg.experts_held
    flat = x.reshape(g, d).astype(dt)
    ok = jnp.ones((g,), bool) if valid is None else valid.reshape(g)

    with jax.named_scope("moe.route"):
        logits = L.dense(mp["router"], flat, jnp.float32)           # [G, E] f32
        scores, groups = routed_scores(logits, cfg.gate_fn), None
        if cfg.n_group > 1:
            with jax.named_scope("moe.groups"):
                scores, groups = group_limited(scores, cfg.n_group, cfg.topk_group)
        if "bias" in mp:
            # a selection-only bias: it decides the picks and is no part of a gate
            _, picks = jax.lax.top_k(scores + mp["bias"].astype(jnp.float32), k)
            top = jnp.take_along_axis(scores, picks, axis=-1)
        else:
            top, picks = jax.lax.top_k(scores, k)
        gates = top
        if cfg.norm_topk_prob:                                      # over the picks
            total, eps = top.sum(-1, keepdims=True), getattr(cfg, "norm_topk_eps", 0.0)
            gates = top / (total + eps if eps else total)           # no eps: the same program as ever
        if cfg.routed_scaling_factor != 1.0:
            gates = gates * cfg.routed_scaling_factor
        local = picks - cfg.expert_offset
        here = (local >= 0) & (local < held) & ok[:, None]          # [G, k]
        # a pair's group: its expert's index here, or ``held`` for none
        group = jnp.where(here, local, held).reshape(g * k)
        tile = row_tile(g * k, cfg.n_experts)
        rows = -(-g * k // tile) * tile                              # whole row tiles
        group = jnp.pad(group, (0, rows - g * k), constant_values=held)
        order = jnp.argsort(group, stable=True)                     # held pairs first, by expert
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        n_here = sizes.sum()

    with jax.named_scope("moe.experts"):
        token = jnp.minimum(order // k, g - 1)                      # a pad pair reads the last token
        xs = flat[token]                                            # [rows, D]
        up = grouped_matmul(xs, mp["w_up"].astype(dt), sizes, tile)
        hidden = jax.nn.silu(grouped_matmul(xs, mp["w_gate"].astype(dt), sizes, tile)) * up \
            if "w_gate" in mp else _relu2(up)
        ys = grouped_matmul(hidden, mp["w_down"].astype(dt), sizes, tile)  # [rows, D]
        ys = jnp.where((jnp.arange(rows) < n_here)[:, None], ys, 0)  # past the groups: not computed
        back = jnp.argsort(order)[: g * k]                           # pair → its sorted row
        weight = jnp.where(here, gates, 0.0).astype(jnp.float32)
        out = (ys[back].reshape(g, k, d).astype(jnp.float32) * weight[..., None]).sum(1)

    if "shared" in mp:
        with jax.named_scope("moe.shared"):
            sp = mp["shared"]
            up = jnp.einsum("gd,sdf->gsf", flat, sp["w_up"].astype(dt))
            hidden = jax.nn.silu(jnp.einsum("gd,sdf->gsf", flat, sp["w_gate"].astype(dt))) * up \
                if "w_gate" in sp else _relu2(up)
            shared = jnp.einsum("gsf,sfd->gd", hidden, sp["w_down"].astype(dt),
                                preferred_element_type=jnp.float32)
            out = out + (shared / cfg.n_shared_experts if cfg.shared_combine == "mean" else shared)

    counts = jnp.stack([ok.sum() * k, n_here, jnp.asarray(held, jnp.int32),
                        (sizes > 0).sum()]).astype(jnp.int32)
    result = (out.astype(dt).reshape(b, t, d), picks.reshape(b, t, k).astype(jnp.int32), counts)
    if groups is not None:   # a second thing the family decides by rank
        result += (groups.reshape(b, t, -1),)
    return result


def moe_forward(
    params: dict,
    cfg: MoeConfig,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
) -> tuple[Array, Optional[Cache], Array]:
    """ids [B, T] → (logits [B, T, vocab] f32, cache, total aux loss).

    Prefill/decode (cache + positions) semantics match models/llama.py
    ``llama_forward``, but the return adds a trailing router-aux scalar the
    training loss consumes — serving code that expects the two-tuple
    contract uses :func:`moe_serving_forward`, which drops it.
    """
    dt = cfg.jdtype
    b, t = ids.shape
    if cache is not None:
        cache = dict(cache)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    rope_len = cache["k"].shape[2] if cache is not None else max(t, cfg.max_len)
    cos, sin = L.rope_frequencies(cfg.head_dim, rope_len, cfg.rope_theta)

    x = L.embed(params["embed_tokens"], ids, dt)
    aux_total = jnp.zeros((), jnp.float32)
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        attn_out, cache = _attn(
            lp["attn"], cfg, L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps),
            positions, cos, sin, i, cache, cache_index, pad_mask, attn_fn,
        )
        x = x + attn_out
        moe_out, aux = moe_mlp(
            lp["moe"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps), pad_mask
        )
        x = x + moe_out
        aux_total = aux_total + aux
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.dense(params["lm_head"], x, dt)
    return logits.astype(jnp.float32), cache, aux_total


def moe_serving_forward(
    params: dict,
    cfg: MoeConfig,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
) -> tuple[Array, Optional[Cache]]:
    """Two-tuple adapter matching ``llama_forward``'s serving contract
    (runtime/paged.py unpacks ``logits, cache``); the
    router aux loss is a training-only signal and is dropped here."""
    logits, cache, _ = moe_forward(
        params, cfg, ids, positions, cache, cache_index, pad_mask, attn_fn
    )
    return logits, cache


def decode_layer(lp: dict, cfg: MoeConfig, i: int, x: Array, step: DecodeStep) -> Array:
    """``models/llama.py``'s block with the routed SwiGLU as its second half;
    frozen and free rows are masked out of routing: they claim no capacity."""
    def routed(lp, cfg, xm, step):
        return moe_mlp(lp["moe"], cfg, xm, step.valid)[0]

    return llama.decode_layer(lp, cfg, i, x, step, ffn=routed)


FAMILY = Family(
    name="moe", config=MoeConfig, init=init_moe, forward=moe_serving_forward, init_cache=init_cache,
    decode_layer=decode_layer, head=llama.decode_head, decode_tables=llama.FAMILY.decode_tables,
    mesh_rules=MOE_EP_RULES)


def moe_loss(params: dict, cfg: MoeConfig, ids: Array, mask: Array) -> Array:
    """Next-token cross-entropy + router aux — the ep train-step objective."""
    logits, _, aux = moe_forward(params, cfg, ids[:, :-1], pad_mask=mask[:, :-1])
    targets = ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[:, :, None], axis=-1)[..., 0]
    weights = mask[:, 1:].astype(jnp.float32)
    ce = (nll * weights).sum() / jnp.maximum(weights.sum(), 1.0)
    return ce + cfg.router_aux_weight * aux
