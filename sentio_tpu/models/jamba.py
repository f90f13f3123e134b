"""Decoder family ``jamba`` (AI21-Jamba2-3B): every layer a mixer AND a dense
SwiGLU — ``h <- h + Mixer(RMSNorm(h)); h <- h + MLP(RMSNorm(h))`` — the mixer
attention where ``i % attn_layer_period == attn_layer_offset`` and a Mamba-1
mixer everywhere else (13 to 1 as published). A final RMSNorm and a head TIED
to the embedding. No position embedding anywhere: the attention is
rotation-free and the Mamba layers are causal by construction.

    Mamba-1 (inner = mamba_expand x hidden, state N, Δ through a rank-R bottleneck, K taps):
        [x | z]     = u W_in                          inner | inner, no bias
        x           <- silu(depthwise causal conv over K taps (x) + b_conv)
        [r | B | C] = x W_x                           R | N | N, no bias
        r, B, C     <- RMSNorm_dt(r), RMSNorm_B(B), RMSNorm_C(C)      a weight each (Jamba's addition)
        D_t         = softplus(r W_dt + b_dt)         R -> inner; the bias float32, added in float32
        A           = -exp(A_log)                     a number per channel AND state column
        S_t         = exp(D_t (x) A) * S_{t-1} + (D_t * x_t) (x) B_t
        y_t         = S_t C_t + D * x_t
        out         = (y * silu(z)) W_out             no norm between
    attention: one KV head under every query head (multi-query), no bias, no rotation, causal

The decay ``exp(D_t[d] A[d, n])`` differs for every channel and every state
column, so — unlike ``models/nemotron_h.py``'s Mamba-2, whose decay is one
scalar a head — no chunk of the recurrence is a matrix product: it is a
SELECTIVE SCAN, elementwise over ``[inner, N]`` a token. On the chip prefill
runs it through ``kernels/selective_scan.py`` — ONE call a layer, the state in
VMEM over the segment; elsewhere (and as what that kernel is held to)
:func:`selective_scan` runs it as a loop over blocks of ``SCAN_BLOCK`` tokens
whose steps are unrolled inside one iteration, the state carried in float32
and ``exp(D_t (x) A)`` made inside the step: no ``[T, inner, N]`` tensor exists
either way (PERF.md section 6, PR 48, has the chip's readings of the loop in
blocks of 1 to 128, of an associative scan and of the kernel, and why the loop
could not stay: :func:`scan_segment`).

WHAT A SEQUENCE CARRIES. An attention layer leaves a key and a value a token in
the page pool (the pool's layer axis counts the attention layers:
``attn_index``). A Mamba layer keeps a STATE whatever the length: ``S`` in
float32 — held ``[N, inner]``, the published ``[inner, N]`` TRANSPOSED, so that
the channel axis lies on the chip's 128 lanes (sixteen columns minor would pad
every tile eight times over) — and the last ``K - 1`` columns of ``x`` before
the convolution. ``runtime/paged.py`` keeps both per decode slot and in the
bounded pool of snapshots the radix cache hands to page boundaries, as it does
for ``models/nemotron_h.py``; ``A_log`` is held ``[N, inner]`` for the same
reason.

:func:`mamba1_segment` is prefill: a right-padded segment from a carried state
(zeros at position 0, a snapshot behind a radix hit, the slot's own behind an
earlier segment). A pad position has ``D = 0``, which neither decays nor adds,
so the state after the segment IS the state after the row's own tokens; the
state at every boundary asked for comes out of the scan.
:func:`mamba1_step` is decode: the one-token update of the slot's state — on the
chip through ``kernels/ssm_update.py::selective_update``, ONE call a layer over
the rows that advance, the state left in HBM and written in place, where that
module's ``ssm_update_path`` says so of the state; in XLA everywhere else.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from sentio_tpu.kernels.selective_scan import selective_scan_kernel
from sentio_tpu.models import layers as L
from sentio_tpu.models.families import DecodeStep, Family, StateBeside
from sentio_tpu.models.lfm2_moe import _attn, head_logits, recurrent_refusals
from sentio_tpu.models.llama import Cache, LlamaConfig, _mlp, qkv_proj
# the state at position 0 and the contiguous cache of a prefill (K and V of the attention layers, ``state``,
# ``snap_at`` — here in ``SNAP_TOKENS`` from the segment's start — and ``snaps``) are the sixth family's: both
# read ``cfg.state_shapes`` and ``cfg.attn_layers`` and nothing of its blocks
from sentio_tpu.models.nemotron_h import init_nemotron_cache as init_jamba_cache, zero_state

Array = jax.Array

# Tokens one iteration of the prefill scan takes, its steps unrolled (chosen on
# the chip: PERF.md section 6, PR 48; a segment is padded to whole blocks), and
# the tokens a snapshot boundary is counted in: a page is whole multiples of
# THOSE, and a block passes ``SCAN_BLOCK / SNAP_TOKENS`` of them.
SCAN_BLOCK = 32
SNAP_TOKENS = 16
# Which form a segment's scan takes. None: by backend, as ``models/moe.py`` picks its
# grouped matmul — the kernel on a TPU, the loop elsewhere. A test or a timing
# says ``"pallas"`` (compiled: for a described chip), ``"interpret"`` or ``"xla"``.
SCAN_FORM: Optional[str] = None

# Seeded weights (tests, the fake-model mode, the benchmark's checkpoints). The
# head is TIED, so ``models/cohere2_moe.py``'s two findings carry over with
# their sizes: the embedding a quarter as large (a token's own row must not
# out-vote 65k others), the query projection four times as large (attention
# peaked on a few keys, not the context's average), the mixers' output
# projections ``WO_SCALE``. The Mamba's vectors take the published
# initialisation: ``A_log = log(1..N)`` along the state's columns, ``b_dt`` the
# inverse softplus of a step log-uniform in ``DT_MIN..DT_MAX`` (floored), ``D``
# ones; the convolution's bias a tenth of a unit normal.
EMBED_STD = 0.005
WQ_SCALE = 4.0
WO_SCALE = 0.3
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4
CONV_BIAS_STD = 0.1


@dataclass(frozen=True)
class JambaConfig(LlamaConfig):
    """The catalog's keys as fields. ``mlp_dim`` is the dense SwiGLU every
    layer has; ``num_experts`` must be 1 (see ``__post_init__``). ``max_len``
    the positions the model declares (nothing is sized by it); ``rope_theta``
    is ``LlamaConfig``'s field, 0 here and read nowhere: nothing rotates."""

    vocab_size: int = 65_536
    dim: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    mlp_dim: int = 8192
    max_len: int = 262_144
    rope_theta: float = 0.0
    norm_eps: float = 1e-6
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    tie_word_embeddings: bool = True
    num_experts: int = 1

    def __post_init__(self):
        if self.num_experts != 1:
            raise ValueError(f"num_experts={self.num_experts}: this family is the DENSE Jamba, a SwiGLU in every "
                             "layer; routed feed-forwards beside Mamba-1 mixers are not written yet")
        if not self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings=False: this family's head is the embedding's transpose")
        if self.mamba_proj_bias:
            raise ValueError("mamba_proj_bias=True: W_in and W_out with a bias are not written (the published model has none)")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(f"attn_layer_offset={self.attn_layer_offset} of period {self.attn_layer_period}")

    @property
    def attn_layers(self) -> tuple[int, ...]:
        """The layers whose mixer is attention — the layers the page pool has."""
        return tuple(i for i in range(self.n_layers) if i % self.attn_layer_period == self.attn_layer_offset)

    @property
    def ssm_layers(self) -> tuple[int, ...]:
        """The layers whose mixer is Mamba — the layers the state has."""
        return tuple(i for i in range(self.n_layers) if i % self.attn_layer_period != self.attn_layer_offset)

    def attn_index(self, layer: int) -> int:
        return self.attn_layers.index(layer)

    def ssm_index(self, layer: int) -> int:
        return self.ssm_layers.index(layer)

    @property
    def inner(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def conv_taps(self) -> int:
        """Columns of ``x`` a Mamba layer carries: ``mamba_d_conv - 1``."""
        return self.mamba_d_conv - 1

    def state_shapes(self, rows: int) -> dict:
        """``{name: (shape, dtype)}`` of what ``rows`` sequences carry, a Mamba
        layer each on the leading axis: the convolution's columns in the
        model's dtype, ``S`` transposed (``[N, inner]``) in float32."""
        lm = len(self.ssm_layers)
        return {"conv": ((lm, rows, self.conv_taps, self.inner), self.jdtype),
                "ssm": ((lm, rows, self.mamba_d_state, self.inner), jnp.float32)}

    @classmethod
    def tiny(cls) -> "JambaConfig":
        """CPU-test scale: three Mamba layers around one attention layer, 4 query heads on ONE kv head."""
        return cls(vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=1, mlp_dim=96, max_len=512,
                   attn_layer_period=4, attn_layer_offset=2, mamba_d_state=8, mamba_dt_rank=8)


def init_jamba(rng: Array, cfg: JambaConfig) -> dict:
    """Seeded tree (the sizes above). Canonical ``[in, out]`` kernels;
    ``models/llama.py::serving_layout`` turns an attention layer's ``wq``,
    ``wk``, ``wv`` and a Mamba layer's ``w_in`` as it does every family's."""
    keys = iter(jax.random.split(rng, 1 + cfg.n_layers * 10))
    q_dim, kv_dim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    inner, n, rank = cfg.inner, cfg.mamba_d_state, cfg.mamba_dt_rank

    def dense(n_in, n_out, scale=1.0):
        kernel = jax.random.truncated_normal(next(keys), -2.0, 2.0, (n_in, n_out)) * scale * n_in ** -0.5
        return {"kernel": kernel.astype(jnp.float32)}

    table = jax.random.normal(next(keys), (cfg.vocab_size, cfg.dim)) * EMBED_STD
    params: dict = {"embed_tokens": {"embedding": table.astype(jnp.float32)}, "final_norm": L.rmsnorm_init(cfg.dim)}
    for i in range(cfg.n_layers):
        layer: dict = {"norm": L.rmsnorm_init(cfg.dim), "mlp_norm": L.rmsnorm_init(cfg.dim),
                       "mlp": {"w_gate": dense(cfg.dim, cfg.mlp_dim), "w_up": dense(cfg.dim, cfg.mlp_dim),
                               "w_down": dense(cfg.mlp_dim, cfg.dim)}}
        if i in cfg.attn_layers:
            layer["attn"] = {"wq": dense(cfg.dim, q_dim, WQ_SCALE), "wk": dense(cfg.dim, kv_dim),
                             "wv": dense(cfg.dim, kv_dim), "wo": dense(q_dim, cfg.dim, WO_SCALE)}
        else:
            dt = jnp.exp(jax.random.uniform(next(keys), (inner,)) * (jnp.log(DT_MAX) - jnp.log(DT_MIN))
                         + jnp.log(DT_MIN))
            dt = jnp.maximum(dt, DT_FLOOR)
            layer["mamba"] = {
                "w_in": dense(cfg.dim, 2 * inner),
                # the depthwise taps [inner, K]: column j weighs x_{t-(K-1)+j}
                "conv_kernel": (jax.random.normal(next(keys), (inner, cfg.mamba_d_conv))
                                * cfg.mamba_d_conv ** -0.5).astype(jnp.float32),
                "w_x": dense(inner, rank + 2 * n),
                "dt_norm": L.rmsnorm_init(rank), "b_norm": L.rmsnorm_init(n), "c_norm": L.rmsnorm_init(n),
                "w_dt": dense(rank, inner),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),      # softplus^-1(dt)
                "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (n, inner)),
                "d": jnp.ones((inner,), jnp.float32),
                "w_out": dense(inner, cfg.dim, WO_SCALE),
            }
            if cfg.mamba_conv_bias:
                layer["mamba"]["conv_bias"] = (jax.random.normal(next(keys), (inner,))
                                               * CONV_BIAS_STD).astype(jnp.float32)
        params[f"layers_{i}"] = layer
    return params


# ---------------------------------------------------------------- the Mamba-1 mixer


def _in_proj(mp: dict, cfg: JambaConfig, u: Array) -> tuple[Array, Array]:
    """u [B, T, d] → x before the convolution and the gate z, [B, T, inner]
    each, in the model's dtype."""
    with jax.named_scope("ssm.in"):
        # ``w_in_t``: the serving tree's [out, in] (``models/llama.py::serving_layout``)
        xz = L.dense_t(mp["w_in_t"], u, cfg.jdtype) if "w_in_t" in mp else L.dense(mp["w_in"], u, cfg.jdtype)
        return tuple(jnp.split(xz, 2, axis=-1))


def _conv_dt(mp: dict, cfg: JambaConfig, ext: Array, t: int) -> tuple[Array, Array, Array, Array]:
    """``ext [B, taps + t, inner]`` (x from ``taps`` columns before the segment
    on) → x after the convolution ``[B, t, inner]``, ``D`` ``[B, t, inner]``, B
    and C ``[B, t, N]``, all float32: the taps summed in float32 in one order,
    the bias, silu; then Δ, B and C each through its own RMSNorm."""
    taps = mp["conv_kernel"].astype(jnp.float32)
    acc = sum(ext[:, j: j + t].astype(jnp.float32) * taps[:, j] for j in range(taps.shape[1]))
    x = jax.nn.silu(acc + mp["conv_bias"] if "conv_bias" in mp else acc)
    with jax.named_scope("ssm.dt"):
        rbc = L.dense(mp["w_x"], x, cfg.jdtype).astype(jnp.float32)
        r, bmat, cmat = jnp.split(rbc, [cfg.mamba_dt_rank, cfg.mamba_dt_rank + cfg.mamba_d_state], axis=-1)
        r, bmat, cmat = (L.rmsnorm(mp[name], v, cfg.norm_eps)
                         for name, v in (("dt_norm", r), ("b_norm", bmat), ("c_norm", cmat)))
        step = jax.nn.softplus(L.dense(mp["w_dt"], r, cfg.jdtype).astype(jnp.float32) + mp["dt_bias"])
    return x, step, bmat, cmat


def _out_proj(mp: dict, cfg: JambaConfig, y: Array, z: Array) -> Array:
    with jax.named_scope("ssm.out"):
        return L.dense(mp["w_out"], y * jax.nn.silu(z.astype(jnp.float32)), cfg.jdtype)


def selective_scan(x: Array, step: Array, a: Array, bmat: Array, cmat: Array, start: Array,
                   at: Optional[Array] = None, block: Optional[int] = None) -> tuple[Array, Array, Optional[Array]]:
    """The recurrence over a segment. x and ``step`` [B, T, inner] (``step`` 0
    at a pad position), ``a`` [N, inner] negative, B and C [B, T, N], all
    float32, ``T`` whole blocks, from ``start`` [B, N, inner] → (y [B, T,
    inner] without the ``D x`` term, the state after the segment, the states
    at the boundaries ``at`` [B, K] — in ``SNAP_TOKENS`` from the segment's
    start, 0 the start itself — as [B, K, N, inner], or None). One iteration
    of the loop takes ``block`` tokens, its steps unrolled; the decay is made
    inside the step."""
    b, t, inner = x.shape
    block = block or SCAN_BLOCK
    every = min(block, SNAP_TOKENS)     # steps between two looks at the boundaries (a block under one: its end)

    def blocks(v):  # [B, T, ...] → [T / block, block, B, ...]
        return jnp.moveaxis(v.reshape(b, t // block, block, *v.shape[2:]), 0, 2)

    def body(carry, blk):
        s, snaps = carry
        dl, dx, bm, cm, index = blk
        ys = []
        for i in range(block):
            s = jnp.exp(dl[i][:, None, :] * a) * s + dx[i][:, None, :] * bm[i][:, :, None]
            ys.append(jnp.sum(s * cm[i][:, :, None], axis=1))
            if snaps is not None and (i + 1) % every == 0:
                tokens = index * block + i + 1
                snaps = jnp.where((at * SNAP_TOKENS == tokens)[:, :, None, None], s[:, None], snaps)
        return (s, snaps), jnp.stack(ys)

    snaps = None if at is None else jnp.broadcast_to(start[:, None], (b, at.shape[1], *start.shape[1:]))
    (last, snaps), y = jax.lax.scan(
        body, (start, snaps), (blocks(step), blocks(step * x), blocks(bmat), blocks(cmat),
                               jnp.arange(t // block)))
    return jnp.moveaxis(y, 2, 0).reshape(b, t, inner), last, snaps


def scan_segment(x: Array, step: Array, a: Array, bmat: Array, cmat: Array, start: Array,
                 at: Optional[Array] = None) -> tuple[Array, Array, Optional[Array]]:
    """:func:`selective_scan`'s contract in the form ``SCAN_FORM`` names. The
    loop is a fusion or two a TOKEN: 20 thousand device operations a 512-token
    segment of 26 layers, under which a profiler window never returned (my
    chip runs, PR 48), so a serving TPU takes the kernel — one call a layer,
    which leaves the state at every ``SNAP_TOKENS`` and the caller picks."""
    form = SCAN_FORM or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if form == "xla":
        return selective_scan(x, step, a, bmat, cmat, start, at, SCAN_BLOCK)
    y, states = selective_scan_kernel(x, step, a, bmat, cmat, start, snap=SNAP_TOKENS,
                                      interpret=form == "interpret")
    if at is None:
        return y, states[:, -1], None
    kept = jnp.concatenate([start[:, None], states], axis=1)     # index 0: the start itself
    return y, states[:, -1], jnp.take_along_axis(kept, at[:, :, None, None], axis=1)


def mamba1_segment(mp: dict, cfg: JambaConfig, u: Array, state: dict, lens: Optional[Array],
                   boundaries: Optional[Array] = None) -> tuple[Array, dict, Optional[dict]]:
    """The Mamba-1 mixer over a segment: u ``[B, T, d]`` from the carried
    ``state`` (``conv [B, taps, inner]``, ``ssm [B, N, inner]``) → (out ``[B,
    T, d]``, each row's state after ITS ``lens[b]`` tokens — after all ``T``
    where ``lens`` is None —, the states at ``boundaries [B, K]`` (in
    ``SNAP_TOKENS`` from the segment's start) as ``{conv: [B, K, taps, inner],
    ssm: [B, K, N, inner]}``, or None)."""
    b, t, _ = u.shape
    taps = cfg.conv_taps
    with jax.named_scope("ssm_scan"):
        x, z = _in_proj(mp, cfg, u)
        ext = jnp.concatenate([state["conv"].astype(x.dtype), x], axis=1)         # [B, taps + T, inner]
        x, step, bmat, cmat = _conv_dt(mp, cfg, ext, t)
        if lens is not None:  # a pad position neither decays the state nor adds to it
            step = jnp.where(jnp.arange(t)[None, :, None] < lens[:, None, None], step, 0.0)
        pad = -t % SCAN_BLOCK  # whole blocks: the positions added are pads too
        padded = [jnp.pad(arr, ((0, 0), (0, pad), (0, 0))) for arr in (x, step, bmat, cmat)]
        y, last, snapped = scan_segment(*padded[:2], -jnp.exp(mp["a_log"]), *padded[2:],
                                        state["ssm"].astype(jnp.float32), boundaries)
        out = _out_proj(mp, cfg, y[:, :t] + x * mp["d"], z)

        def cut(n):  # ext[n : n + taps] are the columns before position n (the carried ones for a row of none)
            return jax.vmap(lambda row, at: jax.lax.dynamic_slice(row, (at, 0), (taps, ext.shape[-1])))(ext, n)

        after = {"conv": ext[:, t:] if lens is None else cut(lens), "ssm": last}
        snaps = None
        if boundaries is not None:
            snaps = {"conv": jnp.stack([cut(boundaries[:, k] * SNAP_TOKENS) for k in range(boundaries.shape[1])],
                                       axis=1), "ssm": snapped}
    return out, after, snaps


def mamba1_step(mp: dict, cfg: JambaConfig, u: Array, state: dict, update=None) -> tuple[Array, dict]:
    """One token a row: u ``[B, 1, d]`` over ``state`` → (out ``[B, 1, d]``,
    the state after it): the convolution's columns shifted by this token's,
    ``S <- exp(D (x) A) * S + (D x) (x) B``, ``y = S C + D x``. The state's
    sum and ``y`` are written HERE, in XLA, unless the caller brings ``update``
    (``runtime/paged.py::paged_decode_forward`` where the engine bound
    ``kernels/ssm_update.py``, by that module's ``ssm_update_path``):
    ``update(state["ssm"], D [B, inner], D x [B, inner], B [B, N], C [B, N],
    a_log [N, inner])`` → (what to hand back as ``"ssm"``, ``S C`` [B, inner])
    — the caller's ``state["ssm"]`` is then whatever its ``update`` takes (all
    layers' states, updated in place), and which rows it advances is the
    caller's too."""
    with jax.named_scope("ssm_update"):
        x, z = _in_proj(mp, cfg, u)
        ext = jnp.concatenate([state["conv"].astype(x.dtype), x], axis=1)         # [B, taps + 1, inner]
        x, step, bmat, cmat = _conv_dt(mp, cfg, ext, 1)
        if update is not None:
            ssm, y = update(state["ssm"], step[:, 0], (step * x)[:, 0], bmat[:, 0], cmat[:, 0], mp["a_log"])
            y = y[:, None]
        else:
            ssm = (jnp.exp(step[:, 0, None, :] * -jnp.exp(mp["a_log"])) * state["ssm"]
                   + (step * x)[:, 0, None, :] * bmat[:, 0, :, None])
            y = jnp.sum(ssm * cmat[:, 0, :, None], axis=1)[:, None]
        out = _out_proj(mp, cfg, y + x * mp["d"], z)
    return out, {"conv": ext[:, 1:], "ssm": ssm}


# ------------------------------------------------------------------ the forwards


def plain_qkv(ap: dict, cfg: JambaConfig, u: Array, positions: Array) -> tuple[Array, Array, Array]:
    """q, k, v of a rotation-free attention layer: the projections, nothing else."""
    return qkv_proj(ap, cfg, u)


def jamba_forward(
    params: dict,
    cfg: JambaConfig,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
    logits_at: Optional[Array] = None,
) -> tuple[Array, Optional[Cache]]:
    """ids [B, T] → (logits [B, T, vocab] float32, cache). The prefill /
    scoring contract of ``llama_forward`` over a cache of
    :func:`init_jamba_cache`: K and V as every family's, and the Mamba
    layers' state — read from ``cache["state"]`` as each row's start, handed
    back there as each row's state after its own tokens (``pad_mask`` says how
    many: rows are RIGHT-padded), the states at the boundaries
    ``cache["snap_at"]`` in ``cache["snaps"]``. Without a cache every Mamba
    layer starts from zeros. Only an ``attn_fn`` that ``takes_prior``
    (``kernels/prefill_attention.py``) is used; any other is ignored.
    ``logits_at [B]``: the head at that one position a row alone, logits
    ``[B, 1, vocab]``."""
    if not getattr(attn_fn, "takes_prior", False):
        attn_fn = None
    b, t = ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    lens = snap_at = None
    if cache is not None:
        cache = dict(cache, state=dict(cache["state"]), snaps=dict(cache["snaps"]))
        lens = jnp.full((b,), t, jnp.int32) if pad_mask is None else pad_mask.sum(axis=1).astype(jnp.int32)
        snap_at = cache["snap_at"] if cache["snap_at"].shape[1] else None
    state = cache["state"] if cache is not None else zero_state(cfg, b)

    x = L.embed(params["embed_tokens"], ids, cfg.jdtype)
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        u = L.rmsnorm(lp["norm"], x, cfg.norm_eps)
        if "mamba" in lp:
            j = cfg.ssm_index(i)
            out, after, snaps = mamba1_segment(lp["mamba"], cfg, u, {name: s[j] for name, s in state.items()},
                                               lens, snap_at)
            if cache is not None:
                for name in after:
                    cache["state"][name] = cache["state"][name].at[j].set(after[name].astype(state[name].dtype))
                    if snaps is not None:
                        cache["snaps"][name] = cache["snaps"][name].at[j].set(
                            snaps[name].astype(state[name].dtype))
        else:
            out, cache = _attn(lp["attn"], cfg, u, positions, cfg.attn_index(i), cache, cache_index,
                               pad_mask, attn_fn, project=plain_qkv)
        x = x + out
        x = x + _mlp(lp["mlp"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps))
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    return head_logits(params, cfg, x), cache


def decode_layer(lp: dict, cfg: JambaConfig, i: int, x: Array, step: DecodeStep) -> Array:
    """Layer ``i`` of a decode step on ``x [B, 1, d]``: the slot's state
    advanced by :func:`mamba1_step`, or rotation-free attention over the pages
    (pool layer ``attn_index``); then the SwiGLU."""
    u = L.rmsnorm(lp["norm"], x, cfg.norm_eps)
    if "mamba" in lp:
        out = step.advance(cfg.ssm_index(i), lambda held, update: mamba1_step(lp["mamba"], cfg, u, held, update))
    else:
        q, k, v = plain_qkv(lp["attn"], cfg, u, None)
        attn = step.attend(q, k, v, cfg.attn_index(i), scope="attn.full")
        out = L.dense(lp["attn"]["wo"], attn.reshape(x.shape[0], 1, -1), cfg.jdtype)
    x = x + out
    return x + _mlp(lp["mlp"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps))


FAMILY = Family(
    name="jamba", config=JambaConfig, init=init_jamba, forward=jamba_forward, logits_at=True,
    init_cache=init_jamba_cache, decode_layer=decode_layer,
    head=lambda params, cfg, x: head_logits(params, cfg, x)[:, 0],
    pool_layers=lambda cfg: len(cfg.attn_layers),
    state=StateBeside(per="snapshot", zeros=zero_state, page_tokens=lambda cfg: SNAP_TOKENS),
    refuses={**recurrent_refusals("Mamba", "snapshot"),
             "int8": "K and V are a hundredth of what a sequence of {cfg} keeps beside its float32 Mamba state: "
                     "int8 pages beside it have no quality gate and nothing to save"})
