"""Decoder family ``nemotron_h`` (NVIDIA-Nemotron-3-Nano-30B-A3B): blocks that
are ONE operator each — ``h <- h + Op(RMSNorm(h))`` — chosen a block by a
letter of ``hybrid_override_pattern``, kept as published: ``M`` a Mamba-2
mixer, ``E`` routed experts with a shared one, ``*`` attention. A final
RMSNorm and an UNTIED head. No position embedding anywhere: the attention is
rotation-free and the Mamba layers are causal by construction.

    M:  [z | xBC | dt] = u W_in                       inner | inner + 2 G N | H wide, no bias
        xBC <- silu(depthwise causal conv over ``conv_kernel`` taps + bias)
        x = xBC[:inner] as [H, P];  B, C = the next two [G, N];  head j reads group j // (H / G)
        D_t = softplus(dt_t + dt_bias)                no upper clamp (``time_step_*`` are the INIT's)
        S_t[j] = exp(D_t[j] A[j]) S_{t-1}[j] + D_t[j] x_t[j] (x) B_t[g(j)]       A = -exp(A_log), a scalar a head
        y_t[j] = S_t[j] C_t[g(j)] + D[j] x_t[j]
        out = W_out GroupRMSNorm(y * silu(z))         the gate BEFORE the norm, G groups, one [inner] weight
    E:  s = sigmoid(u W_r) in float32; picks = top-k of s + b; gates s[picks] / (sum + 1e-20) x 2.5
        every expert UNGATED, W_down relu(W_up u)^2; one shared expert of the same form, added
    *:  GQA, no bias, no rotation, causal, softmax(q k^T / sqrt(D)) v

WHAT A SEQUENCE CARRIES differs by the letter. ``*`` leaves a key and a value
a token in the page pool (the pool's layer axis counts the ``*`` blocks:
``attn_index``); ``E`` nothing; ``M`` a STATE whatever the length: the matrix
``S [H, P, N]`` a head (float32: 64 x 64 x 128 numbers at the published widths,
2.1 MB a layer) and the last ``conv_kernel - 1`` columns of ``xBC`` before the
convolution. ``runtime/paged.py`` keeps both per decode SLOT beside the pool
and — so that the radix cache can serve a prefix — in a BOUNDED pool of
SNAPSHOTS: a state is fifty times the K and V of the page it ends, so a page
does not own one by right; the prefix cache chooses which page boundaries keep
one (``runtime/radix.py``).

TWO forms of one recurrence. :func:`mamba_segment` is prefill: the chunked scan
in its matmul form (``chunk_size`` tokens a chunk: inside a chunk the
recurrence is a masked ``[Q, Q]`` product, between chunks the state is carried
by a short loop), from a carried state (zeros at position 0, a snapshot behind
a radix hit, the slot's own behind an earlier segment). Rows are right-padded:
a pad position has ``D = 0``, which neither decays nor adds, so the state after
the whole segment IS the state after the row's own tokens; the state AT every
chunk boundary falls out of the scan, which is where snapshots come from.
:func:`mamba_step` is decode: the one-token update of the slot's state.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp

from sentio_tpu.models import layers as L
from sentio_tpu.models.families import DecodeStep, Family, StateBeside
from sentio_tpu.models.lfm2_moe import _attn, recurrent_refusals
from sentio_tpu.models.llama import Cache, LlamaConfig, qkv_proj
from sentio_tpu.models.moe import expert_layer, expert_tiles

Array = jax.Array

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"

# Seeded weights (tests, the fake-model mode, the benchmark's checkpoints): the
# sizes of ``models/lfm2_moe.py`` where the blocks are alike (the query
# projection four times as large, the output projections 0.3, a non-zero
# expert bias of the spread of the TOP scores). The head is UNTIED, so the
# embedding keeps its usual size and the head is drawn a quarter as large: a
# greedy answer then follows the context and not one loud row. ``A`` and ``dt``
# take the published initialisation: ``A`` uniform in 1..16, ``dt`` log-uniform
# in ``time_step_min..max`` and floored, stored as the inverse softplus.
WQ_SCALE = 4.0
WO_SCALE = 0.3
HEAD_SCALE = 0.25
EXPERT_BIAS_STD = 0.02


@dataclass(frozen=True)
class NemotronHConfig(LlamaConfig):
    """``pattern``: ``hybrid_override_pattern``, one letter a block, kept as
    the string it is published as. ``mlp_dim`` is ONE routed expert,
    ``shared_mlp_dim`` the shared one. ``n_groups`` counts the MAMBA's groups
    of ``B`` and ``C``; the router's ``n_group`` / ``topk_group`` are 1 as
    published. ``max_len`` the positions the model declares (nothing is sized
    by it)."""

    vocab_size: int = 65_536
    dim: int = 2688
    n_layers: int = 14
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128                 # a FIELD: attention is n_heads * head_dim wide, not dim
    mlp_dim: int = 1856
    shared_mlp_dim: int = 3712
    max_len: int = 262_144
    norm_eps: float = 1e-5
    pattern: str = "MEMEM*EMEMEM*E"
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_experts: int = 128
    experts_per_token: int = 6
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    norm_topk_eps: float = 1e-20        # the published code's ``+ 1e-20`` under the gates' sum
    experts_held: int = 64              # the share of a layer's experts one chip of the deployment holds
    expert_offset: int = 0
    # how ``models/moe.py::expert_layer`` runs this family, always
    gate_fn: ClassVar[str] = "sigmoid"
    n_group: ClassVar[int] = 1
    topk_group: ClassVar[int] = 1
    shared_combine: ClassVar[str] = "sum"

    def __post_init__(self):
        if len(self.pattern) != self.n_layers or not set(self.pattern) <= {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(f"pattern {self.pattern!r}: {self.n_layers} letters of "
                             f"{MAMBA!r}, {EXPERTS!r}, {ATTENTION!r} wanted")
        if self.mamba_heads % self.n_groups or self.n_shared_experts != 1:
            raise ValueError(f"{self.mamba_heads} Mamba heads over {self.n_groups} groups, "
                             f"{self.n_shared_experts} shared experts: whole groups and one shared expert")
        if not 0 <= self.expert_offset <= self.n_experts - self.experts_held:
            raise ValueError(f"experts {self.expert_offset}..+{self.experts_held} of {self.n_experts}")

    def _blocks(self, letter: str) -> tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.pattern) if kind == letter)

    @property
    def attn_layers(self) -> tuple[int, ...]:
        """The blocks that are attention — the layers the page pool has."""
        return self._blocks(ATTENTION)

    @property
    def ssm_layers(self) -> tuple[int, ...]:
        """The blocks that are Mamba — the layers the state has."""
        return self._blocks(MAMBA)

    @property
    def n_routed_layers(self) -> int:
        return len(self._blocks(EXPERTS))

    def attn_index(self, layer: int) -> int:
        return self.attn_layers.index(layer)

    def ssm_index(self, layer: int) -> int:
        return self.ssm_layers.index(layer)

    @property
    def inner(self) -> int:
        """The Mamba's inner width: heads x head width, NOT ``expand x dim``."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """What the short convolution runs over: ``x``, ``B`` and ``C``."""
        return self.inner + 2 * self.n_groups * self.ssm_state

    @property
    def conv_taps(self) -> int:
        """Columns of ``xBC`` a Mamba layer carries: ``conv_kernel - 1``."""
        return self.conv_kernel - 1

    def state_shapes(self, rows: int) -> dict:
        """``{name: (shape, dtype)}`` of what ``rows`` sequences carry, a Mamba
        layer each on the leading axis: the convolution's columns in the
        model's dtype, the matrix state in float32 (the model card's advice
        for serving)."""
        lm = len(self.ssm_layers)
        return {"conv": ((lm, rows, self.conv_taps, self.conv_dim), self.jdtype),
                "ssm": ((lm, rows, self.mamba_heads, self.mamba_head_dim, self.ssm_state), jnp.float32)}

    @classmethod
    def tiny(cls) -> "NemotronHConfig":
        """CPU-test scale: every kind of block, whole groups, chunk = a 16-token page."""
        return cls(vocab_size=512, dim=64, n_layers=7, n_heads=4, n_kv_heads=2, head_dim=16, mlp_dim=48,
                   shared_mlp_dim=64, max_len=512, pattern="MEMEM*E", mamba_heads=8, mamba_head_dim=8,
                   ssm_state=16, n_groups=2, chunk_size=16, n_experts=8, experts_per_token=2, experts_held=8)


def zero_state(cfg: NemotronHConfig, rows: int) -> dict:
    """The state of ``rows`` sequences at position 0."""
    return {name: jnp.zeros(shape, dtype) for name, (shape, dtype) in cfg.state_shapes(rows).items()}


def init_nemotron_h(rng: Array, cfg: NemotronHConfig) -> dict:
    """Seeded tree (the sizes above). Canonical ``[in, out]`` kernels;
    ``models/llama.py::serving_layout`` turns an attention block's ``wq``,
    ``wk``, ``wv`` as it does every family's, a Mamba block's ``w_in`` with
    them, and pads the experts' width to whole tiles of lanes."""
    keys = iter(jax.random.split(rng, 3 + cfg.n_layers * 8))
    q_dim, kv_dim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def dense(n_in, n_out, scale=1.0):
        kernel = jax.random.truncated_normal(next(keys), -2.0, 2.0, (n_in, n_out)) * scale * n_in ** -0.5
        return {"kernel": kernel.astype(jnp.float32)}

    def stack(count, n_in, n_out):  # a stack of experts in one draw
        kernel = jax.random.truncated_normal(next(keys), -2.0, 2.0, (count, n_in, n_out)) * n_in ** -0.5
        return kernel.astype(jnp.float32)

    params: dict = {
        "embed_tokens": L.embed_init(next(keys), cfg.vocab_size, cfg.dim),
        "lm_head": dense(cfg.dim, cfg.vocab_size, HEAD_SCALE),
        "final_norm": L.rmsnorm_init(cfg.dim),
    }
    for i, kind in enumerate(cfg.pattern):
        layer: dict = {"norm": L.rmsnorm_init(cfg.dim)}
        if kind == MAMBA:
            dt = jnp.exp(jax.random.uniform(next(keys), (cfg.mamba_heads,))
                         * (jnp.log(cfg.time_step_max) - jnp.log(cfg.time_step_min)) + jnp.log(cfg.time_step_min))
            dt = jnp.maximum(dt, cfg.time_step_floor)
            layer["mamba"] = {
                "w_in": dense(cfg.dim, cfg.inner + cfg.conv_dim + cfg.mamba_heads),
                # the depthwise taps [conv_dim, kernel]: column j weighs xBC_{t-(kernel-1)+j}
                "conv_kernel": (jax.random.normal(next(keys), (cfg.conv_dim, cfg.conv_kernel))
                                * cfg.conv_kernel ** -0.5).astype(jnp.float32),
                "conv_bias": jnp.zeros((cfg.conv_dim,), jnp.float32),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),    # softplus^-1(dt)
                "a_log": jnp.log(jax.random.uniform(next(keys), (cfg.mamba_heads,), minval=1.0, maxval=16.0)),
                "d": jnp.ones((cfg.mamba_heads,), jnp.float32),
                "out_norm": L.rmsnorm_init(cfg.inner),
                "w_out": dense(cfg.inner, cfg.dim, WO_SCALE),
            }
        elif kind == ATTENTION:
            layer["attn"] = {"wq": dense(cfg.dim, q_dim, WQ_SCALE), "wk": dense(cfg.dim, kv_dim),
                             "wv": dense(cfg.dim, kv_dim), "wo": dense(q_dim, cfg.dim, WO_SCALE)}
        else:
            layer["moe"] = {
                "router": dense(cfg.dim, cfg.n_experts),
                "bias": (jax.random.normal(next(keys), (cfg.n_experts,)) * EXPERT_BIAS_STD).astype(jnp.float32),
                "w_up": stack(cfg.experts_held, cfg.dim, cfg.mlp_dim),
                "w_down": stack(cfg.experts_held, cfg.mlp_dim, cfg.dim),
                "shared": {"w_up": stack(1, cfg.dim, cfg.shared_mlp_dim),
                           "w_down": stack(1, cfg.shared_mlp_dim, cfg.dim)},
            }
        params[f"layers_{i}"] = layer
    return params


def init_nemotron_cache(cfg: NemotronHConfig, batch: int, max_len: int, snaps: int = 0) -> Cache:
    """The contiguous cache of a prefill: K and V of the ATTENTION blocks
    ``[La, B, S, Hkv, D]``; ``state`` — what each row STARTS from (zeros:
    position 0), and where the forward leaves each row's state after its own
    tokens; ``snap_at [B, snaps]`` — the chunk boundaries of the segment (in
    chunks from its start, 0 for the start itself) whose state the forward
    leaves in ``snaps`` (the names of ``state``, ``[Lm, B, snaps, ...]``)."""
    dt = cfg.jdtype
    kv = (len(cfg.attn_layers), batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt), "state": zero_state(cfg, batch),
            "snap_at": jnp.zeros((batch, snaps), jnp.int32),
            "snaps": {name: jnp.zeros((shape[0], batch, snaps, *shape[2:]), dtype)
                      for name, (shape, dtype) in cfg.state_shapes(batch).items()}}


# ---------------------------------------------------------------- the Mamba-2 mixer


def _in_proj(mp: dict, cfg: NemotronHConfig, u: Array) -> tuple[Array, Array, Array]:
    """u [B, T, d] → z [B, T, inner], xBC before the convolution [B, T,
    conv_dim], both in the model's dtype, and ``D = softplus(dt + dt_bias)``
    [B, T, H] float32."""
    dt = cfg.jdtype
    # ``w_in_t``: the serving tree's [out, in] (``models/llama.py::serving_layout``)
    zxd = L.dense_t(mp["w_in_t"], u, dt) if "w_in_t" in mp else L.dense(mp["w_in"], u, dt)
    z, xbc, step = jnp.split(zxd, [cfg.inner, cfg.inner + cfg.conv_dim], axis=-1)
    return z, xbc, jax.nn.softplus(step.astype(jnp.float32) + mp["dt_bias"])


def _conv(mp: dict, cfg: NemotronHConfig, ext: Array, t: int) -> tuple[Array, Array, Array]:
    """``ext [B, taps + t, conv_dim]`` (xBC from ``taps`` columns before the
    segment on) → x [B, t, H, P], B and C [B, t, G, N] float32: the taps
    summed in float32 in one order, the bias, silu."""
    taps = mp["conv_kernel"].astype(jnp.float32)
    acc = sum(ext[:, j: j + t].astype(jnp.float32) * taps[:, j] for j in range(taps.shape[1]))
    xbc = jax.nn.silu(acc + mp["conv_bias"])
    b = ext.shape[0]
    x, bmat, cmat = jnp.split(xbc, [cfg.inner, cfg.inner + cfg.n_groups * cfg.ssm_state], axis=-1)
    return (x.reshape(b, t, cfg.mamba_heads, cfg.mamba_head_dim),
            bmat.reshape(b, t, cfg.n_groups, cfg.ssm_state), cmat.reshape(b, t, cfg.n_groups, cfg.ssm_state))


def _out_proj(mp: dict, cfg: NemotronHConfig, y: Array, z: Array) -> Array:
    """y [B, T, inner] float32 gated by ``silu(z)``, THEN the RMSNorm over each
    of the ``n_groups`` groups of ``inner / n_groups`` under one weight, and
    ``W_out``."""
    b, t, _ = y.shape
    gated = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(b, t, cfg.n_groups, -1)
    normed = gated * jax.lax.rsqrt((gated * gated).mean(-1, keepdims=True) + cfg.norm_eps)
    normed = normed.reshape(b, t, cfg.inner) * mp["out_norm"]["scale"]
    return L.dense(mp["w_out"], normed, cfg.jdtype)


def ssm_scan(x: Array, step: Array, a: Array, bmat: Array, cmat: Array, start: Array, chunk: int
             ) -> tuple[Array, Array]:
    """The chunked scan, matmul form. x [B, T, H, P], ``step`` [B, T, H] (0 at
    a pad position), ``a`` [H] negative, B and C [B, T, G, N], all float32,
    ``T`` whole chunks, from ``start`` [B, H, P, N] → (y [B, T, H, P] without
    the ``D x`` term, the state AT every chunk boundary [B, T / chunk + 1, H,
    P, N]: index 0 is ``start``, the last the state after the segment). A ROW
    AT A TIME (``lax.map``): a row's ``[chunks, Q, Q, H]`` decay is 34 MB at
    eight chunks of the published widths, eight rows' at once 0.27 GB, and a
    row alone fills the chip's matrix unit."""
    t, h, p = x.shape[1:]
    g, n = bmat.shape[-2:]
    c, r = t // chunk, h // g                                            # chunks; heads a group (head j: group j // r)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None, None]

    def one(row):
        x, step, bmat, cmat, start = row
        x, step = x.reshape(c, chunk, g, r, p), step.reshape(c, chunk, g, r)
        bmat, cmat = bmat.reshape(c, chunk, g, n), cmat.reshape(c, chunk, g, n)
        cum = jnp.cumsum(step * a.reshape(g, r), axis=1)                 # [c, Q, G, R]: log decay up to and with i
        xs = x * step[..., None]                                         # what position j adds, before B
        with jax.named_scope("ssm.diag"):   # inside a chunk: y_i = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) xs_j
            cb = jnp.einsum("cign,cjgn->cijg", cmat, bmat)               # [c, Q, Q, G]
            # the exponent of a masked pair is set to 0 BEFORE exp: exp of a large positive would be inf
            decay = jnp.where(tri, jnp.exp(jnp.where(tri, cum[:, :, None] - cum[:, None], 0.0)), 0.0)
            y = jnp.einsum("cijgr,cjgrp->cigrp", cb[..., None] * decay, xs)
        with jax.named_scope("ssm.states"):  # what a chunk adds to the state, and the state carried over the chunks
            to_end = jnp.exp(cum[:, -1:] - cum)                          # [c, Q, G, R]
            added = jnp.einsum("cjgr,cjgrp,cjgn->cgrpn", to_end, xs, bmat)
            whole = jnp.exp(cum[:, -1])                                  # [c, G, R]
            states = [start.reshape(g, r, p, n)]
            for k in range(c):
                states.append(states[-1] * whole[k, :, :, None, None] + added[k])
            states = jnp.stack(states)                                   # [c + 1, G, R, P, N]
        with jax.named_scope("ssm.carried"):  # what the state at a chunk's start gives position i
            y = y + jnp.einsum("cign,cgrpn,cigr->cigrp", cmat, states[:-1], jnp.exp(cum))
        return y.reshape(t, h, p), states.reshape(c + 1, h, p, n)

    return jax.lax.map(one, (x, step, bmat, cmat, start))


def mamba_segment(mp: dict, cfg: NemotronHConfig, u: Array, state: dict, lens: Optional[Array],
                  snap_at: Optional[Array] = None) -> tuple[Array, dict, Optional[dict]]:
    """The Mamba-2 mixer over a segment: u ``[B, T, d]`` from the carried
    ``state`` (``conv [B, taps, conv_dim]``, ``ssm [B, H, P, N]``) → (out
    ``[B, T, d]``, each row's state after ITS ``lens[b]`` tokens — after all
    ``T`` where ``lens`` is None —, the states at the chunk boundaries
    ``snap_at [B, K]`` (chunks from the segment's start) as ``{conv: [B, K,
    taps, conv_dim], ssm: [B, K, H, P, N]}``, or None)."""
    b, t, _ = u.shape
    chunk, taps = cfg.chunk_size, cfg.conv_taps
    with jax.named_scope("ssm_scan"):
        z, xbc, step = _in_proj(mp, cfg, u)
        ext = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], axis=1)     # [B, taps + T, conv_dim]
        x, bmat, cmat = _conv(mp, cfg, ext, t)
        if lens is not None:  # a pad position neither decays the state nor adds to it
            step = jnp.where(jnp.arange(t)[None, :, None] < lens[:, None, None], step, 0.0)
        pad = -t % chunk      # whole chunks: the positions added are pads too
        padded = [jnp.pad(arr, ((0, 0), (0, pad)) + ((0, 0),) * (arr.ndim - 2)) for arr in (x, step, bmat, cmat)]
        y, states = ssm_scan(*padded[:2], -jnp.exp(mp["a_log"]), *padded[2:], state["ssm"], chunk)
        y = y[:, :t] + x * mp["d"][:, None]
        out = _out_proj(mp, cfg, y.reshape(b, t, cfg.inner), z)

        def cut(n):  # ext[n : n + taps] are the columns before position n (the carried ones for a row of none)
            return jax.vmap(lambda row, at: jax.lax.dynamic_slice(row, (at, 0), (taps, ext.shape[-1])))(ext, n)

        last = {"conv": ext[:, t:] if lens is None else cut(lens), "ssm": states[:, -1]}
        snaps = None
        if snap_at is not None:
            snaps = {"conv": jnp.stack([cut(snap_at[:, k] * chunk) for k in range(snap_at.shape[1])], axis=1),
                     "ssm": jnp.take_along_axis(states, snap_at[:, :, None, None, None], axis=1)}
    return out, last, snaps


def mamba_step(mp: dict, cfg: NemotronHConfig, u: Array, state: dict, update=None) -> tuple[Array, dict]:
    """One token a row: u ``[B, 1, d]`` over ``state`` → (out ``[B, 1, d]``,
    the state after it): the convolution's columns shifted by this token's,
    ``S <- exp(D A) S + D x (x) B``, ``y = S C + D x``. The state's sum and
    ``y`` are written HERE, in XLA, unless the caller brings ``update``
    (``runtime/paged.py::paged_decode_forward`` where the engine bound
    ``kernels/ssm_update.py``, by that module's ``ssm_update_path``):
    ``update(state["ssm"], decay [B, H], x dt [B, H, P], B [B, G, N], C)`` →
    (what to hand back as ``"ssm"``, ``S C`` [B, H, P]) — the caller's
    ``state["ssm"]`` is then whatever its ``update`` takes (all blocks' states,
    updated in place), and which rows it advances is the caller's too."""
    b = u.shape[0]
    rep = cfg.mamba_heads // cfg.n_groups
    with jax.named_scope("ssm_update"):
        z, xbc, step = _in_proj(mp, cfg, u)
        ext = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], axis=1)     # [B, taps + 1, conv_dim]
        x, bmat, cmat = _conv(mp, cfg, ext, 1)
        x, step = x[:, 0], step[:, 0]                                             # [B, H, P], [B, H]
        decay = jnp.exp(step * -jnp.exp(mp["a_log"]))
        if update is not None:
            ssm, y = update(state["ssm"], decay, x * step[..., None], bmat[:, 0], cmat[:, 0])
        else:
            bmat, cmat = (jnp.repeat(m[:, 0], rep, axis=1) for m in (bmat, cmat))     # [B, H, N]
            ssm = state["ssm"] * decay[..., None, None] + (x * step[..., None])[..., None] * bmat[:, :, None, :]
            y = jnp.einsum("bhpn,bhn->bhp", ssm, cmat)
        y = y + x * mp["d"][:, None]
        out = _out_proj(mp, cfg, y.reshape(b, 1, cfg.inner), z)
    return out, {"conv": ext[:, 1:], "ssm": ssm}


# -------------------------------------------------------------- the other two, and the head


def plain_qkv(ap: dict, cfg: NemotronHConfig, u: Array, positions: Array) -> tuple[Array, Array, Array]:
    """q, k, v of a rotation-free attention block: the projections, nothing else."""
    return qkv_proj(ap, cfg, u)


def head_logits(params: dict, cfg: NemotronHConfig, x: Array) -> Array:
    """The final norm and the untied head, float32."""
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.dense(params["lm_head"], x, cfg.jdtype).astype(jnp.float32)    # as ``llama_forward``'s head


def nemotron_h_forward(
    params: dict,
    cfg: NemotronHConfig,
    ids: Array,
    positions: Optional[Array] = None,
    cache: Optional[Cache] = None,
    cache_index: Array | int = 0,
    pad_mask: Optional[Array] = None,
    attn_fn=None,
    logits_at: Optional[Array] = None,
) -> tuple[Array, Optional[Cache], dict]:
    """ids [B, T] → (logits [B, T, vocab] float32, cache, routed). The prefill
    / scoring contract of ``llama_forward`` over a cache of
    :func:`init_nemotron_cache`: K and V as every family's, and the Mamba
    blocks' state — read from ``cache["state"]`` as each row's start, handed
    back there as each row's state after its own tokens (``pad_mask`` says how
    many: rows are RIGHT-padded), the states at the boundaries
    ``cache["snap_at"]`` in ``cache["snaps"]``. Without a cache every Mamba
    block starts from zeros. ``routed = {"experts": [Le, B, T, k] int32,
    "counts": [4] int32}``. Only an ``attn_fn`` that ``takes_prior``
    (``kernels/prefill_attention.py``) is used; any other is ignored.
    ``logits_at [B]``: the head at that one position a row alone, logits
    ``[B, 1, vocab]`` — an admission samples from a prompt's last position, and
    the head over eight rows of 1,024 is 3 GB of logits nothing reads."""
    if not getattr(attn_fn, "takes_prior", False):
        attn_fn = None
    dt = cfg.jdtype
    b, t = ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    lens = snap_at = None
    if cache is not None:
        cache = dict(cache, state=dict(cache["state"]), snaps=dict(cache["snaps"]))
        lens = jnp.full((b,), t, jnp.int32) if pad_mask is None else pad_mask.sum(axis=1).astype(jnp.int32)
        snap_at = cache["snap_at"] if cache["snap_at"].shape[1] else None
    state = cache["state"] if cache is not None else zero_state(cfg, b)

    x = L.embed(params["embed_tokens"], ids, dt)
    picks, counts = [], jnp.zeros((4,), jnp.int32)
    for i, kind in enumerate(cfg.pattern):
        lp = params[f"layers_{i}"]
        u = L.rmsnorm(lp["norm"], x, cfg.norm_eps)
        if kind == MAMBA:
            j = cfg.ssm_index(i)
            out, last, snaps = mamba_segment(lp["mamba"], cfg, u, {name: s[j] for name, s in state.items()},
                                             lens, snap_at)
            if cache is not None:
                for name in last:
                    cache["state"][name] = cache["state"][name].at[j].set(last[name].astype(state[name].dtype))
                    if snaps is not None:
                        cache["snaps"][name] = cache["snaps"][name].at[j].set(
                            snaps[name].astype(state[name].dtype))
        elif kind == ATTENTION:
            out, cache = _attn(lp["attn"], cfg, u, positions, cfg.attn_index(i), cache, cache_index,
                               pad_mask, attn_fn, project=plain_qkv)
        else:
            out, chosen, n = expert_layer(lp["moe"], cfg, u, pad_mask)
            picks.append(chosen)
            counts = counts + n
        x = x + out
    routed = {"experts": jnp.stack(picks)} if picks else {}
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    return head_logits(params, cfg, x), cache, {**routed, "counts": counts}


def decode_layer(lp: dict, cfg: NemotronHConfig, i: int, x: Array, step: DecodeStep) -> Array:
    """Block ``i`` of a decode step on ``x [B, 1, d]``, ONE operator: the slot's
    state advanced by :func:`mamba_step`, rotation-free attention over the
    pages (pool layer ``attn_index``) or routed experts."""
    kind = cfg.pattern[i]
    u = L.rmsnorm(lp["norm"], x, cfg.norm_eps)
    if kind == MAMBA:
        out = step.advance(cfg.ssm_index(i), lambda held, update: mamba_step(lp["mamba"], cfg, u, held, update))
    elif kind == ATTENTION:
        q, k, v = plain_qkv(lp["attn"], cfg, u, None)
        attn = step.attend(q, k, v, cfg.attn_index(i), scope="attn.full")
        out = L.dense(lp["attn"]["wo"], attn.reshape(x.shape[0], 1, -1), cfg.jdtype)
    else:
        # a row that does not advance is routed nowhere (``models/cohere2_moe.py``)
        out, chosen, n = expert_layer(lp["moe"], cfg, u, step.valid)
        step.note({"experts": chosen}, n)
    return x + out


FAMILY = Family(
    name="nemotron_h", config=NemotronHConfig, init=init_nemotron_h, forward=nemotron_h_forward,
    logits_at=True, init_cache=init_nemotron_cache, decode_layer=decode_layer,
    head=lambda params, cfg, x: head_logits(params, cfg, x)[:, 0],
    pool_layers=lambda cfg: len(cfg.attn_layers),
    state=StateBeside(per="snapshot", zeros=zero_state, page_tokens=lambda cfg: cfg.chunk_size),
    picks=lambda cfg: {"experts": cfg.experts_per_token}, expert_tiles=expert_tiles,
    refuses={**recurrent_refusals("Mamba", "snapshot"),
             "int8": "K and V are a thirtieth of what a sequence of {cfg} keeps (its Mamba state is float32, as "
                     "the model card advises): int8 pages beside it have no quality gate and nothing to save"})
