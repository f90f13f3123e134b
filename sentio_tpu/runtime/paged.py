"""Paged KV cache + continuous batching — the long-context serving core.

The reference caps context at ~2000 tokens and serves one request per HTTP
call (/root/reference/src/core/graph/nodes.py:296-338, factory.py:90); its
"batching" is a connection pool. Here the KV cache is *paged*: HBM holds one
pool of fixed-size pages ([L, P, page, Hkv, D]; ``L`` counts the layers that
HAVE keys and values: ``PagedPool`` says what a family keeps beside them) and
every live sequence owns a page table mapping logical blocks to physical
pages. That buys:

* **continuous batching** — requests join and leave decode slots without
  recompiling or re-laying-out anyone else's cache; one compiled decode
  program serves the whole lifetime of the server;
* **long contexts without fragmentation** — a 8K-token sequence and a
  50-token sequence coexist in the same pool, each paying only for the
  pages it touches;
* **instant reclaim** — finishing a request frees integer page ids, not
  device memory.

Device side is pure-functional: the fused tick threads the page pool
through jit with donated buffers (the pool is updated in place, never
copied). Host side, ``PageAllocator`` is a free-list and ``ContinuousBatchingEngine``
owns slot admission / EOS retirement, mirroring the reference's resilience
stance (a failing request fails alone, SURVEY.md §5).

The engine is built around ONE cost model: device dispatches are async and
effectively free, while every host-visible transfer is a blocking round
trip (how much each costs on the chip is not measured yet). Hence:

* **multi-step fused ticks** — one ``lax.scan`` dispatch runs up to
  ``max_tick_steps`` decode sub-steps with per-row budgets and EOS halting;
  the host fetches ONE packed [1+steps, B] token array per tick and replays
  the device's halting rule exactly (no mask transfer);
* **batched admission, deferred first tokens** — queued requests prefill as
  width-bucketed batches (prefill + cache scatter + first-token sample in
  one dispatch), and the sampled first tokens stay on device until the next
  tick's fetch carries them back;
* **device-carried decode state** — token/position/halt arrays thread from
  tick to tick as device arrays (host numpy rides jit calls, never eager
  uploads), which enables
* **pipelined ticks** (``pipeline_depth=2``) — tick N+1 dispatches BEFORE
  tick N's fetch, overlapping the round trip with device compute. A tick's
  record keeps the ``_Slot`` objects it was dispatched with and is folded
  into THEM: a lane whose row was granted its last token is handed to the
  next queued request at once (``_admit``), while that row's last tick is
  still in flight, and a lane retired and refilled since dispatch is never
  replayed into its new request.

Page 0 is reserved as a scratch page: free slots' page tables point at it,
so masked lanes in the fused decode step write garbage somewhere harmless.
"""

from __future__ import annotations

import functools
import itertools
import logging
import time
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from sentio_tpu.analysis.audit.registry import jit_family
from sentio_tpu.analysis.sanitizer import check_engine_invariants, engine_guard
from sentio_tpu.infra import faults
from sentio_tpu.infra.phases import (
    CONV_STATE_KINDS, ENGINE_PHASES, KV_PAGE_KINDS, LANE_ADMISSION_KINDS, MOE_KINDS,
    PREFILL_LATENT_KINDS, PREFILL_TURN_KINDS, ROW_STEP_KINDS, SSM_STATE_KINDS, PhaseTimer,
)
from sentio_tpu.infra.tracing import annotation, dispatching, get_stamper, harvested, span
from sentio_tpu.models.families import DecodeStep, family_of
from sentio_tpu.models.llama import LlamaConfig, serving_layout
from sentio_tpu.parallel.batcher import bucket_size

Array = object  # jax.Array — jax imported lazily


# --------------------------------------------------------------------- pool


@dataclass
class PagedPool:
    """Device-side page pool. k/v: [L, P, page, Hkv, D] arrays, or — with
    int8 KV quantization — pytrees ``{"q": int8 [L,P,page,Hkv,D], "s": bf16
    [L,P,Hkv,page]}`` (per-token-per-head absmax scales, stored page-minor
    so the Pallas kernel DMAs one lane-dense scale tile per page — see
    kernels/paged_attention.py). The pytree form
    rides through every jit signature, scan carry, and donation unchanged;
    only the read/write helpers below understand the representation.
    What a family keeps is its record's (``models/families.py``). A LATENT
    family has ONE pool, ``k`` ``[L, P, latent_dim, page]`` — a page lies
    latent-major, its positions the lanes of a tile
    (``kernels/latent_attention.py`` says why) — and ``v`` is None: an empty
    pytree, which rides every signature, carry and donation as it is. A family
    with STATE BESIDE THE PAGES (``StateBeside``) has pages for its attention
    layers only (the pool's layer axis counts those) and two more entries, a
    state layer each on the leading axis: ``conv [Ls, slots, ...]``, what a
    decode slot carries, and ``tail`` — per page ``[Ls, P, ...]``, the state at
    every page's end, written by prefill for the pages it fills and by decode
    when a page fills: what a sequence that starts behind cached pages starts
    from — or per snapshot ``[Ls, S, ...]``, a BOUNDED pool whose slots the
    radix cache hands to the page boundaries it chooses (``runtime/radix.py``);
    arrays, or dicts of arrays under the state's own names. Both None for
    every other family.
    Page id 0 = scratch."""

    k: Array
    v: Array
    page_size: int
    quantized: bool = False
    conv: Array = None
    tail: Array = None

    @property
    def num_pages(self) -> int:
        return (self.k["q"] if self.quantized else self.k).shape[1]

    @property
    def hbm_bytes(self) -> int:
        """Static device footprint of the k+v page pools (payload + scales
        for the quantized repr) and, where the family has one, of the
        convolution state per slot and per page — the number the footprint
        claims are audited by (bench phase A/C, the compile-manifest pools
        section)."""
        import jax

        return sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves((self.k, self.v, self.conv, self.tail))
        )

    @property
    def token_bytes(self) -> int:
        """Of ``hbm_bytes``, what ONE token keeps in the pages over all the
        layers that have them (K and V, or the latents; scales with int8
        pages): whatever a layer's window, a row holds it for every position."""
        return _tree_bytes((self.k, self.v)) // (self.num_pages * self.page_size)

    @property
    def conv_state_bytes(self) -> int:
        """Of ``hbm_bytes``, the convolution state (0 for a family without)."""
        return _tree_bytes((self.conv, self.tail))

    @property
    def snapshot_bytes(self) -> int:
        """Of ``conv_state_bytes``, the snapshot pool of a family with a
        matrix state (0 for every other)."""
        return _tree_bytes(self.tail) if isinstance(self.tail, dict) else 0


def _tree_bytes(tree) -> int:
    import jax

    return sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


# a family with Mamba layers: the page boundaries ONE row of ONE prefill
# dispatch may leave a snapshot at (the boundary a match was cut back from, and
# the last whole page the dispatch reaches: ``_snapshot_plan``)
SNAPS_PER_ROW = 2


def quantize_kv(x):
    """[..., D] float → (int8 [..., D], bf16 scale [...]). Symmetric absmax
    per vector; a zero vector gets scale 0 and dequantizes to exact zeros.
    bfloat16 scales keep the overhead at 2 bytes per vector in a dtype the
    TPU's vector units load natively (float16 is refused by Mosaic on v5e).
    The payload is quantized against the ROUNDED scale that is stored, so
    the 8-bit scale mantissa adds no error on top of the int8 step: a scale
    that rounded down merely clips the largest element at 127."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    scale = (jnp.max(jnp.abs(xf), axis=-1) / 127.0).astype(jnp.bfloat16)
    q = jnp.clip(
        jnp.round(xf / jnp.maximum(scale.astype(jnp.float32), 1e-30)[..., None]),
        -127, 127,
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype):
    import jax.numpy as jnp

    return (
        q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
    ).astype(dtype)


def dequantize_pages(q, scale, dtype):
    """Page-shaped pair as the pool stores it — payload [..., page, Hkv, D],
    scales page-minor [..., Hkv, page] — → dense [..., page, Hkv, D]."""
    return dequantize_kv(q, scale.swapaxes(-1, -2), dtype)


def _page_write(pages, layer, page_ids, offsets, val):
    """Write val [B, Hkv, D] at (layer, page_ids[b], offsets[b]) per row —
    representation-aware (plain array or int8+scale pytree)."""
    if isinstance(pages, dict):
        q, s = quantize_kv(val)
        return {
            "q": pages["q"].at[layer, page_ids, offsets].set(q),
            # scales are page-minor [L, P, Hkv, page]: s [B, Hkv] lands at
            # (layer, page_ids[b], :, offsets[b])
            "s": pages["s"].at[layer, page_ids, :, offsets].set(s),
        }
    # a position's vectors as the pool holds them (lane-packed: ``init_pool``)
    return pages.at[layer, page_ids, offsets].set(val.reshape(val.shape[0], *pages.shape[-2:]))


def _write_kv(k_pages, v_pages, layer, page_ids, offsets, k, v, dt, write_impl=None):
    """This step's k and v [B, 1, Hkv, D], as ``dt``, into both pools →
    (k_pages, v_pages): through ``write_impl`` where the engine bound one
    (``kernels/page_write.py``: a pool the compiler would otherwise move for
    the scatter, written in place by a DMA a row), else :func:`_page_write`'s
    scatter, a pool at a time."""
    if write_impl is not None:
        return write_impl(k_pages, v_pages, layer, page_ids, offsets, k[:, 0].astype(dt), v[:, 0].astype(dt))
    k_pages = _page_write(k_pages, layer, page_ids, offsets, k[:, 0].astype(dt))
    return k_pages, _page_write(v_pages, layer, page_ids, offsets, v[:, 0].astype(dt))


def _page_dim(pages) -> int:
    return (pages["q"] if isinstance(pages, dict) else pages).shape[-3]


def _gather_pages(pages, layer, page_table, dtype, head_dim=None):
    """Pool [L, P, page, Hkv, D](-repr), a layer + table [B, NB] → that
    layer's pages, dense [B, NB*page, Hkv, D]. One gather on (layer, page):
    no ``pages[layer]`` is formed on the way. ``head_dim`` tells a
    lane-packed pool's heads apart again (``init_pool``)."""
    b, nb = page_table.shape
    if isinstance(pages, dict):
        kc = dequantize_pages(
            pages["q"][layer, page_table], pages["s"][layer, page_table], dtype)
    else:
        kc = pages[layer, page_table]
    return kc.reshape(b, nb * kc.shape[2], -1, head_dim or kc.shape[-1])


def _latent_tokens(pages, index):
    """Latent pages ``pages[index]`` ``[..., NB, latent_dim, page]`` as the
    tokens they hold, ``[..., NB * page, latent_dim]``."""
    dense = pages[index].swapaxes(-1, -2)
    return dense.reshape(*dense.shape[:-3], dense.shape[-3] * dense.shape[-2], dense.shape[-1])


def _latent_write(pages, layer, page_ids, offsets, latents):
    """Write latents [B, latent_dim] at (layer, page_ids[b], :, offsets[b]) a
    row: one COLUMN of a latent-major page each, as one in-place slice update
    a row. (Asked to scatter the columns in one operation, the TPU compiler
    turns the WHOLE pool latent-minor for the scatter and back for the
    kernel, every layer of every sub-step: tests/test_chip_compile.py.)"""
    import jax

    for b in range(latents.shape[0]):
        pages = jax.lax.dynamic_update_slice(
            pages, latents[b][None, None, :, None], (layer, page_ids[b], 0, offsets[b]))
    return pages


def init_pool(
    cfg: LlamaConfig, num_pages: int, page_size: int, mesh=None,
    quantized: bool = False, slots: int = 0, pack: int = 1, snapshots: int = 0,
) -> PagedPool:
    """Allocate the page pool; with a mesh, kv heads shard over ``tp`` (the
    same axis the wk/wv weight columns shard on, so per-shard Q·K never
    crosses devices) and page tables stay replicated host-side. With
    ``quantized`` the pool stores int8 + per-vector scales — ~half the HBM
    and half the decode-attention read bandwidth of bf16 pages. A family with
    state beside the pages (``PagedPool``) gets it for ``slots`` decode slots
    and for every page or ``snapshots`` snapshots.

    ``pack`` > 1 is the pool's layout for heads NARROWER than the 128 lanes of
    a tile (``kernels/paged_attention.py::lane_packing``): ``pack`` kv heads
    share a row, ``[L, P, page, Hkv / pack, D * pack]`` — the same numbers in
    the same order as ``[L, P, page, Hkv, D]``, a position's heads side by
    side, in a shape whose pages ARE whole tiles, so the decode kernel's DMA
    can bring them. Every reader and writer here reshapes what it holds to
    the pool's last two axes (the update, never the pool); bf16 pages on one
    device only."""
    import jax.numpy as jnp

    family = family_of(cfg)
    # refused where the engine is built; here for every other caller
    for what, asked_for in (("mesh", mesh is not None), ("int8", quantized)):
        if asked_for and (reason := family.refusal(what, cfg)):
            raise ValueError(reason)
    if family.latent:
        pages = jnp.zeros((cfg.n_layers, num_pages, cfg.latent_dim, page_size), cfg.jdtype)
        return PagedPool(k=pages, v=None, page_size=page_size)

    conv = tail = None
    n_layers = family.pool_layers(cfg)
    if family.state is not None:
        conv = family.state.zeros(cfg, slots)
        tail = family.state.zeros(cfg, num_pages if family.state.per == "page" else snapshots)
    if pack > 1 and (quantized or mesh is not None or cfg.n_kv_heads % pack):
        raise ValueError(f"pack={pack}: lane-packed pages are bf16, on one device, whole rows of heads")
    shape = (n_layers, num_pages, page_size, cfg.n_kv_heads // pack, cfg.head_dim * pack)

    def alloc(arr_shape, dtype, spec=None):
        # born in its final placement: a pool zero-filled on the default
        # device and resharded afterwards stages the WHOLE pool on one chip
        return jnp.zeros(arr_shape, dtype, device=spec)

    kv_spec = scale_spec = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from sentio_tpu.parallel.mesh import AXIS_TP

        tp = mesh.shape[AXIS_TP]
        if cfg.n_kv_heads % tp != 0:
            raise ValueError(
                f"n_kv_heads={cfg.n_kv_heads} not divisible by tp={tp}"
            )
        kv_spec = NamedSharding(mesh, P(None, None, None, AXIS_TP, None))
        scale_spec = NamedSharding(mesh, P(None, None, AXIS_TP, None))

    if quantized:
        scale_shape = (n_layers, num_pages, cfg.n_kv_heads, page_size)
        k = {"q": alloc(shape, jnp.int8, kv_spec),
             "s": alloc(scale_shape, jnp.bfloat16, scale_spec)}
        v = {"q": alloc(shape, jnp.int8, kv_spec),
             "s": alloc(scale_shape, jnp.bfloat16, scale_spec)}
    else:
        k = alloc(shape, cfg.jdtype, kv_spec)
        v = alloc(shape, cfg.jdtype, kv_spec)
    return PagedPool(k=k, v=v, page_size=page_size, quantized=quantized, conv=conv, tail=tail)


class PageAllocator:
    """Host free-list over page ids 1..P-1 (0 is the shared scratch page)."""

    def __init__(self, num_pages: int) -> None:
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved scratch)")
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self.num_pages = num_pages

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise MemoryError(f"paged KV pool exhausted: need {n}, have {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        return out

    def free(self, ids: Sequence[int]) -> None:
        for pid in ids:
            if pid == 0:
                continue
            self._free.append(pid)


# ------------------------------------------------------------ device kernels


def _paged_attn_xla(q, k_pages, v_pages, layer, page_table, lens, n_rep, window=None):
    """Decode attention over a page table, XLA gather path (``window``: the
    keys a query sees behind itself, itself included; None = all).

    q [B,1,H,D]; k/v_pages [L,P,page,Hkv,D] (the whole pool, either repr);
    layer int; page_table [B,NB]; lens [B]. Gathers each row's pages of that
    layer into a contiguous [B, NB*page, Hkv, D] window — XLA fuses the
    gather into the attention when the window is modest; the Pallas kernel
    in kernels/paged_attention.py walks the table in VMEM instead and is
    what runs on TPU.
    """
    import jax.numpy as jnp

    from sentio_tpu.models import layers as L

    kc = _gather_pages(k_pages, layer, page_table, q.dtype, q.shape[-1])
    vc = _gather_pages(v_pages, layer, page_table, q.dtype, q.shape[-1])
    kc = L.repeat_kv(kc, n_rep)
    vc = L.repeat_kv(vc, n_rep)
    kj = jnp.arange(kc.shape[1])[None, None, None, :]
    mask = kj <= lens[:, None, None, None]  # new token sits at index lens
    if window is not None:
        mask &= kj > (lens - window)[:, None, None, None]
    return L.attention(q, kc, vc, mask, q.dtype)


def paged_decode_forward(params, cfg: LlamaConfig, tok, lens, page_table, k_pages, v_pages,
                         attn_impl=None, write_mask=None, return_routed=False, conv=None, tail=None,
                         write_impl=None, ssm_impl=None):
    """One decode step over the paged pool: the ONE walk of a step's layers,
    whatever the family (``models/families.py``: its record's ``decode_layer``
    and ``head`` are what differs; the pool's operations reach them as a
    ``DecodeStep``).

    tok [B] int32 (last sampled token per slot); lens [B] absolute position
    the new token occupies; page_table [B, NB]. Returns (logits [B, V],
    k_pages, v_pages) with this step's k/v scattered into each row's current
    page. Masked/free slots must point their page table at scratch page 0.
    ``write_mask`` [B] bool (optional) redirects masked rows' k/v writes to
    the scratch page — the multi-step tick uses it to freeze rows that hit
    EOS or their budget mid-scan without corrupting their cache.
    ``write_impl`` (optional) writes the step's k and v into both pools in
    the scatter's place (``kernels/page_write.py``; :func:`_write_kv`).
    ``ssm_impl`` (optional) updates a Mamba family's ``conv["ssm"]`` in place
    (``kernels/ssm_update.py``).

    A LATENT family's ``k_pages`` are its latents; ``v_pages`` is None and
    comes back None. ``return_routed`` adds what a family's expert layers
    decided (``{"experts": [L, B, k] picks, "counts": [4]}``, ``"groups"``
    beside them where it picks groups first) as a fourth result, None for a
    family whose layers hand back nothing. A family with STATE BESIDE THE
    PAGES is given it as ``conv`` (a row a slot) and ``tail`` (``PagedPool``;
    decode writes no snapshot, so a snapshot pool may stay away, None, and
    goes through as it came) and returns six: the four, then both carried on.
    A row that advances shifts its state and, at the last positions of its
    page, leaves the newest in that page's tail; a row that does not
    (``write_mask`` false) keeps its state and writes no tail.
    """
    import contextlib

    import jax
    import jax.numpy as jnp

    from sentio_tpu.models import layers as L

    family = family_of(cfg)
    dt = cfg.jdtype
    b = tok.shape[0]
    page = k_pages.shape[-1] if family.latent else _page_dim(k_pages)
    pool = {"k": k_pages, "v": v_pages, "conv": conv, "tail": tail}

    positions = lens[:, None]  # [B,1]
    reach = page_table.shape[1] * page
    tables = family.decode_tables(cfg, reach) if family.decode_tables else None
    page_ids = jnp.take_along_axis(page_table, (lens // page)[:, None], axis=1)[:, 0]
    offsets = lens % page
    advancing = jnp.ones((b,), bool) if write_mask is None else write_mask
    per = family.state.per if conv is not None else None   # the kind of state the caller handed in
    if per == "page":
        # the tail's column this position is (negative: none); a row with nothing to leave indexes past the pool
        column = offsets - (page - tail.shape[2])
        tail_ids = jnp.where(advancing & (column >= 0), page_ids, tail.shape[1])
        column = jnp.maximum(column, 0)
    attn_lens, valid = lens, None
    if write_mask is not None:
        page_ids = jnp.where(write_mask, page_ids, 0)
        offsets = jnp.where(write_mask, offsets, 0)
        # a row that does not advance (a free slot, one frozen mid-tick) keeps
        # nothing of this step: its attention reads one block, not its whole
        # length (the decode kernel's cost is the blocks a row's ``lens`` names)
        attn_lens = jnp.where(write_mask, lens, 0)
        valid = write_mask[:, None]

    def attend(q, k, v, layer, window=None, scope=None):
        pool["k"], pool["v"] = _write_kv(pool["k"], pool["v"], layer, page_ids, offsets, k, v, dt, write_impl)
        # (the pool whole and the layer's index: a pages[i] handed to a kernel is a copy of the layer)
        windowed = {} if window is None else {"window": window}   # an impl that knows no window is never told of one
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            return (attn_impl or _paged_attn_xla)(q, pool["k"], pool["v"], layer, page_table, attn_lens,
                                                  cfg.n_heads // cfg.n_kv_heads, **windowed)

    def attend_latent(latent, queries, layer, sm_scale):
        pool["k"] = _latent_write(pool["k"], layer, page_ids, offsets, latent)
        q_lat, q_pe = queries()
        with jax.named_scope("attn.latent"):
            return (attn_impl or _latent_attn_xla)(q_lat, q_pe, pool["k"], layer, page_table, attn_lens, sm_scale)

    def advance_paged(j, step_fn):
        held = pool["conv"]
        out, shifted = step_fn(held[j], None)
        pool["conv"] = held.at[j].set(jnp.where(advancing[:, None, None], shifted, held[j]))
        pool["tail"] = pool["tail"].at[j, tail_ids, column].set(shifted[:, -1], mode="drop")
        return out

    def advance_snapshot(j, step_fn):
        state = pool["conv"]
        if ssm_impl is None:
            out, after = step_fn({name: s[j] for name, s in state.items()}, None)
        else:   # the kernel takes all blocks' states and the mask, and hands them back updated
            out, after = step_fn({"conv": state["conv"][j], "ssm": state["ssm"]},
                                 lambda ssm, *terms: ssm_impl(ssm, j, advancing, *terms))
            state["ssm"] = after.pop("ssm")
        for name in after:
            held = state[name]
            rows = advancing.reshape(b, *([1] * (held.ndim - 2)))
            state[name] = held.at[j].set(jnp.where(rows, after[name].astype(held.dtype), held[j]))
        return out

    picks, counts = {}, []   # a kind of pick → a layer's [B, k] each; the [4] counts, where the family has them

    def note(chosen, n):
        for name, value in chosen.items():
            picks.setdefault(name, []).append(value[:, 0])
        counts[0] = counts[0] + n

    if per == "snapshot":
        pool["conv"] = dict(conv)
    advance = {"page": advance_paged, "snapshot": advance_snapshot}.get(per)
    step = DecodeStep(positions=positions, valid=valid, tables=tables,
                      attend=attend_latent if family.latent else attend, advance=advance, note=note)

    x = L.embed(params["embed_tokens"], tok[:, None], dt)  # [B,1,d]
    if family.picks is not None:
        counts.append(jnp.zeros((4,), jnp.int32))
    for i in range(cfg.n_layers):
        x = family.decode_layer(params[f"layers_{i}"], cfg, i, x, step)
    logits = family.head(params, cfg, x)

    # three results; the picks a fourth where asked for; six for a family that was handed its state
    routed = {**{name: jnp.stack(value) for name, value in picks.items()}, "counts": counts[0]} if counts else None
    out = logits, pool["k"], pool["v"]
    if conv is not None:
        return (*out, routed, pool["conv"], pool["tail"])
    return (*out, routed) if return_routed else out


def _latent_attn_xla(q_lat, q_pe, pages, layer, page_table, lens, sm_scale):
    """Absorbed decode attention over a page table, XLA gather path: each
    row's latent pages of that layer gathered into ``[B, NB*page,
    latent_dim]`` (``kernels/latent_attention.py`` walks the table in VMEM
    instead and is what runs on a TPU). q_lat [B, H, r], q_pe [B, H, rope] →
    o_lat [B, H, r]."""
    import jax.numpy as jnp

    from sentio_tpu.models.deepseek_v2 import latent_attention

    latents = _latent_tokens(pages, (layer, page_table))
    seen = jnp.arange(latents.shape[1])[None, :] <= lens[:, None]  # new token sits at index lens
    return latent_attention(q_lat, q_pe, latents, seen, sm_scale)


def scatter_prefill(k_pages, v_pages, k_cache, v_cache, page_table):
    """Copy a contiguous prefill cache into the pool.

    k/v_cache [L, B, S, Hkv, D] (S a multiple of page size), page_table
    [B, S/page]. Blocks past a row's prompt length should map to scratch
    page 0 in the table — their garbage lands there. A latent pool
    (``v_pages`` None) takes ``k_cache [L, B, S, 1, latent_dim]`` and turns
    each page latent-major on the way.
    """
    lcount, b, s, hkv, hd = k_cache.shape
    if v_pages is None:
        page = k_pages.shape[-1]
        r = k_cache.reshape(lcount, b, s // page, page, hd).swapaxes(-1, -2)
        return k_pages.at[:, page_table].set(r), None
    page = _page_dim(k_pages)
    nb = s // page

    def scatter_one(pages, cache):
        if isinstance(pages, dict):
            q, sc = quantize_kv(cache.reshape(lcount, b, nb, page, hkv, hd))
            return {
                "q": pages["q"].at[:, page_table].set(q),
                "s": pages["s"].at[:, page_table].set(sc.swapaxes(-1, -2)),
            }
        # dim 1 of pages indexed by the [B, NB] table → scatter of [L, B, NB]
        # pages, each written as the [page·Hkv, D] matrix it is in memory (a
        # view, as the decode kernel takes it): asked to scatter
        # [page, Hkv, D] windows at 4 kv heads, the TPU compiler first turns
        # the WHOLE pool head-major and back, K and V, every call
        # (a lane-packed pool's rows hold several heads: the UPDATE takes the
        # pool's shape, the same numbers in the same order)
        rows, lanes = page * pages.shape[-2], pages.shape[-1]
        flat = pages.reshape(lcount, -1, rows, lanes)
        return flat.at[:, page_table].set(
            cache.reshape(lcount, b, nb, rows, lanes)).reshape(pages.shape)

    return scatter_one(k_pages, k_cache), scatter_one(v_pages, v_cache)


# ---------------------------------------------------------------- the engine


@dataclass
class _Slot:
    # one request's stay in a lane: made at admission, never reused. The lane
    # it was admitted to (row of the decode batch and of the host mirrors);
    # it OWNS that lane while ``engine.slots[lane] is slot`` — a spent slot
    # whose lane was handed on lives on in its tick's record until harvested
    lane: int = -1
    request_id: int = -1
    pages: list[int] = field(default_factory=list)
    length: int = 0          # tokens currently in cache (prompt + generated)
    prompt_tokens: int = 0
    max_new: int = 0
    temperature: float = 0.0
    top_k: int = 0
    emitted: list[int] = field(default_factory=list)
    active: bool = False
    # first sampled token still on device (admission defers its fetch; the
    # next tick's packed output materializes it host-side)
    pending_first: bool = False
    # decode sub-steps granted to dispatched-but-unharvested ticks — budget
    # math must count them or a pipelined tick would over-run the limits
    inflight_steps: int = 0
    # tokens served from shared (read-only) prefix-cache pages at the front
    # of this slot's page table — counted in capacity, never freed by retire
    shared_tokens: int = 0
    # radix-cache bookkeeping: the node chain this slot pins (its page table
    # references those pages), the truncated prompt token ids (the insert key
    # once the prompt KV is fully written), and pages whose ownership moved
    # to the cache at insert time (retire must NOT free them)
    prefix_node: object = None
    prompt_ids: Optional[list] = None
    donated: list = field(default_factory=list)
    # wall-clock at submit(); TTFT is measured when the first sampled token
    # becomes host-visible (pending_first flips False)
    submit_t: float = 0.0
    # chunked prefill (prefill_chunk engine option): suffix tokens not yet
    # written to this slot's pages, and how many own tokens already are.
    # While prefill_todo is set the slot holds pages but takes no decode
    # budget — decode ticks for OTHER slots interleave with its segments.
    prefill_todo: Optional[list] = None
    prefill_done: int = 0
    # wall-clock at admission into this slot (the end of slot_wait, the
    # start of prefill) and the prefill dispatches this request took so far
    admit_t: float = 0.0
    prefill_segments: int = 0
    # the flight record this request's spans are written under (None: an
    # untraced caller): its prefill dispatches' completion stamps go there
    trace_id: Optional[str] = None
    # ``keep_choices``: the picks this request's tokens were routed by, one
    # [L, window, k] buffer a kind of choice (-1: not by this request), and
    # the prefill dispatches' picks still on the device (array, row, first
    # position, tokens), fetched when the request retires
    choices: Optional[dict] = None
    choice_parts: list = field(default_factory=list)
    # a family with Mamba layers: the snapshot this request starts from
    # (pinned in the radix cache until its first prefill is dispatched), the
    # tokens its pages matched where that is MORE than it starts behind (the
    # boundary a snapshot is due at: the match was cut back), and the
    # (boundary, snapshot) its prefill dispatches wrote and the radix cache
    # has not been told of yet
    start_snap: Optional[int] = None
    cut_from: int = 0
    snaps_written: list = field(default_factory=list)
    # sampled-token logprob accumulators (sum, min, count) as of the last
    # harvest that folded this request's tokens, read by _retire into the
    # PagedResult; zeros on the spec path, which samples no logprobs
    lp: tuple = (0.0, 0.0, 0)


@dataclass
class _Request:
    request_id: int
    prompt: str
    max_new: int
    temperature: float
    # per-request top-k (0 = off). Rides every sampling dispatch as TRACED
    # int32 data — one compiled program for any k (PR 4's top_k fix), so
    # sampling stays fused inside the decode scan rather than becoming a
    # second logits-then-sample dispatch per tick.
    top_k: int = 0
    submit_t: float = 0.0
    # absolute time.perf_counter() deadline (None = no deadline). The queue
    # drops an expired request BEFORE admission — prefilling for a caller
    # that already gave up wastes exactly the ticks continuous batching is
    # supposed to reclaim (Yu et al., OSDI '22)
    deadline_ts: Optional[float] = None
    # lazily cached tokenization — _admit may inspect a queued request many
    # times (skip-ahead scans the queue every tick) without re-encoding
    tok_ids: Optional[list] = None
    # prior-prefix admission (resume-by-replay, runtime/replica.py): token
    # ids appended after the truncated prompt as already-generated context.
    # The prompt truncation reserve is computed as if max_new were
    # max_new + len(prior_tokens), which reproduces the ORIGINAL
    # admission's truncation exactly — the resumed context is byte-for-byte
    # the dead replica's context at the splice point.
    prior_tokens: Optional[list] = None
    # per-request sampling seed (None = leave the engine RNG stream alone):
    # folded ONCE into the engine's shared RNG at admission. Best-effort —
    # the engine RNG advances per tick for the whole batch, so this only
    # yields reproducible draws when the request is the engine's sole
    # sampled traffic; it is NOT a per-request pinned stream
    seed: Optional[int] = None
    trace_id: Optional[str] = None


@dataclass
class PagedResult:
    request_id: int
    text: str
    tokens: list[int]
    prompt_tokens: int
    finish_reason: str  # "stop" | "length" | "cancelled" | "expired" | "error"
    # prompt tokens actually forwarded at admission vs served read-only from
    # the radix prefix cache (prefill_tokens + prefix_hit_tokens ==
    # prompt_tokens) — the per-request evidence of prefill work skipped
    prefill_tokens: int = 0
    prefix_hit_tokens: int = 0
    # sampled-token logprob accumulators (sum / min / sample count over
    # every token this request sampled, EOS included) — the raw signal the
    # verify confidence gate (ops/confidence.py) scores. count == 0 means
    # no logprobs were observed (cancelled pre-decode, spec-tick path).
    logprob_sum: float = 0.0
    logprob_min: float = 0.0
    logprob_count: int = 0
    # which serving replica's engine produced this result (-1 = a bare
    # engine outside any service); stamped by PagedGenerationService at
    # completion so tracing spans and stats sinks can name the replica
    replica_id: int = -1
    # engine-side stage stamps: when the request was admitted into a slot
    # (perf_counter; 0.0 = never) and how many prefill dispatches its
    # prompt took — what the service turns into slot_wait and prefill
    admit_t: float = 0.0
    prefill_segments: int = 0
    # only from ``run_all(return_choices=True)`` on a family that chooses:
    # ``{"experts": int32 [routed layers, prompt + answer tokens - 1, k]}`` (a
    # family that picks GROUPS first has ``"groups"`` beside it), what
    # each of THIS request's tokens was routed by at every layer — negative
    # where the radix cache served the position (an earlier request's routing)
    choices: Optional[dict] = None

    @property
    def logprob_mean(self) -> Optional[float]:
        if self.logprob_count <= 0:
            return None
        return self.logprob_sum / self.logprob_count

    def stats_dict(self) -> dict:
        """The confidence-gate signal as one dict — THE shape every
        ``stats``/``stats_out`` sink (TpuProvider, generate_stream) fills,
        so the streaming and non-streaming gates can never diverge."""
        out = {
            "logprob_sum": self.logprob_sum,
            "logprob_min": self.logprob_min,
            "logprob_count": self.logprob_count,
            "logprob_mean": self.logprob_mean,
            "tokens": len(self.tokens),
            "prompt_tokens": self.prompt_tokens,
            "finish_reason": self.finish_reason,
        }
        if self.replica_id >= 0:
            out["replica_id"] = self.replica_id
        return out


class ContinuousBatchingEngine:
    """Slot-based continuous batching over the paged pool.

    A fixed decode batch of ``max_slots`` lanes runs one fused decode step
    per tick; requests are admitted into free lanes (prefill → scatter into
    pages) and retired on EOS / length, freeing their pages. The decode
    program compiles ONCE for the server's lifetime — admission changes
    only array *contents* (page tables, lengths, masks), never shapes.

    Single-threaded step() core so tests/bench drive it deterministically;
    serve/ wraps it in an asyncio pump.
    """

    PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)

    def __init__(
        self,
        model_config: Optional[LlamaConfig] = None,
        params=None,
        tokenizer=None,
        max_slots: int = 8,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        max_pages_per_seq: int = 16,
        rng_seed: int = 0,
        use_pallas: Optional[bool] = None,
        steps_per_tick: int = 8,
        max_tick_steps: Optional[int] = None,
        ignore_eos: bool = False,
        pipeline_depth: int = 1,
        mesh=None,
        forward_fn=None,
        kv_quant: str = "none",
        prefill_chunk: Optional[int] = None,
        draft_params=None,
        draft_config=None,
        spec_k: int = 4,
        prefix_cache: bool = True,
        ssm_snapshots: int = 64,
    ) -> None:
        """``forward_fn`` swaps the prefill forward (llama_forward contract)
        of a family that lets it; the fused decode tick walks the layers of
        the configuration's family (``models/families.py``). ``ssm_snapshots``: the
        states a family with Mamba layers keeps for the prefix cache
        (``PagedPool``; ``SSM_SNAPSHOTS``), ignored by every other."""
        import jax

        from sentio_tpu.models.tokenizer import ByteTokenizer

        self.cfg = model_config or LlamaConfig.tiny()
        # what the engine asks of the configuration's family (``models/families.py``)
        family = self.family = family_of(self.cfg)
        cfg_name = type(self.cfg).__name__
        explicit_params = params
        if params is None:
            # seeded init of the configuration's family, placed by its rules
            from sentio_tpu.runtime.weights import load_decoder

            params = load_decoder(
                mesh=mesh, model_config=self.cfg, rng_seed=rng_seed).params
        self.tokenizer = tokenizer or ByteTokenizer(self.cfg.vocab_size)
        # the tree the compiled programs read (models/llama.py
        # ``serving_layout``): made here once from a canonical tree, passed
        # through untouched — the same object, so replicas and
        # ``spawn_fresh`` share it — when ``load_decoder`` or another engine
        # has made it already
        handed, params = params, serving_layout(params)
        if params is not handed:
            logging.getLogger(__name__).info(
                "attention projections stored as the programs read them: "
                "wq, wk and wv of %d layers turned [out, in]", self.cfg.n_layers)
        if mesh is None and not all(
                isinstance(leaf, jax.Array)
                for leaf in jax.tree_util.tree_leaves(params)):
            # a tree handed over as host numpy goes to the device ONCE,
            # here — as jit arguments its leaves would be uploaded again on
            # every dispatch. A tree already on the device is kept as it is
            # (replicas share ONE copy of the weights by identity). Under a
            # mesh the caller has placed the tree by its sharding rules.
            params = jax.device_put(params)
        self.params = params
        # its record's facts, as the host code reads them: expert layers that
        # hand back their picks and pairs; ONE latent a token and layer in
        # place of K and V; state beside the pool (``PagedPool.conv``,
        # ``.tail``) threaded through the four programs, per slot and per page
        # or per slot and in a bounded pool of snapshots
        self.routed = family.picks is not None
        self.latent = family.latent
        self.slot_state = family.state is not None
        self.conv_state = self.slot_state and family.state.per == "page"
        self.ssm_state = self.slot_state and family.state.per == "snapshot"
        # what the family is not served with, and its reason
        for what, asked_for in (("draft", draft_params is not None), ("mesh", mesh is not None),
                                ("int8", kv_quant != "none")):
            if asked_for and (reason := family.refusal(what, self.cfg)):
                raise ValueError(f"kv_quant={kv_quant!r}: {reason}" if what == "int8" else reason)
        if self.ssm_state and page_size % family.state.page_tokens(self.cfg):
            raise ValueError(f"page_size={page_size}: a snapshot is the scan's state at a chunk boundary, "
                             f"so a page is whole chunks of {family.state.page_tokens(self.cfg)} tokens")
        # a kind of choice → how many a token: a request's ``choices`` hold one buffer a kind
        self._choice_depths = family.picks(self.cfg) if self.routed else {}
        # how the decode program's grouped expert matmuls are tiled: decided
        # from shapes when it is traced (``models/moe.py::expert_tile``), so
        # said once, here and in ``stats()``
        self._expert_tiles = None
        if family.expert_tiles is not None:
            layer = next(lp["moe"] for lp in self.params.values() if isinstance(lp, dict) and "moe" in lp)
            self._expert_tiles = family.expert_tiles(layer, self.cfg, max_slots)
            logging.getLogger(__name__).info(
                "grouped expert matmuls of a decode step, [rows, tk, tn] and grid steps an expert: %s",
                ", ".join(f"{name} {t['tile']} x{t['steps_per_expert']}"
                          for name, t in self._expert_tiles.items()))
        if forward_fn is None:
            forward_fn = family.forward
        elif forward_fn is not family.forward:
            if self.routed:
                raise ValueError(f"a {cfg_name} model prefills through {family.forward.__name__}")
            if explicit_params is None:
                raise ValueError(
                    f"forward_fn {getattr(forward_fn, '__name__', forward_fn)} does not match the {cfg_name} "
                    f"model family ({family.forward.__name__}): pass matching params explicitly (the default "
                    "init builds the config family's tree)")
        self.forward_fn = forward_fn
        self.max_slots = max_slots
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        # decode sub-steps fused into ONE device dispatch per tick: host
        # round trips amortize over the chunk.
        # Admission latency grows by at most steps_per_tick decode steps.
        self.steps_per_tick = max(int(steps_per_tick), 1)
        # with an EMPTY queue nothing waits on admission, so ticks may grow
        # to this cap (rounded to a bucket) — the whole remaining generation
        # of the longest row can ride one dispatch + one fetch
        self.max_tick_steps = max(int(max_tick_steps), self.steps_per_tick) \
            if max_tick_steps is not None else self.steps_per_tick
        # benchmark workloads: random-init weights frequently greedy-sample
        # EOS immediately; fixed-length generation measures the real cost
        self.ignore_eos = bool(ignore_eos)
        # depth 2 dispatches tick N+1 BEFORE fetching tick N's tokens, so
        # the ~RTT host fetch overlaps device compute. Decode state (tok/
        # lens/halted) is carried ON DEVICE between ticks; EOS halting and
        # budget schedules are device/deterministic, so the speculative tick
        # is always semantically correct — at worst it spends masked
        # sub-steps on rows the harvest then retires. Depth 1 = synchronous;
        # a single in-flight record means deeper values are not supported.
        self.pipeline_depth = min(max(int(pipeline_depth), 1), 2)
        self.mesh = mesh
        # chunked prefill (vLLM-style): prompts longer than this admit as
        # page-aligned segments, ONE segment dispatch per tick, so a 4-8K
        # prefill never stalls other slots' decode for its whole length —
        # each tick pays at most one segment of prefill latency. None = off
        # (whole-prompt admission, the default).
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk <= 0 or prefill_chunk % page_size:
                raise ValueError(
                    f"prefill_chunk must be a positive multiple of page_size "
                    f"({page_size}), got {prefill_chunk}"
                )
        self.prefill_chunk = prefill_chunk
        # paged speculative decoding (runtime/paged_spec.py): a draft model
        # turns each decode tick into draft/verify/accept rounds — exact by
        # construction (greedy rows bit-exact, sampled rows marginally
        # exact) while continuous batching keeps working
        self.draft_params = None
        self.draft_cfg = draft_config
        self.spec_k = max(int(spec_k), 1)
        self._spec_tick = None
        self._spec_dk = self._spec_dv = None
        if draft_params is not None:
            if draft_config is None:
                raise ValueError("draft_params requires draft_config")
            if mesh is not None:
                raise ValueError("paged speculation does not support a mesh yet")
            if prefill_chunk is not None:
                raise ValueError(
                    "paged speculation and chunked prefill are mutually "
                    "exclusive (the draft prefills whole prompts)"
                )
            if draft_config.vocab_size != self.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_config.vocab_size} != target "
                    f"vocab {self.cfg.vocab_size}"
                )
            self.draft_params = serving_layout(draft_params)
        if kv_quant not in ("none", "int8"):
            raise ValueError(f"kv_quant must be 'none' or 'int8', got {kv_quant!r}")
        # int8 pages: ~half the pool HBM and decode-read bandwidth; scales
        # add D-th of the bf16 footprint back
        self.kv_quant = kv_quant
        if num_pages is None:
            num_pages = 1 + max_slots * max_pages_per_seq
        # heads narrower than a tile's 128 lanes: where the decode kernel is
        # wanted (the same ask as below) the pool is made lane-packed, kv
        # heads side by side in a row, so that its pages are whole tiles
        # (``init_pool``); bf16 pages on one device, and not under
        # speculation, whose dense cache reads the pool's shape
        # the snapshot pool's slots (at least one: the programs index it)
        self._snapshots = max(int(ssm_snapshots), 1) if self.ssm_state else 0
        self._kv_pack = 1
        if (jax.default_backend() == "tpu" if use_pallas is None else use_pallas) \
                and kv_quant == "none" and mesh is None and draft_params is None and not self.latent:
            from sentio_tpu.kernels.paged_attention import lane_packing

            self._kv_pack = lane_packing(self.cfg.n_kv_heads, self.cfg.head_dim)
        with span("pool.alloc", pages=int(num_pages)) as alloc:
            self.pool = init_pool(
                self.cfg, num_pages, page_size, mesh=mesh,
                quantized=kv_quant == "int8", slots=max_slots, pack=self._kv_pack,
                snapshots=self._snapshots,
            )
            jax.block_until_ready(self.pool.k)  # the span's seconds are the allocation's
            alloc.fields["bytes"] = int(self.pool.hbm_bytes)
        self.allocator = PageAllocator(num_pages)  # guarded-by: engine-thread

        # SENTIO_SANITIZE=1: single-driver-thread guard on mutating entry
        # points + page-conservation / radix-refcount checks per tick. None
        # when disabled, so the steady-state cost is one attribute test.
        self._san = engine_guard("ContinuousBatchingEngine")

        self.slots = [_Slot(lane=i) for i in range(max_slots)]  # guarded-by: engine-thread
        self.last_tick_active = 0
        # tick-phase attribution (infra/phases.py): reset at the top of
        # every step(), accumulated by the dispatch helpers, closed out at
        # the bottom of step() into last_step_phases (seconds per phase,
        # keys == ENGINE_PHASES) — the serving pump merges its own
        # inbox_drain/deliver sections in and records the full phase_ms
        # dict on the flight tick event. Plain perf_counter deltas.
        self._phase = PhaseTimer(ENGINE_PHASES)  # guarded-by: engine-thread
        self.last_step_phases: dict = dict.fromkeys(ENGINE_PHASES, 0.0)  # guarded-by: engine-thread
        # device sub-steps actually executed (the scan runs its full static
        # length; every sub-step streams the weights once) — throughput and
        # HBM-utilization math must use this, not ticks x steps_per_tick
        self.total_sub_steps = 0
        # lifetime prefill-vs-decode token split: the flight recorder's pump
        # diffs these per tick to attribute each tick's work. Prefill counts
        # tokens actually forwarded (suffix-only on a prefix hit; per-segment
        # under chunked prefill); decode counts every folded sampled token.
        self.prefill_tokens_total = 0
        self.decode_tokens_total = 0
        # what the slots did with the sub-steps the device ran, counted when
        # a tick is harvested: slots x sub-steps row-steps, each useful (its
        # token was folded into an answer), halted (a request held the slot,
        # but the row had finished, spent its budget or was still
        # prefilling) or empty (no request in the slot). Lifetime totals,
        # and the same for the tick(s) harvested by the latest step().
        self.row_steps_total = dict.fromkeys(ROW_STEP_KINDS, 0)
        self.last_tick_row_steps = dict.fromkeys(ROW_STEP_KINDS, 0)
        self.last_tick_sub_steps = 0
        # K/V page blocks of those sub-steps, counted at dispatch by the
        # decode kernel's own rule (``_kv_pages``) and booked at harvest:
        # ``held`` what its walk copies and computes, ``tabled`` every cell
        # of every page table (held / tabled is the share of a walk of the
        # table that is work), ``behind_window`` what the rows hold where a
        # layer's window no longer reaches
        self.kv_pages_total = dict.fromkeys(KV_PAGE_KINDS, 0)
        self.last_tick_kv_pages = dict.fromkeys(KV_PAGE_KINDS, 0)
        # a routed family's expert layers, summed ON THE DEVICE inside the
        # decode tick and the prefill programs and fetched as four more rows
        # of the tick's packed tokens (no fetch of their own): pairs of token
        # and pick ``routed`` over all experts and ``held`` here, and of the
        # ``held`` experts x layers x decode sub-steps those a pair ``touched``
        self.moe_total = dict.fromkeys(MOE_KINDS, 0)
        self.last_tick_moe = dict.fromkeys(MOE_KINDS, 0)
        self._moe_acc = None  # the prefill programs' pairs since the last tick, on the device
        self._moe_zero = None  # four zeros on the device, made at the first dispatch that needs them
        # a latent family's prefill dispatches, from their own integers: tokens
        # a call computed (``new``) and prior tokens whose latents that call
        # turned back into keys and values (``expanded``: every segment after
        # the first, and every radix hit, expands its whole prior). Counted at
        # dispatch, booked with the next harvested tick beside the row-steps
        self.prefill_latent_total = dict.fromkeys(PREFILL_LATENT_KINDS, 0)
        self.last_tick_prefill_latent = dict.fromkeys(PREFILL_LATENT_KINDS, 0)
        self._prefill_latent_pending = dict.fromkeys(PREFILL_LATENT_KINDS, 0)
        # a family with convolution state: what each row of a prefill dispatch
        # STARTED from — ``zero`` (position 0), ``tail`` (a cached page's
        # stored tail: a radix hit) or ``carried`` (a later segment of a
        # chunked prompt, from the tail its own earlier segment left) — and
        # the page tails written (``pages``: by prefill for the pages it
        # filled, by decode when a page filled). Counted on the host, booked
        # with the next harvested tick beside the row-steps
        self.conv_state_total = dict.fromkeys(CONV_STATE_KINDS, 0)
        self.last_tick_conv_state = dict.fromkeys(CONV_STATE_KINDS, 0)
        self._conv_state_pending = dict.fromkeys(CONV_STATE_KINDS, 0)
        # a family with Mamba layers, the same three books: what each row of a
        # prefill dispatch STARTED from — ``zero``, ``snapshot`` (a radix hit,
        # cut back to a boundary that kept its state) or ``carried`` (a later
        # segment of a chunked prompt, from its slot) —, the snapshots
        # ``written`` (handed a slot and filled by a prefill dispatch) and
        # ``evicted`` (a slot taken from its boundary for another), and the
        # tokens the pages matched that were computed again for want of a
        # snapshot (``cut_back_tokens``)
        self.ssm_state_total = dict.fromkeys(SSM_STATE_KINDS, 0)
        self.last_tick_ssm_state = dict.fromkeys(SSM_STATE_KINDS, 0)
        self._ssm_state_pending = dict.fromkeys(SSM_STATE_KINDS, 0)
        # chunked prefill dispatches ONE segment a tick over all slots: a
        # tick in which n slots hold a pending segment books one turn
        # ``taken`` and n - 1 ``waited``. Counted in ``_advance_prefill``
        # from the list it builds, booked with the next harvested tick
        self.prefill_turns_total = dict.fromkeys(PREFILL_TURN_KINDS, 0)
        self.last_tick_prefill_turns = dict.fromkeys(PREFILL_TURN_KINDS, 0)
        self._prefill_turns_pending = dict.fromkeys(PREFILL_TURN_KINDS, 0)
        # admissions by the lane they took: ``free`` held no request,
        # ``spent`` held a row whose last tokens ride the tick in flight
        # (``_spent``) and was handed on before that tick's harvest. Counted
        # in ``_admit``, inside the step() that the last_tick dict belongs to
        self.lane_admissions_total = dict.fromkeys(LANE_ADMISSION_KINDS, 0)
        self.last_tick_lane_admissions = dict.fromkeys(LANE_ADMISSION_KINDS, 0)
        # the pump's step number, carried by this engine's completion stamps
        # (infra/tracing.py::dispatching); 0 outside a pump
        self.tick_step = 0
        # ``run_all(return_choices=True)``: each result carries the picks its
        # OWN tokens were routed by (fetched when asked for, never otherwise)
        self.keep_choices = False
        self._queue: list[_Request] = []  # guarded-by: engine-thread
        # skip-ahead admission: a request too large for the current free
        # pages may be jumped by later, smaller requests — but only
        # head_skip_bound times, after which the head gets strict FIFO
        # priority (starvation bound). Counts reset when the head admits.
        self.head_skip_bound = 16
        self._head_skips = 0
        # TTFT telemetry: submit() → first token host-visible, seconds
        self.ttft_samples: deque = deque(maxlen=1024)
        self.ttft_count = 0
        # automatic radix prefix cache (runtime/radix.py): every admitted
        # prompt's full-page KV is inserted into a token-id radix tree and
        # later requests — including the verify node reusing the generate
        # node's prompt head — longest-prefix-match against it, prefilling
        # only their unmatched suffix. prefix_cache=False (PREFIX_CACHE=0)
        # disables it entirely: every admission takes the cold prefill path,
        # byte-for-byte the pre-cache behavior.
        self._prefix_cache_enabled = bool(prefix_cache)
        if self._prefix_cache_enabled:
            from sentio_tpu.runtime.radix import RadixPrefixCache

            self._radix = RadixPrefixCache(page_size, self.allocator, self._snapshots)
        else:
            self._radix = None
        # operator visibility for the BPE-boundary failure mode: a cached
        # head that never token-matches is silent otherwise (correct output,
        # zero benefit). Hits/misses count admissions against a non-empty
        # cache; the *_tokens totals count matched vs forwarded prompt
        # tokens — the number the prefill-skip claim is audited by.
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens_total = 0
        self.prefix_miss_tokens_total = 0
        # paged-speculation efficiency: emitted/verifies = tokens-per-verify
        # (how well the draft predicts the target — the number that decides
        # whether the draft pays for itself)
        self.spec_emitted_total = 0
        self.spec_verifies_total = 0
        self._finished_buffer: list[PagedResult] = []  # guarded-by: engine-thread
        # (first tokens, their logprobs — device arrays —, [slot_idx, ...], the
        # rows' convolution state on the device or None) per admission chunk,
        # consumed by the next decode tick
        self._pending_first: list = []  # guarded-by: engine-thread
        # optional callable the serving layer sets so ticks stay SHORT when
        # callers are waiting upstream of the engine's own queue (the
        # service inbox) — the engine queue alone can't see them
        self.pressure_hint = None
        # warmup override: pins the next ticks' fused-scan length to one
        # declared ladder rung so the compile fence can warm every rung
        # deterministically instead of racing a backlog into existence
        # (service.warmup); ignored unless the value is in tick_step_sizes()
        self.force_tick_steps: Optional[int] = None
        # device-resident decode carry (tok, lens, halted) threaded from the
        # previous tick's outputs; None until the first dispatch
        self._dev_state = None
        # dispatched-but-unfetched tick awaiting harvest (pipeline_depth 2)
        self._inflight: Optional[dict] = None
        self._next_id = itertools.count()
        self._rng = jax.random.PRNGKey(rng_seed + 1)
        # host mirrors of device state, re-uploaded when admission changes them
        self._page_table = np.zeros((max_slots, max_pages_per_seq), np.int32)
        self._lens = np.zeros(max_slots, np.int32)
        self._temps = np.zeros(max_slots, np.float32)
        self._top_ks = np.zeros(max_slots, np.int32)
        self._last_tok = np.zeros(max_slots, np.int32)
        # On TPU the Pallas paged-attention kernel reads the [L, P, ...] pool
        # where it lies: it walks the blocks each row HOLDS (its ``lens``
        # names them; one for a free slot) and copies each by its own DMA,
        # so its time follows what the rows hold, not the size of the page
        # table. The XLA gather path is what runs elsewhere: the CPU test
        # path, and on TPU a geometry whose pages the DMA cannot bring
        # (``untiled``: a head_dim under 128, int8 pages under 128 tokens or
        # with one kv head a device — XLA does not store such a pool in the
        # order of its shape, and until PR 29 every layer-call was handed a
        # copy of it). This is a SELECTION from static facts, made once and
        # logged: nothing catches a kernel failure and carries on with the
        # other path, and a caller who ASKS for the kernel at such a geometry
        # is refused. Under a mesh the kernel runs inside shard_map over tp
        # (heads-sharded pool). int8 pools go through the same walk with
        # their scale pages copied beside the int8 pages, so kv_quant="int8"
        # keeps the fast path
        asked = self._use_pallas_asked = use_pallas
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        if use_pallas and jax.default_backend() == "tpu":
            from sentio_tpu.kernels.latent_attention import latent_untiled
            from sentio_tpu.kernels.paged_attention import untiled
            from sentio_tpu.parallel.mesh import AXIS_TP

            tp = mesh.shape[AXIS_TP] if mesh is not None else 1
            why = latent_untiled(page_size, self.cfg.kv_lora_rank, self.cfg.qk_rope_head_dim) \
                if self.latent else untiled(page_size, self.cfg.n_kv_heads // tp // self._kv_pack,
                                            self.cfg.head_dim * self._kv_pack, kv_quant == "int8")
            if why and asked:
                raise ValueError(f"use_pallas=True, but {why}")
            if why:
                logging.getLogger(__name__).warning(
                    "decode attention runs the XLA gather path, not the "
                    "paged kernel: %s", why)
                use_pallas = False
        self._attn_impl = None
        if use_pallas and self.latent:
            from sentio_tpu.kernels.latent_attention import make_latent_attn_impl

            self._attn_impl = make_latent_attn_impl()
        elif use_pallas:
            from sentio_tpu.kernels.paged_attention import make_paged_attn_impl

            self._attn_impl = make_paged_attn_impl(mesh=mesh)
        # The step's K and V rows are written by a kernel too where the walk
        # above was chosen and ``page_write_path`` says so of the pool: a
        # plain bf16 pool on one device, small enough for the compiler to
        # place in nearer memory — which it then moves there for the XLA
        # scatter and back for the walk, every sub-step. Every other pool
        # keeps the scatter, and its program
        self._write_impl = None
        if self._attn_impl is not None and not self.latent:
            from sentio_tpu.kernels.page_write import make_page_write_impl, page_write_path

            if page_write_path(self.pool.k, mesh) == "pallas":
                self._write_impl = make_page_write_impl()
        logging.getLogger(__name__).info(
            "decode writes K and V into the pool by %s",
            "the page-write kernel, in place in HBM" if self._write_impl is not None else "the XLA scatter")
        # A Mamba family's one-token update of ``pool.conv["ssm"]`` likewise,
        # where the kernels were asked for and ``ssm_update_path`` says so of
        # the state: float32 in whole tiles on one device — a matrix a head
        # (Mamba-2) or a row's ``[N, inner]`` (Mamba-1), the adapter reads
        # which from the rank. The narrow rehearsal widths and a state under
        # a mesh keep the model's own sum and the masked ``.at[j].set``
        self._ssm_impl = None
        if use_pallas and self.ssm_state:
            from sentio_tpu.kernels.ssm_update import make_ssm_update_impl, ssm_update_path

            if ssm_update_path(self.pool.conv["ssm"], mesh) == "pallas":
                self._ssm_impl = make_ssm_update_impl()
        if self.ssm_state:
            logging.getLogger(__name__).info(
                "decode updates the Mamba state by %s",
                "the ssm-update kernel: the advancing rows' state read once and written in place"
                if self._ssm_impl is not None else "the XLA form, every slot's state")
        # The prefill programs' attention is chosen HERE too, by the same
        # ask: the flash kernel that knows a prior (kernels/
        # prefill_attention.py) on TPU, and where a test asks for it on the
        # CPU (interpret mode), bound into ``self.forward_fn`` so that every
        # caller of the engine's forward — the two prefill programs, the
        # benchmark's reference check — runs the one path. It has its own
        # geometry rule (a head must be whole lane tiles), so decode may keep
        # its XLA gather where prefill takes its kernel. Left as XLA, with the
        # line logged: under a mesh (the kernel has no shard_map wrapper
        # yet), and a forward the caller brought (its attention is its own)
        self._family_forward = self.forward_fn
        self._prefill_attn = None
        if asked or (asked is None and jax.default_backend() == "tpu"):
            from sentio_tpu.kernels.prefill_attention import make_prefill_attn_fn, prefill_untiled

            why = ""
            if self.forward_fn is not family.forward:
                why = "the caller brought its own forward_fn"
            elif mesh is not None:
                why = "the prefill kernel runs on one device a process, and this engine has a mesh"
            elif jax.default_backend() == "tpu":
                why = prefill_untiled(self.cfg.qk_nope_head_dim if self.latent else self.cfg.head_dim,
                                      self.cfg.v_head_dim if self.latent else self.cfg.head_dim)
            if why:
                logging.getLogger(__name__).warning(
                    "prefill attention runs the XLA form, not the flash kernel: %s", why)
            else:
                self._prefill_attn = make_prefill_attn_fn()
                self.forward_fn = functools.partial(self.forward_fn, attn_fn=self._prefill_attn)
        self._build_fns()

    # ------------------------------------------------------------- compiled

    def _build_fns(self) -> None:
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        attn_impl = self._attn_impl
        write_impl = self._write_impl
        ssm_impl = self._ssm_impl
        forward_fn = self.forward_fn
        eos_id = self.tokenizer.eos_id

        ignore_eos = self.ignore_eos
        routed = self.routed
        # a forward that computes its head at ONE position a row where asked: an admission reads no other
        family = self.family
        # (only of the family's forward called bare: one the prefill kernel is bound into, a ``partial``,
        # keeps its head at every position until ROADMAP S15 turns that on with a measurement of its own)
        last_only = family.logits_at and forward_fn is family.forward

        def last_logits(logits, lens):
            """[B, V] at each row's last token, of all positions' or of that one's."""
            return logits[:, 0] if last_only else \
                jnp.take_along_axis(logits, (lens - 1)[:, None, None], axis=1)[:, 0]

        @jit_family("paged.step_n", static_argnames=("steps",),
                    donate_argnums=(5, 6), donate_argnames=("conv", "tail"))
        def step_n(params, tok, lens, halted, page_table, k_pages, v_pages,
                   rng, temps, top_ks, budgets, lp_sum, lp_min, lp_cnt,
                   steps, moe_acc=None, conv=None, tail=None):
            """``steps`` decode sub-steps fused into one dispatch (lax.scan).

            Per-row ``budgets`` bound how far each row may advance (token
            budget / page capacity, mirrored host-side); rows halt early on
            EOS. Frozen rows keep their lens/tok and write to scratch.
            Returns per-step sampled tokens [1+steps, B] plus one packed
            [3, B] float32 logprob-state array — the ONLY arrays the host
            fetches per tick — and the carried (tok, lens, halted, lp_sum,
            lp_min, lp_cnt) DEVICE state, so the next tick can dispatch
            without waiting for this tick's fetch (pipelining) and without
            re-uploading host mirrors. The execution mask is not returned:
            the host replay reconstructs it exactly from its own budgets
            plus first-EOS.

            ``lp_sum``/``lp_min``/``lp_cnt`` are per-slot RUNNING logprob
            accumulators (sum, min, sample count over every token this
            request sampled, including an EOS) carried in the scan body as
            traced state — the confidence gate's raw signal, accumulated
            with zero extra dispatches. Admission seeds them with the first
            token's logprob via ``merge_admitted``.

            A routed family (``moe_acc`` [4] int32: the pairs its prefill
            programs routed since the last tick) carries its expert layers'
            counts through the scan, returns them as four more rows of
            ``packed`` and adds one result: every sub-step's picks
            ``{"experts": [steps, L, B, k]}`` (one entry a kind of choice),
            which stay on the device unless a caller asked for them.

            A family with convolution state (``conv`` [Lc, B, 2, d], a row a
            slot, and the page tails ``tail`` [Lc, P, 2, d]; both donated)
            carries both through the scan — a row that does not advance in a
            sub-step keeps its state — and returns them last. A family with
            MAMBA layers hands in its slots' state as ``conv`` alone: decode
            writes no snapshot, and the last result is None.
            """
            from sentio_tpu.runtime.sampling import sample_tokens

            def picks_of(moe):
                return {name: moe[name] for name in moe if name != "counts"}

            def body(carry, idx):
                # ``more``: what only some families carry (the expert layers'
                # counts under "moe", the convolution state under its names)
                (tok, lens, k_pages, v_pages, rng, halted,
                 lp_sum, lp_min, lp_cnt, more) = carry
                active = (~halted) & (idx < budgets)
                state = {name: more[name] for name in ("conv", "tail") if name in more}
                logits, k_pages, v_pages, *moe = paged_decode_forward(
                    params, cfg, tok, lens, page_table, k_pages, v_pages,
                    attn_impl=attn_impl, write_mask=active, return_routed=routed,
                    write_impl=write_impl, ssm_impl=ssm_impl, **state,
                )
                rng, sub = jax.random.split(rng)
                # temperature AND top-k sample INSIDE the scan body — the
                # tick is one dispatch, never logits-then-sample. top_ks is
                # traced [B] int32; k<=0 rows keep the full distribution.
                nxt, lp = sample_tokens(logits, sub, temps, top_k=top_ks)
                tok = jnp.where(active, nxt, tok)
                lens = jnp.where(active, lens + 1, lens)
                lp_sum = jnp.where(active, lp_sum + lp, lp_sum)
                lp_min = jnp.where(active, jnp.minimum(lp_min, lp), lp_min)
                lp_cnt = jnp.where(active, lp_cnt + 1, lp_cnt)
                if not ignore_eos:
                    halted = halted | (active & (nxt == eos_id))
                more = dict(more)
                if routed:
                    more["moe"] = more["moe"] + moe[0]["counts"]
                more.update({name: new for name, new in zip(("conv", "tail"), moe[1:]) if name in state})
                return (tok, lens, k_pages, v_pages, rng, halted, lp_sum, lp_min,
                        lp_cnt, more), ((nxt, picks_of(moe[0])) if routed else nxt)

            tok_in = tok
            # rows whose (deferred) first token is already EOS never run
            if not ignore_eos:
                halted = halted | (tok == eos_id)
            more = {"moe": moe_acc} if routed else {}
            more.update({name: arr for name, arr in (("conv", conv), ("tail", tail)) if arr is not None})
            (tok, lens, k_pages, v_pages, rng, halted,
             lp_sum, lp_min, lp_cnt, more), toks = jax.lax.scan(
                body, (tok, lens, k_pages, v_pages, rng, halted, lp_sum, lp_min, lp_cnt, more),
                jnp.arange(steps)
            )
            if routed:
                toks, picks = toks
            # packed [1 + steps, B]: row 0 echoes the INPUT tokens so freshly
            # admitted rows' device-resident first tokens reach the host in
            # the same single fetch as the tick outputs
            packed = jnp.concatenate([tok_in[None, :], toks], axis=0)
            if routed:  # [4, B] more: each count across its row
                packed = jnp.concatenate(
                    [packed, jnp.broadcast_to(more["moe"][:, None], (4, packed.shape[1]))], axis=0)
            # one [3, B] fetch (not three): final accumulators, harvested
            # into the host mirrors the retiring PagedResult reads
            lp_state = jnp.stack(
                [lp_sum, lp_min, lp_cnt.astype(jnp.float32)], axis=0
            )
            out = (packed, lp_state, tok, lens, halted,
                   lp_sum, lp_min, lp_cnt, k_pages, v_pages, rng)
            if routed:
                out = (*out, picks)
            return out if conv is None else (*out, more["conv"], more.get("tail"))

        self._step_n = step_n

        @jit_family("paged.merge_admitted", donate_argnames=("conv",))
        def merge_admitted(tok, lens, halted, lp_sum, lp_min, lp_cnt,
                           first, first_lp, new_lens, idxs, conv=None, conv_rows=None):
            """Scatter admission's device-resident first tokens (plus their
            prompt lengths, a cleared halt flag, and the first token's
            logprob seeding the per-slot confidence accumulators) into the
            carried decode state. ``idxs`` pads to ``first``'s length with
            an out-of-range index; mode='drop' discards the pad rows. A
            family with convolution state hands in the slots' state ``conv``
            [Lc, slots, 2, d] (donated) and the admitted rows' ``conv_rows``
            [Lc, rows, 2, d] as their prefill left it: a slot's new request
            starts from ITS prompt's state, whatever the last one left."""
            if conv is not None:
                conv = conv.at[:, idxs].set(conv_rows, mode="drop")
            tok = tok.at[idxs].set(first, mode="drop")
            lens = lens.at[idxs].set(new_lens, mode="drop")
            halted = halted.at[idxs].set(False, mode="drop")
            lp_sum = lp_sum.at[idxs].set(first_lp, mode="drop")
            lp_min = lp_min.at[idxs].set(first_lp, mode="drop")
            lp_cnt = lp_cnt.at[idxs].set(1, mode="drop")
            out = tok, lens, halted, lp_sum, lp_min, lp_cnt
            return out if conv is None else (*out, conv)

        self._merge_admitted = merge_admitted

        @jit_family("paged.prefill_scatter", donate_argnums=(7, 8), donate_argnames=("tail", "conv"))
        def prefill_scatter(params, ids, positions, lens, rng, temps, scat,
                            k_pages, v_pages, top_ks, moe_acc=None, tail=None, conv=None, snap=None):
            """Batched admission in ONE dispatch: contiguous prefill forward,
            cache scatter into each row's pages, first-token sample (token +
            its logprob, seeding the confidence accumulators) from each
            row's last prompt logit. Pad rows scatter to scratch page 0. A
            routed family returns one more: ``{"experts": [L, B, width, k]
            picks, "counts": moe_acc + the pairs this call routed}``. A
            family with convolution state (``tail`` [Lc, P, 2, d], donated)
            starts every row from zeros and returns two more: each row's
            state after ITS prompt [Lc, B, 2, d] (the merge into the decode
            batch puts it in the row's slot) and ``tail`` with the tails of
            the pages this call filled. A family with MAMBA layers (``tail``
            the snapshot pool, ``conv`` its slots' state, both donated; ``snap``
            small host arrays a row: ``slot`` [B], and the chunk boundaries
            ``at`` [B, K] whose state goes to the snapshots ``ids`` [B, K], an
            id past the pool for none) starts every row from zeros, puts each
            row's state after ITS prompt in its slot, and returns ``conv`` and
            ``tail`` so written."""
            from sentio_tpu.runtime.sampling import sample_tokens

            b, width = ids.shape
            cache = ssm_start(new_cache(b, width, width // page_size), snap, conv, tail)
            # pad tails and junk admission rows must not claim routed-expert
            # capacity (llama ignores the mask on the cache path)
            pad_mask = jnp.arange(width)[None, :] < lens[:, None]
            logits, cache, *moe = forward_fn(
                params, cfg, ids, positions=positions, cache=cache, cache_index=0,
                pad_mask=pad_mask, **({"logits_at": lens - 1} if last_only else {}),
            )
            k_pages, v_pages = scatter_prefill(
                k_pages, v_pages, cache["k"], cache["v"], scat
            )
            rng, sub = jax.random.split(rng)
            first, first_lp = sample_tokens(last_logits(logits, lens), sub, temps, top_k=top_ks)
            out = first, first_lp, k_pages, v_pages, rng
            return prefill_results(out, moe, moe_acc, cache, scat, tail, conv, snap)

        def prefill_results(out, moe, moe_acc, cache, scat, tail, conv, snap):
            """A prefill program's five results, then what its family adds."""
            if routed:
                out = (*out, prefill_routed(moe[0], moe_acc))
            if snap is not None:  # each row's state into its slot, [Lm, B, K, ...] into the snapshots asked
                out = (*out,
                       {name: conv[name].at[:, snap["slot"]].set(cache["state"][name], mode="drop") for name in conv},
                       {name: tail[name].at[:, snap["ids"]].set(cache["snaps"][name], mode="drop") for name in tail})
            elif tail is not None:  # [Lc, B, NB, 2, d] at the [B, NB] pages the call filled
                out = (*out, cache["conv"], tail.at[:, scat].set(cache["tail"]))
            return out

        def ssm_start(cache, snap, conv, tail):
            """A family with Mamba layers: the cache with the boundaries to
            snapshot, and each row's state as ``snap["start"]`` [B] says (a
            snapshot's id; -1 zeros, the only start without the key; -2 the
            row's own slot: a chunked prompt's earlier segment left it there)."""
            if snap is None:
                return cache
            cache = dict(cache, snap_at=snap["at"])
            if "start" in snap:
                start, slot = snap["start"], jnp.minimum(snap["slot"], max_slots - 1)

                def pick(name):
                    rows = (1, -1) + (1,) * (tail[name].ndim - 2)
                    return jnp.where((start >= 0).reshape(rows), tail[name][:, jnp.maximum(start, 0)],
                                     jnp.where((start == -2).reshape(rows), conv[name][:, slot], 0))

                cache["state"] = {name: pick(name) for name in cache["state"]}
            return cache

        def prefill_routed(moe, moe_acc):
            # a prefill's pairs are counted; expert-steps are the decode tick's
            return {**moe, "counts": moe_acc + moe["counts"] * jnp.asarray([1, 1, 0, 0], jnp.int32)}

        latent = self.latent

        conv_state, ssm_state = self.conv_state, self.ssm_state
        page_size, max_slots = self.page_size, self.max_slots

        def new_cache(rows, length, pages=0):
            """The contiguous cache a prefill fills, as the family makes it
            (``pages``: the new tokens' pages, whose tails its forward leaves)."""
            beside = {"page": (pages,), "snapshot": (SNAPS_PER_ROW,)}[family.state.per] if family.state else ()
            return family.init_cache(cfg, rows, length, *beside)

        self._prefill_scatter = prefill_scatter

        @jit_family("paged.prior_prefill_scatter", static_argnames=("do_sample",),
                    donate_argnums=(7, 8), donate_argnames=("tail", "conv"))
        def prior_prefill_scatter(params, ids, positions, lens, rng, temps,
                                  scat, k_pages, v_pages, prior_table,
                                  n_prior, top_ks, do_sample, moe_acc=None, tail=None, conv=None, snap=None):
            """Prefill a batch of suffixes against per-row prior KV already
            in the pool — ONE compiled family for both radix-cache admission
            (prior = the matched shared-prefix pages) and chunked-prefill
            segments (prior = the row's own earlier segments + any matched
            prefix). Primes a contiguous cache from each row's prior pages,
            runs the suffix tokens at per-row offset positions, scatters
            only the new blocks.

            ``prior_table`` [B, PNB] is padded to a power-of-two page-count
            bucket with scratch page 0 and ``n_prior`` [B] carries the TRUE
            per-row prior lengths (traced, not static): pad pages' garbage
            stays masked because every key index past a row's real tokens
            exceeds all of its query positions, and the bucketing bounds
            compile variants to O(log window) instead of one fresh XLA
            program per (prior, width) pair. The first token samples only
            when ``do_sample`` (chunked prefill's non-final segments pass
            False), keeping the rng stream identical to whole-prompt
            admission.

            A LATENT family's prior is latents: they are primed as they lie
            and the forward EXPANDS them, with the segment's own, to keys and
            values in every layer (``models/deepseek_v2.py``; PERF.md has the
            timing of this against attending absorbed over the prior).

            A family with CONVOLUTION state starts each row from the tail of
            its prior's LAST page (``tail`` [Lc, P, 2, d], donated; a prior is
            whole pages: a radix hit's, or this prompt's earlier segments',
            whose tails the calls that filled them left there) — zeros for a
            row without a prior — and returns what ``prefill_scatter`` does.

            A family with MAMBA layers starts each row where ``snap["start"]``
            says (``ssm_start``): the SNAPSHOT the radix cache cut its match
            back to, or the state its own slot carries from the prompt's
            earlier segment — a prior's pages hold K and V, no state."""
            from sentio_tpu.runtime.sampling import sample_tokens

            b, width = ids.shape
            pnb = prior_table.shape[1]
            prior_w = pnb * page_size
            cache = ssm_start(new_cache(b, prior_w + width, width // page_size), snap, conv, tail)
            if conv_state and pnb:
                last = jnp.take_along_axis(
                    prior_table, jnp.maximum(n_prior // page_size - 1, 0)[:, None], axis=1)[:, 0]
                cache = dict(cache)
                cache["conv"] = jnp.where((n_prior > 0)[None, :, None, None], tail[:, last], 0)
            if pnb:
                def prime(cache_arr, pages):
                    if pages is None:
                        return None
                    if latent:  # [L, B, PNB, latent_dim, page] → tokens
                        return cache_arr.at[:, :, :prior_w, 0].set(
                            _latent_tokens(pages, (slice(None), prior_table)))
                    if isinstance(pages, dict):
                        dense = dequantize_pages(
                            pages["q"][:, prior_table],
                            pages["s"][:, prior_table], cache_arr.dtype)
                    else:
                        dense = pages[:, prior_table]  # [L, B, PNB, pg, Hk, Hd]
                    lcount, bb, nb_, pg_ = dense.shape[:4]
                    return cache_arr.at[:, :, :prior_w].set(
                        dense.reshape(lcount, bb, nb_ * pg_, *cache_arr.shape[-2:]))

                cache = dict(cache)
                cache["k"] = prime(cache["k"], k_pages)
                cache["v"] = prime(cache["v"], v_pages)

            pad_mask = jnp.arange(width)[None, :] < lens[:, None]
            logits, cache, *moe = forward_fn(
                params, cfg, ids, positions=positions, cache=cache,
                cache_index=n_prior, pad_mask=pad_mask, **({"logits_at": lens - 1} if last_only else {}),
            )
            # each row's new KV sits at its own dynamic offset in the primed
            # cache — slice the [n_prior, n_prior + width) window per row
            def row_window(arr, start):  # [L, S, Hk, Hd] → [L, width, Hk, Hd]
                return jax.lax.dynamic_slice(
                    arr, (0, start, 0, 0),
                    (arr.shape[0], width, arr.shape[2], arr.shape[3]))

            def new_rows(arr):
                return None if arr is None else jax.vmap(
                    row_window, in_axes=(1, 0), out_axes=1)(arr, n_prior)

            k_new, v_new = new_rows(cache["k"]), new_rows(cache["v"])
            k_pages, v_pages = scatter_prefill(k_pages, v_pages, k_new, v_new, scat)
            if do_sample:
                rng, sub = jax.random.split(rng)
                first, first_lp = sample_tokens(last_logits(logits, lens), sub, temps, top_k=top_ks)
            else:
                first = jnp.zeros((b,), jnp.int32)
                first_lp = jnp.zeros((b,), jnp.float32)
            out = first, first_lp, k_pages, v_pages, rng
            return prefill_results(out, moe, moe_acc, cache, scat, tail, conv, snap)

        self._prior_prefill_scatter = prior_prefill_scatter

        if self.draft_params is not None:
            from sentio_tpu.models.llama import llama_forward as _draft_fwd
            from sentio_tpu.runtime.paged_spec import build_spec_tick

            dcfg = self.draft_cfg
            self._spec_tick = build_spec_tick(
                self._family_forward, cfg, _draft_fwd, dcfg,
                eos_id=self.tokenizer.eos_id, ignore_eos=self.ignore_eos,
                page_size=self.page_size,
            )

            @jit_family("paged.draft_prefill", donate_argnums=(2, 3))
            def draft_prefill(params_d, ids, d_k, d_v, rows_idx, lens):
                """Fill the persistent draft cache rows for freshly admitted
                slots (the draft's analogue of prefill_scatter; prefix pages
                are target-only, so the draft always prefills the FULL
                prompt). Pad rows index max_slots and drop."""
                from sentio_tpu.models.llama import init_cache

                b, width = ids.shape
                cache = init_cache(dcfg, b, width)
                positions = jnp.broadcast_to(
                    jnp.arange(width, dtype=jnp.int32)[None, :], (b, width)
                )
                pad_mask = jnp.arange(width)[None, :] < lens[:, None]
                _, cache = _draft_fwd(
                    params_d, dcfg, ids, positions=positions, cache=cache,
                    cache_index=0, pad_mask=pad_mask,
                )
                d_k = d_k.at[:, rows_idx, :width].set(cache["k"], mode="drop")
                d_v = d_v.at[:, rows_idx, :width].set(cache["v"], mode="drop")
                return d_k, d_v

            self._draft_prefill = draft_prefill

    def _ensure_draft_cache(self) -> None:
        import jax.numpy as jnp

        if self._spec_dk is not None:
            return
        dcfg = self.draft_cfg
        window = self.max_pages_per_seq * self.page_size
        shape = (dcfg.n_layers, self.max_slots, window,
                 dcfg.n_kv_heads, dcfg.head_dim)
        self._spec_dk = jnp.zeros(shape, dcfg.jdtype)
        self._spec_dv = jnp.zeros(shape, dcfg.jdtype)

    # --------------------------------------------------------------- public

    def submit(self, prompt: str, max_new_tokens: int = 64, temperature: float = 0.0,
               deadline_ts: Optional[float] = None, top_k: int = 0,
               prior_tokens: Optional[Sequence[int]] = None,
               seed: Optional[int] = None,
               trace_id: Optional[str] = None) -> int:
        """``deadline_ts`` is an absolute ``time.perf_counter()`` deadline:
        the queue drops the request (finish_reason="expired") if it is still
        waiting for a slot when the deadline passes. ``top_k`` (0 = off)
        rides the fused decode dispatch as traced per-row data — any value
        shares the one compiled tick program.

        ``prior_tokens`` is the prior-prefix admission surface (resume-by-
        replay, runtime/replica.py): already-generated token ids appended
        after the (truncation-exact) prompt as context, so decode continues
        from the splice point. The radix cache turns the replay into a
        prefix hit when the pages survive here, and a bounded replay
        prefill otherwise; emitted tokens are post-splice only.
        ``seed`` (None = off) folds into the engine RNG at admission.
        ``trace_id`` names the flight record whose ``prefill`` span this
        request's prefill dispatches book their device time on."""
        if self._san is not None:
            self._san.enter("submit")
        top_k = int(top_k)
        if top_k > 0 and self._spec_tick is not None:
            raise ValueError(
                "top_k sampling is not supported with paged speculation "
                "(the spec tick's accept/correct rule is temperature-only)"
            )
        rid = next(self._next_id)
        self._queue.append(_Request(
            rid, prompt, max_new_tokens, temperature, top_k=max(top_k, 0),
            submit_t=time.perf_counter(), deadline_ts=deadline_ts,
            prior_tokens=(list(prior_tokens) if prior_tokens else None),
            seed=seed, trace_id=trace_id,
        ))
        return rid

    def warm_prefix(self, text: str) -> int:
        """Pre-populate the radix prefix cache with ``text``'s full-page KV
        so even the FIRST matching request admits suffix-only (without
        warming, request one prefills cold and seeds the cache itself).
        Returns the number of tokens now cached (0 = cache disabled or text
        shorter than one page). Idempotent; safe while slots are active —
        the cache is append-only from the engine's single driver thread and
        warming never frees pages a live table references. Warmed nodes are
        unpinned: LRU eviction reclaims them under page-pool pressure like
        any other cached prefix."""
        if self._san is not None:
            self._san.enter("warm_prefix")
        if self._radix is None:
            return 0
        toks = self.tokenizer.encode(text, add_bos=True)
        # leave at least one page of table room for suffix + decode
        n_blocks = min(len(toks) // self.page_size, self.max_pages_per_seq - 1)
        if n_blocks <= 0:
            return 0
        full = n_blocks * self.page_size
        matched, _pages, _node = self._radix.match(toks[:full])
        if matched >= full:
            return full  # already warm
        need = (full - matched) // self.page_size
        if need > self.allocator.free_pages:
            self._radix.evict(need - self.allocator.free_pages)
            matched, _pages, _node = self._radix.match(toks[:full])
            need = (full - matched) // self.page_size
            if need > self.allocator.free_pages:
                return 0  # pool pinned by live slots; requests warm it later
        pages = self.allocator.alloc(need)
        # cold-prefill the whole span, scatter only the uncovered blocks
        # (already-cached blocks scatter to scratch page 0 and are dropped);
        # the sampled token is discarded — this dispatch only fills pages
        width = self._prefill_width(full)
        ids, lens, temps, top_ks, scat, positions = self._assemble_prefill(
            [(toks[:full], 0.0, 0, [0] * (matched // self.page_size) + pages)],
            width,
        )
        written: list = []
        (_first, _first_lp, self.pool.k, self.pool.v, self._rng), _picks, _conv_rows = \
            self._prefill_call(
                self._prefill_scatter,
                self.params, ids, positions, lens, self._rng, temps, scat,
                self.pool.k, self.pool.v, top_ks, rows=[(written, 0, full)],
            )
        _node, donated = self._radix.insert(toks[:full], matched, pages)
        self._attach_snapshots(toks[:full], written)
        leftover = set(pages) - set(donated)
        if leftover:  # span raced into the tree between match and insert
            self.allocator.free(list(leftover))
        return full

    def peek_prefix(self, tok_ids: Sequence[int]) -> int:
        """Read-only routing probe: how many leading tokens of ``tok_ids``
        this engine's radix cache could serve from cached KV, clamped the
        same way admission clamps a real match (at least one suffix token
        must remain to prefill). Takes no refcounts, touches no LRU state,
        and — alone among engine methods — is safe to call from a non-driver
        thread: the result is an affinity HINT for the replica router, so a
        stale read during a concurrent insert/evict merely routes one
        request suboptimally. No ``_san.enter`` for the same reason: the
        single-driver contract guards mutation, and this mutates nothing."""
        if self._radix is None or not tok_ids:
            return 0
        try:
            matched = self._radix.peek_prefix(tok_ids)
        except Exception:  # noqa: BLE001 — torn concurrent read: no hint
            return 0
        max_shared = ((len(tok_ids) - 1) // self.page_size) * self.page_size
        return max(min(matched, max_shared), 0)

    def cancel(self, request_id: int) -> bool:
        """Abandon a request: queued → dropped; decoding → slot retired and
        pages freed (the tokens so far are discarded). Must be called by the
        engine's single driver thread, like every other engine method — so
        BETWEEN steps, where every request that holds pages owns its lane (a
        slot whose lane was handed on is retired by the same ``step()``)."""
        if self._san is not None:
            self._san.enter("cancel")
        for idx, req in enumerate(self._queue):
            if req.request_id == request_id:
                del self._queue[idx]
                if idx == 0:
                    # the skip budget belongs to the departed head; the new
                    # head must not inherit an exhausted one (it would
                    # disable skip-ahead on its first blocked scan)
                    self._head_skips = 0
                return True
        for slot in self.slots:
            if slot.active and slot.request_id == request_id:
                self._retire(slot, "cancelled")
                return True
        return False

    def reset(self) -> None:
        """Rebuild all device/host decode state after a failed tick.

        ``step``'s compiled programs donate the pool buffers — an exception
        mid-dispatch can leave ``pool.k/v`` deleted and slots half-admitted,
        which would poison every later tick. Queued and in-flight requests
        are dropped (their callers were already failed by the layer above);
        weights and compiled programs are kept."""
        if self._san is not None:
            self._san.enter("reset")
        # chaos seam: lets drills force the reset itself to fail (the path
        # that latches a service _broken and quarantines a replica) —
        # previously reachable only implicitly through a re-armed paged.step
        faults.hit("engine.reset")
        import jax

        self.pool = init_pool(
            self.cfg, self.allocator.num_pages, self.page_size, mesh=self.mesh,
            quantized=self.kv_quant == "int8", slots=self.max_slots, pack=self._kv_pack,
            snapshots=self._snapshots,
        )
        self.allocator = PageAllocator(self.allocator.num_pages)
        self.slots = [_Slot(lane=i) for i in range(self.max_slots)]
        self._queue.clear()
        self._head_skips = 0
        self._finished_buffer.clear()
        self._pending_first.clear()
        self._dev_state = None
        self._moe_acc = None
        self._prefill_latent_pending = dict.fromkeys(PREFILL_LATENT_KINDS, 0)
        self._prefill_turns_pending = dict.fromkeys(PREFILL_TURN_KINDS, 0)
        self._conv_state_pending = dict.fromkeys(CONV_STATE_KINDS, 0)
        self._ssm_state_pending = dict.fromkeys(SSM_STATE_KINDS, 0)
        # the failed tick's arrays are not worth waiting for
        get_stamper().drain()
        if self._inflight is not None:
            # dispatched, never harvested: the device ran these row-steps
            # and nothing of them was delivered
            self._count_row_steps(self._inflight, useful=0)
        self._inflight = None
        if self._prefix_cache_enabled:
            from sentio_tpu.runtime.radix import RadixPrefixCache

            self._radix = RadixPrefixCache(self.page_size, self.allocator, self._snapshots)
        self._spec_dk = self._spec_dv = None  # rebuilt lazily (zeros)
        self._page_table[:] = 0
        self._lens[:] = 0
        self._temps[:] = 0.0
        self._top_ks[:] = 0
        self._last_tok[:] = 0
        self._rng = jax.random.PRNGKey(int(np.random.default_rng().integers(2**31)))

    # FamilyFn instances owned by THIS engine (fresh jit wrappers per
    # engine): the pump's per-engine compile attribution and the rebuild
    # path's fence exemption both iterate exactly these attributes
    FAMILY_ATTRS = ("_step_n", "_merge_admitted", "_prefill_scatter",
                    "_prior_prefill_scatter", "_draft_prefill", "_spec_tick")

    def set_fence_exempt(self, exempt: bool) -> None:
        """Mark this engine's own jit families exempt from (or again subject
        to) an armed compile fence. A supervised in-place rebuild constructs
        a FRESH engine whose families are all cold — its warmup compiles are
        expected and must not trip the fence, while a steady-state recompile
        on any sibling replica's engine still does (the exemption is scoped
        to these instances, not global)."""
        for attr in self.FAMILY_ATTRS:
            fn = getattr(self, attr, None)
            if fn is not None and hasattr(fn, "fence_exempt"):
                fn.fence_exempt = bool(exempt)

    def spawn_fresh(self) -> "ContinuousBatchingEngine":
        """A brand-new engine sharing ONLY this engine's immutable state
        (weights, tokenizer, config) — private pool, allocator, radix tree,
        slots, and jit wrappers. The replica supervisor's in-place rebuild
        path: when ``reset()`` itself failed, the old engine's device
        buffers are unrecoverable and the only safe move is a clean
        re-instantiation from the shared weights (the same constructor path
        serve/dependencies.py uses to build replicas at startup)."""
        return ContinuousBatchingEngine(
            model_config=self.cfg,
            params=self.params,
            tokenizer=self.tokenizer,
            max_slots=self.max_slots,
            page_size=self.page_size,
            # baselined cross-thread-race: a config-constant read of an
            # engine-thread-owned object from the rebuild/supervisor roles —
            # spawn_fresh only runs after the wedged engine is QUARANTINED
            # (its pump abandoned), an ownership handoff the static model
            # cannot see but the runtime ThreadGuard enforces
            num_pages=self.allocator.num_pages,
            max_pages_per_seq=self.max_pages_per_seq,
            use_pallas=self._use_pallas_asked,
            steps_per_tick=self.steps_per_tick,
            max_tick_steps=self.max_tick_steps,
            ignore_eos=self.ignore_eos,
            pipeline_depth=self.pipeline_depth,
            mesh=self.mesh,
            forward_fn=self._family_forward,
            kv_quant=self.kv_quant,
            prefill_chunk=self.prefill_chunk,
            draft_params=self.draft_params,
            draft_config=self.draft_cfg,
            spec_k=self.spec_k,
            prefix_cache=self._prefix_cache_enabled, ssm_snapshots=self._snapshots,
        )

    @property
    def has_work(self) -> bool:
        return (
            bool(self._queue)
            or any(s.active for s in self.slots)
            or self._inflight is not None
        )

    def run_all(
        self, prompts: Sequence[str], max_new_tokens: int = 64, temperature: float = 0.0,
        return_choices: bool = False,
    ) -> list[PagedResult]:
        """Submit-and-drain convenience used by tests and bench. With
        ``return_choices`` each result of a routed family carries
        ``choices``: what its own tokens were routed by (``PagedResult``)."""
        was, self.keep_choices = self.keep_choices, bool(return_choices) and self.routed
        try:
            ids = [self.submit(p, max_new_tokens, temperature) for p in prompts]
            done: dict[int, PagedResult] = {}
            while self.has_work:
                for r in self.step():
                    done[r.request_id] = r
        finally:
            self.keep_choices = was
        return [done[i] for i in ids]

    def step(self) -> list[PagedResult]:
        """One engine tick: admit waiting requests (prefill dispatches, no
        fetch), one fused multi-step decode dispatch, ONE host fetch, retire
        finished slots. With ``pipeline_depth`` 2 the dispatch goes out
        BEFORE the previous tick's fetch, overlapping the host round trip
        with device compute: RESULTS then lag one tick, lanes do not — a row
        whose last tokens ride the tick in flight has its lane admitted into
        here (``_admit``, ``_spent``), and the harvest below retires it from
        that tick's record. Only a row that ends EARLY, on an EOS the host
        has not seen yet, or whose successor's pages do not fit the pool
        beside its own, keeps its lane until its harvest. Returns results
        completed this tick."""
        if self._san is not None:
            self._san.enter("step")
        # the timer and the row-step counts reset BEFORE the injection point:
        # whatever a failed step leaves in them belongs to THIS step alone, so the
        # pump's crash-path flush (partial_step_phases) can never re-count
        # the previous tick's already-recorded phases
        acc = self._phase.acc
        self._phase.reset()
        self.last_tick_row_steps = dict.fromkeys(ROW_STEP_KINDS, 0)
        self.last_tick_kv_pages = dict.fromkeys(KV_PAGE_KINDS, 0)
        self.last_tick_moe = dict.fromkeys(MOE_KINDS, 0)
        self.last_tick_prefill_latent = dict.fromkeys(PREFILL_LATENT_KINDS, 0)
        self.last_tick_prefill_turns = dict.fromkeys(PREFILL_TURN_KINDS, 0)
        self.last_tick_lane_admissions = dict.fromkeys(LANE_ADMISSION_KINDS, 0)
        self.last_tick_conv_state = dict.fromkeys(CONV_STATE_KINDS, 0)
        self.last_tick_ssm_state = dict.fromkeys(SSM_STATE_KINDS, 0)
        self.last_tick_sub_steps = 0
        # chaos-drill injection point: a raised fault propagates exactly like
        # a real failed device dispatch (the serving pump resets + requeues)
        faults.hit("paged.step")
        t0 = time.perf_counter()
        self.last_tick_active = 0
        with annotation("tick.admission_build"):
            self._admit()
            if self.prefill_chunk is not None:
                self._advance_prefill()
        t_admit = time.perf_counter()
        # the admission span minus its jit dispatch calls is pure host build
        # work (tokenize, radix match, page alloc, padded array assembly)
        acc["admission_build"] += (t_admit - t0) - acc["prefill_dispatch"]
        with annotation("tick.decode_dispatch"):
            record = self._dispatch_tick() if any(s.active for s in self.slots) else None
        t_dispatch = time.perf_counter()
        # decode dispatch is HOST CALL time of an async dispatch; any
        # blocking first-token fold inside it already went to device_wait
        acc["decode_dispatch"] += (t_dispatch - t_admit) - acc["device_wait"]
        # buffer swap AFTER dispatch: defensive retires made while budgeting
        # must ride THIS step's results (there may not be a next step)
        out, self._finished_buffer = self._finished_buffer, []
        with annotation("tick.device_wait"):
            if self.pipeline_depth <= 1:
                if record is not None:
                    out.extend(self._harvest(record))
            else:
                prev, self._inflight = self._inflight, record
                if prev is not None:
                    out.extend(self._harvest(prev))
        t_harvest = time.perf_counter()
        # the harvest span is dominated by the blocking packed-token fetch;
        # with pipeline_depth=2 this wait belongs to the PREVIOUS tick's
        # dispatch but is charged to the iteration that harvests it — that
        # is where the wall clock went, so per-tick conservation holds
        acc["device_wait"] += t_harvest - t_dispatch
        if self._san is not None:
            # page conservation + radix refcounts, checked on the tick that
            # broke them — not at pool exhaustion three workloads later
            check_engine_invariants(self)
        acc["other"] += time.perf_counter() - t_harvest
        self.last_step_phases = dict(acc)
        return out

    def partial_step_phases(self) -> dict:
        """Live (possibly mid-step) phase accumulations. When ``step()``
        raises, ``last_step_phases`` still holds the PREVIOUS tick's
        decomposition — the pump's crash-containment path reads these
        partials instead, so a failed iteration's wall time is attributed
        rather than holed (the timer reset at step entry guarantees they
        cover only the failed step)."""
        return dict(self._phase.acc)

    # -------------------------------------------------------------- private

    def _free_slot_indices(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if not s.active]

    def _remaining(self, slot: _Slot) -> int:
        """Tokens ``slot`` may still be granted: what ``max_new`` and its
        page capacity leave once everything folded, pending and in flight is
        counted. THE budget arithmetic — ``_dispatch_tick`` grants from it,
        ``_spent`` hands a lane on by it, and ``_fold_and_maybe_retire``
        retires on the same two bounds."""
        capacity = slot.shared_tokens + len(slot.pages) * self.page_size
        # a pending (still-on-device) first token and any sub-steps
        # already granted to an unharvested tick count against the
        # budget exactly as if they had been folded
        base_emit = (
            len(slot.emitted) + slot.inflight_steps
            + (1 if slot.pending_first else 0)
        )
        written = slot.length + slot.inflight_steps
        # spec mode reserves verify-block headroom inside capacity.
        # Admission over-allocates by the same amount, EXCEPT when the
        # request already hits the max_pages_per_seq window — there the
        # headroom comes out of the emission budget, so window-limited
        # requests finish up to spec_k+1 tokens earlier than the plain
        # engine would (documented in runtime/paged_spec.py)
        spec_head = (self.spec_k + 1) if self._spec_tick is not None else 0
        return max(min(slot.max_new - base_emit,
                       capacity - 1 - spec_head - written), 0)

    def _spent(self, slot: _Slot) -> bool:
        """Every token ``slot`` may still emit is granted to the tick in
        flight: its lane can take the next request NOW. That tick's record
        holds the slot and its harvest folds and retires it — always, since
        the bounds that leave nothing to grant are the ones the fold retires
        on; an EOS only ends the row earlier inside the same tick. Safe by
        DEVICE ORDER, which this relies on: the tick in flight took its own
        copy of the page table, the new request's prefill and
        ``merge_admitted`` are enqueued behind it (they overwrite the lane's
        carried token, length, halt flag, logprob and state rows only after
        it read them), and the prefill writes pages the old row never held —
        its own stay allocated until it retires. Never at depth 1 (nothing is
        in flight when ``_admit`` runs) and never on a spec engine (its
        budgets are verify blocks, and the draft cache is per lane)."""
        record = self._inflight
        return (
            record is not None and self._spec_tick is None
            and record["slots"][slot.lane] is slot
            and slot.active and slot.prefill_todo is None
            and (slot.inflight_steps > 0 or slot.pending_first)
            and self._remaining(slot) == 0
        )

    ADMIT_BUCKETS = (1, 2, 4, 8)

    def _prefill_width(self, n_tokens: int) -> int:
        width = bucket_size(
            max(n_tokens, self.page_size), tuple(
                b for b in self.PREFILL_BUCKETS if b % self.page_size == 0
            ) or (self.page_size,),
        )
        return ((width + self.page_size - 1) // self.page_size) * self.page_size

    def _prior_bucket(self, n_blocks: int) -> int:
        """Static prior-table width for ``n_blocks`` prior pages: the next
        power of two (capped at the per-sequence window) so prior-primed
        prefill compiles O(log window) variants. 0 stays 0 (no prior)."""
        if n_blocks <= 0:
            return 0
        return min(1 << (n_blocks - 1).bit_length(), self.max_pages_per_seq)

    def tick_step_sizes(self) -> tuple[int, ...]:
        """Every fused-tick scan length ``_dispatch_tick`` can request: the
        idle-queue big tick plus the 3-rung pressure ladder. Each distinct
        value is one compiled ``step_n`` (or spec-tick) variant — the set
        the compile manifest commits to."""
        sizes = {self.max_tick_steps}
        for shrink in (1, 2, 4):
            sizes.add(max(self.steps_per_tick // shrink, 2))
        return tuple(sorted(sizes))

    def compile_variant_space(self) -> dict[str, list[dict]]:
        """The DECLARED compile-variant space per jit family, derived from
        the same bucketing helpers the admission/decode paths call
        (``_prefill_width`` / ``_prior_bucket`` / ``tick_step_sizes`` /
        ADMIT_BUCKETS). ``sentio audit`` lowers every descriptor and gates
        the result against the committed manifest, so growing any of these
        sets is a deliberate, reviewable act."""
        window = self.max_pages_per_seq * self.page_size
        # reserve = min(max_new + 2, window // 2) >= 3, so admitted prompts
        # never exceed window - 3 tokens
        max_prompt = max(window - 3, 1)
        widths = sorted({self._prefill_width(n)
                         for n in range(1, max_prompt + 1)})
        pnbs = sorted({self._prior_bucket(b)
                       for b in range(1, self.max_pages_per_seq)})
        rows = list(self.ADMIT_BUCKETS)
        space: dict[str, list[dict]] = {
            "paged.step_n": [{"steps": s} for s in self.tick_step_sizes()],
            "paged.merge_admitted": [{"rows": r} for r in rows],
            "paged.prefill_scatter": [
                {"width": w, "rows": r} for w in widths for r in rows
            ],
            # radix-hit admission: suffix width x prior bucket x row bucket,
            # always sampling the first token
            "paged.prior_prefill_scatter": [
                {"width": w, "pnb": p, "rows": r, "do_sample": True}
                for w in widths for p in pnbs for r in rows
            ],
        }
        if self.prefill_chunk is not None:
            # chunked segments dispatch one row at a time; non-final
            # segments skip sampling and the first segment may have no
            # prior at all (pnb 0)
            seg_widths = sorted({self._prefill_width(n)
                                 for n in range(1, self.prefill_chunk + 1)})
            space["paged.prior_prefill_scatter"] += [
                {"width": w, "pnb": p, "rows": 1, "do_sample": False}
                for w in seg_widths for p in [0] + pnbs
            ]
        if self.draft_params is not None:
            # the draft always prefills the FULL prompt, width clamped to
            # its cache window
            full_widths = sorted({min(self._prefill_width(n), window)
                                  for n in range(1, max_prompt + 1)})
            space["paged.draft_prefill"] = [
                {"width": w, "rows": r} for w in full_widths for r in rows
            ]
            space["paged_spec.spec_tick"] = [
                {"steps": s} for s in self.tick_step_sizes()
            ]
        return space

    def _match_radix(self, tok_ids: Sequence[int]):
        """Longest-prefix match against the radix cache, clamped so at
        least one suffix token remains to prefill (the first sampled token
        comes from the last prompt logit). → (shared, pages, node, snapshot,
        paged): the last two a family's with Mamba layers — the match is CUT
        BACK to the deepest boundary whose state the cache kept, ``snapshot``
        its slot and ``paged`` the tokens the pages alone would have served
        (None and ``shared`` for every other family)."""
        if self._radix is None or self._radix.empty:
            return 0, [], None, None, 0
        max_shared = ((len(tok_ids) - 1) // self.page_size) * self.page_size
        if self.ssm_state:
            return self._radix.match_state(tok_ids, max_shared)
        matched, pages, node = self._radix.match(tok_ids)
        if matched > max_shared:
            matched = max_shared
            pages = pages[: matched // self.page_size]
        if matched <= 0:
            return 0, [], None, None, 0
        return matched, pages, node, None, matched

    def _radix_insert(self, slot_idx: int, tok_ids, shared: int) -> None:
        """Move slot ``slot_idx``'s freshly prefilled full-page prompt span
        ``[shared, full)`` into the radix cache. Donated pages change owner
        (retire no longer frees them); the slot re-pins the deepest node so
        eviction can't touch pages its table references. Must run AFTER the
        dispatch that writes those pages — matches by later admissions are
        then ordered behind the write on device."""
        if self._radix is None:
            return
        slot = self.slots[slot_idx]
        full = (len(tok_ids) // self.page_size) * self.page_size
        if full <= shared:
            return
        own = slot.pages[: (full - shared) // self.page_size]
        node, donated = self._radix.insert(list(tok_ids[:full]), shared, own)
        self._attach_snapshots(tok_ids, slot.snaps_written)
        slot.donated.extend(donated)
        if node is not None and node is not slot.prefix_node:
            self._radix.lock(node)
            self._radix.unlock(slot.prefix_node)
            slot.prefix_node = node

    def _attach_snapshots(self, tok_ids, written: list) -> None:
        """Tell the radix cache of the snapshots a prompt's prefill wrote
        (``written``: (boundary, snapshot), emptied): each boundary of
        ``tok_ids`` owns its snapshot from now — a boundary that owns one
        already, or is not in the tree, gives the slot back."""
        for boundary, snap in written:
            self._radix.snap_attach(tok_ids, boundary, snap)
        written.clear()

    def _row_slot(self, who) -> Optional[_Slot]:
        """A prefill row's slot: ``who`` is its index, or — a row no request
        owns (``warm_prefix``) — the list its written snapshots go to."""
        return self.slots[who] if isinstance(who, int) else None

    def _snapshot_rows(self, n_rows: int, rows, prior: bool) -> dict:
        """The ``snap`` arrays of one prefill dispatch of ``n_rows`` rows for
        a family with Mamba layers. ``rows``: (slot index — ``_row_slot`` —,
        first position, tokens) a row. THE POLICY, a row: a snapshot at the boundary its match
        was cut back from, where the dispatch passes it (the pages said a
        prompt is shared up to there: the next one starts there), and one at
        the last whole page the dispatch reaches (a chunked prompt's segment:
        its end; a prompt's last dispatch: where the same conversation comes
        back to). A boundary gets none where the pool has no slot to give
        (every one pinned or being written)."""
        chunk, page = self.family.state.page_tokens(self.cfg), self.page_size
        snap = {"slot": np.full(n_rows, self.max_slots, np.int32),
                "at": np.zeros((n_rows, SNAPS_PER_ROW), np.int32),
                "ids": np.full((n_rows, SNAPS_PER_ROW), self._snapshots, np.int32)}
        if prior:
            snap["start"] = np.full(n_rows, -1, np.int32)
        for r, (who, start, n) in enumerate(rows):
            slot = self._row_slot(who)
            written = slot.snaps_written if slot is not None else who
            boundaries = []
            if slot is not None:
                snap["slot"][r] = who
                if prior and start > slot.shared_tokens:
                    snap["start"][r] = -2
                elif prior and slot.start_snap is not None:
                    snap["start"][r] = slot.start_snap
                if start < slot.cut_from <= start + n:
                    boundaries.append(slot.cut_from)
            last = (start + n) // page * page
            if last > start and last not in boundaries:
                boundaries.append(last)
            for k, boundary in enumerate(boundaries if self._radix is not None else ()):
                taken = self._radix.snap_alloc()
                if taken is None:
                    break
                snap["at"][r, k], snap["ids"][r, k] = (boundary - start) // chunk, taken
                written.append((boundary, taken))
                self._ssm_state_pending["written"] += 1
        return snap

    def _admit(self) -> None:
        if not self._queue:
            return
        # the lanes this admission may take, in order: those that hold no
        # request, then — only where the queue is longer than they are —
        # those whose row is spent (its last tokens ride the tick in flight)
        lanes = [(i, "free") for i in self._free_slot_indices()]
        if len(self._queue) > len(lanes):
            lanes += [(s.lane, "spent") for s in self.slots if self._spent(s)]
        if not lanes:
            return

        batch: list[tuple[int, _Request, list[int], int]] = []
        now = time.perf_counter()
        qi = 0
        while qi < len(self._queue) and lanes:
            req = self._queue[qi]
            if req.deadline_ts is not None and now >= req.deadline_ts:
                # caller's deadline passed while queued: drop BEFORE paying
                # prefill — the result surfaces so the layer above can close
                # out its waiter with a typed deadline error
                self._queue.pop(qi)
                if qi == 0:
                    self._head_skips = 0
                self._finished_buffer.append(PagedResult(
                    request_id=req.request_id, text="", tokens=[],
                    prompt_tokens=0, finish_reason="expired",
                ))
                continue
            if req.tok_ids is None:
                prompt_ids = self.tokenizer.encode(req.prompt, add_bos=True)
                # budget split inside the per-sequence page window:
                # generation gets its requested tokens up to HALF the window
                # (else decode retires on out_of_pages after window - prompt
                # tokens); the prompt always keeps at least the other half,
                # so a huge max_new can never silently truncate most of the
                # context. A prior-prefix admission (resume-by-replay)
                # counts the prior toward the reserve — max_new + len(prior)
                # equals the ORIGINAL request's max_new, so the prompt
                # truncates exactly as it did at first admission and the
                # resumed context is byte-identical up to the splice.
                window = self.max_pages_per_seq * self.page_size
                prior = req.prior_tokens or []
                reserve = min(req.max_new + len(prior) + 2, window // 2)
                req.tok_ids = prompt_ids[: window - reserve] + list(prior)
                if req.seed is not None:
                    # fold the caller's seed into the ENGINE-SHARED RNG
                    # once, at first admission scan. Best-effort seeding:
                    # with concurrent sampled traffic the shared stream's
                    # position depends on tick interleaving, so this pins
                    # draws only for a lone sampled request (the resumed
                    # continuation's correctness does not depend on it —
                    # it conditions on the replayed prefix either way)
                    import jax

                    self._rng = jax.random.fold_in(
                        self._rng, int(req.seed) & 0x7FFFFFFF)
            tok_ids = req.tok_ids
            # radix-cache hit: longest page-aligned prefix of this prompt
            # already in the pool → the table reuses those pages read-only
            # and only the unmatched suffix prefills
            cache_live = self._radix is not None and not self._radix.empty
            shared, match_pages, match_node, match_snap, paged = self._match_radix(tok_ids)
            # speculation headroom: a verify block writes KV for up to
            # spec_k+1 positions past the accepted length before acceptance
            # is known — those writes need real pages behind them
            spec_head = (self.spec_k + 1) if self._spec_tick is not None else 0

            def pages_needed(sh: int) -> int:
                return min(
                    (len(tok_ids) - sh + req.max_new + spec_head
                     + self.page_size - 1) // self.page_size,
                    self.max_pages_per_seq - sh // self.page_size,
                )

            need_total = pages_needed(shared)
            # a spent lane is taken only where the request's pages fit BESIDE
            # the old row's. Where they cannot, the lane waits for its
            # harvest, which frees those, and the request is admitted then
            # like any other: nothing is evicted in vain, nothing jumps the
            # head into a lane that is not free yet, no head skip is counted
            handover = lanes[0][1] == "spent"
            cached = self._radix.pages_held if self._radix is not None else 0
            if handover and need_total > self.allocator.free_pages + cached:
                break
            if need_total > self.allocator.free_pages and self._radix is not None:
                # reclaim LRU unpinned cached prefixes; the match may have
                # walked nodes the eviction just freed, so rematch after
                if self._radix.evict(need_total - self.allocator.free_pages):
                    shared, match_pages, match_node, match_snap, paged = self._match_radix(tok_ids)
                    need_total = pages_needed(shared)
            if need_total > self.allocator.free_pages and handover:
                break  # what the cache holds is pinned by live rows
            if need_total > self.allocator.free_pages:
                # skip-ahead: a too-large request must not idle free slots
                # while smaller requests queue behind it (round-4 weak #3:
                # avg occupancy 2.95/8 with head-of-line FIFO). Starvation
                # bound: after head_skip_bound jumps the head reverts to
                # strict FIFO — nothing admits past it until its pages free.
                if qi == 0 and self._head_skips >= self.head_skip_bound:
                    break
                qi += 1
                continue
            pages = self.allocator.alloc(need_total)
            slot_idx, kind = lanes.pop(0)
            self.lane_admissions_total[kind] += 1
            self.last_tick_lane_admissions[kind] += 1
            self._queue.pop(qi)
            if qi == 0:
                self._head_skips = 0
            else:
                self._head_skips += 1
            # counted per ADMISSION (not per scan attempt — skip-ahead may
            # examine a queued request many times before it admits). Hits/
            # misses count only against a non-empty cache (the very first
            # admission has nothing to hit); token totals always accrue so
            # the hit ratio reflects the cold start honestly.
            if cache_live:
                if shared:
                    self.prefix_hits += 1
                else:
                    self.prefix_misses += 1
            if self._radix is not None:
                self.prefix_hit_tokens_total += shared
                self.prefix_miss_tokens_total += len(tok_ids) - shared
                self._radix.lock(match_node)
            chunked = (
                self.prefill_chunk is not None
                and len(tok_ids) - shared > self.prefill_chunk
            )
            if not chunked:
                batch.append((slot_idx, req, tok_ids, shared))
            # a fresh slot takes the lane. A spent one stays with the record
            # of the tick in flight, which folds and retires it (its pages are
            # its own until then: the new request's were allocated beside them)
            slot = self.slots[slot_idx] = _Slot(
                lane=slot_idx, request_id=req.request_id, pages=pages,
                length=len(tok_ids), prompt_tokens=len(tok_ids), max_new=req.max_new,
                temperature=req.temperature, top_k=req.top_k, active=True,
                shared_tokens=shared, prefix_node=match_node,
                prompt_ids=list(tok_ids) if self._radix is not None else None,
                submit_t=req.submit_t, admit_t=time.perf_counter(), trace_id=req.trace_id,
                prefill_segments=0 if chunked else 1,
                prefill_todo=list(tok_ids[shared:]) if chunked else None,
                start_snap=match_snap,
                choices={name: np.full(
                    (getattr(self.cfg, "n_routed_layers", self.cfg.n_layers),
                     self.max_pages_per_seq * self.page_size, depth), -1, np.int32)
                    for name, depth in self._choice_depths.items()} if self.keep_choices else None,
            )
            if paged > shared:  # pages matched past the state the cache kept: computed again
                slot.cut_from = paged
                self._ssm_state_pending["cut_back_tokens"] += paged - shared
            if match_snap is not None:
                self._radix.snap_pin(match_snap)
            shared_blocks = shared // self.page_size
            row = np.zeros(self.max_pages_per_seq, np.int32)
            if shared_blocks:
                row[:shared_blocks] = match_pages
            row[shared_blocks : shared_blocks + len(pages)] = pages
            self._page_table[slot_idx] = row
            self._lens[slot_idx] = len(tok_ids)
            self._temps[slot_idx] = req.temperature
            self._top_ks[slot_idx] = req.top_k

        if not batch:
            return

        # batched admission: rows group by prefill-width bucket, each group
        # splits into batch-bucket chunks → admitting N same-width requests
        # costs ceil(N / max_batch_bucket) prefill dispatches, not N. The
        # sampled first tokens STAY ON DEVICE (slot.pending_first): the next
        # tick merges them into its token input and its single packed fetch
        # carries them back — admission adds zero host round trips.
        # rows with a prefix hit group by (suffix width, prior-page bucket)
        # — per-row prior lengths ride the dispatch as data, so different
        # match depths share one compiled program; cold rows keep the plain
        # path (identical dispatch to a cache-disabled engine)
        groups: dict[tuple[int, int], list] = {}
        for item in batch:
            shared = item[3]
            width = self._prefill_width(len(item[2]) - shared)
            pnb = self._prior_bucket(shared // self.page_size)
            groups.setdefault((width, pnb), []).append(item)
        max_rows = max(self.ADMIT_BUCKETS)
        for (width, pnb), members in sorted(groups.items()):
            for start in range(0, len(members), max_rows):
                chunk = members[start : start + max_rows]
                if pnb:
                    self._prefill_chunk_prior(width, pnb, chunk)
                else:
                    self._prefill_chunk(width, [m[:3] for m in chunk])
        if self._spec_tick is not None:
            self._draft_prefill_admitted(batch)

    def _draft_prefill_admitted(self, batch: list) -> None:
        """Fill the draft cache for freshly admitted slots — always over the
        FULL prompt (prefix-shared pages are target-side only), grouped by
        full-length width bucket like target admission."""
        self._ensure_draft_cache()
        # the draft cache window is max_pages_per_seq * page_size per row;
        # a bucketed width past it would make the [:width] update overhang
        # the cache axis and fail at trace time (prompts are already
        # truncated below the window at admission, so clamping is lossless)
        window = self.max_pages_per_seq * self.page_size
        groups: dict[int, list] = {}
        for slot_idx, _req, tok_ids, _shared in batch:
            width = min(self._prefill_width(len(tok_ids)), window)
            groups.setdefault(width, []).append((slot_idx, tok_ids))
        max_rows = max(self.ADMIT_BUCKETS)
        for width, members in sorted(groups.items()):
            for start in range(0, len(members), max_rows):
                chunk = members[start : start + max_rows]
                rows = bucket_size(len(chunk), self.ADMIT_BUCKETS)
                ids = np.full((rows, width), self.tokenizer.pad_id, np.int32)
                lens = np.ones(rows, np.int32)
                rows_idx = np.full(rows, self.max_slots, np.int32)  # pad→drop
                for r, (slot_idx, tok_ids) in enumerate(chunk):
                    ids[r, : len(tok_ids)] = tok_ids
                    lens[r] = len(tok_ids)
                    rows_idx[r] = slot_idx
                with self._phase.phase("prefill_dispatch"):
                    self._spec_dk, self._spec_dv = self._draft_prefill(
                        self.draft_params, ids, self._spec_dk, self._spec_dv,
                        rows_idx, lens,
                    )

    def _assemble_prefill(self, rows_data, width: int, pos_offset: int = 0):
        """Build the padded admission arrays ONE way for every prefill
        flavor. rows_data: [(token_ids, temperature, top_k, pages)]. Pad
        rows and unused scatter blocks point at scratch page 0; args stay
        host numpy (a jit call ships them asynchronously, while an explicit
        jnp.asarray is a SYNCHRONOUS upload)."""
        rows = bucket_size(len(rows_data), self.ADMIT_BUCKETS)
        nb = width // self.page_size
        ids = np.full((rows, width), self.tokenizer.pad_id, np.int32)
        lens = np.ones(rows, np.int32)
        temps = np.zeros(rows, np.float32)
        top_ks = np.zeros(rows, np.int32)
        scat = np.zeros((rows, nb), np.int32)
        for r, (tok_ids, temp, top_k, pages) in enumerate(rows_data):
            ids[r, : len(tok_ids)] = tok_ids
            lens[r] = len(tok_ids)
            temps[r] = temp
            top_ks[r] = top_k
            used = (len(tok_ids) + self.page_size - 1) // self.page_size
            scat[r, :used] = pages[:used]
        positions = (
            pos_offset
            + np.broadcast_to(
                np.arange(width, dtype=np.int32)[None, :], (rows, width)
            )
        ).astype(np.int32)
        return ids, lens, temps, top_ks, scat, positions

    def _prefill_chunk(
        self, width: int, chunk: list[tuple[int, _Request, list[int]]]
    ) -> None:
        """One prefill+scatter+sample dispatch for up to max(ADMIT_BUCKETS)
        same-width-bucket rows (rows pad up to a batch bucket)."""
        faults.hit("paged.admit_scatter")
        ids, lens, temps, top_ks, scat, positions = self._assemble_prefill(
            [(tok_ids, req.temperature, req.top_k, self.slots[slot_idx].pages)
             for slot_idx, req, tok_ids in chunk],
            width,
        )
        with self._phase.phase("prefill_dispatch"):
            (first, first_lp, self.pool.k, self.pool.v, self._rng), picks, conv_rows = \
                self._prefill_call(
                    self._prefill_scatter,
                    self.params, ids, positions, lens, self._rng, temps, scat,
                    self.pool.k, self.pool.v, top_ks,
                    slots=[i for i, _r, _t in chunk], rows=[(i, 0, len(t)) for i, _r, t in chunk],
                )
        self._note_prefill_picks(picks, [(i, 0, len(t)) for i, _r, t in chunk])
        self.prefill_tokens_total += sum(len(t) for _i, _r, t in chunk)
        slot_idxs = [slot_idx for slot_idx, _req, _ids in chunk]
        for slot_idx in slot_idxs:
            self.slots[slot_idx].pending_first = True
        self._pending_first.append((first, first_lp, slot_idxs, conv_rows))
        # the dispatch above writes these rows' full prompt KV — their
        # full-page spans now seed the radix cache for later requests
        for slot_idx, _req, tok_ids in chunk:
            self._radix_insert(slot_idx, tok_ids, 0)

    def _prefill_chunk_prior(self, width: int, pnb: int, chunk: list) -> None:
        """Suffix-only admission for radix-cache hits: ids/positions/scatter
        cover ONLY the unmatched tokens; the compiled fn primes each row's
        cache from its matched prefix pages (per-row table padded to the
        ``pnb`` page bucket with scratch page 0, per-row true prior lengths
        riding as data)."""
        faults.hit("paged.admit_scatter")
        rows_data = []
        n_prior = []
        for slot_idx, req, tok_ids, shared in chunk:
            rows_data.append(
                (tok_ids[shared:], req.temperature, req.top_k,
                 self.slots[slot_idx].pages)
            )
            n_prior.append(shared)
        rows = bucket_size(len(chunk), self.ADMIT_BUCKETS)
        n_prior = np.asarray(n_prior + [0] * (rows - len(chunk)), np.int32)
        prior_tables = np.zeros((rows, pnb), np.int32)
        for r, (slot_idx, _req, _t, shared) in enumerate(chunk):
            sb = shared // self.page_size
            prior_tables[r, :sb] = self._page_table[slot_idx, :sb]
        ids, lens, temps, top_ks, scat, positions = self._assemble_prefill(
            rows_data, width, pos_offset=n_prior[:, None],
        )
        with self._phase.phase("prefill_dispatch"):
            (first, first_lp, self.pool.k, self.pool.v, self._rng), picks, conv_rows = \
                self._prefill_call(
                    self._prior_prefill_scatter,
                    self.params, ids, positions, lens, self._rng, temps, scat,
                    self.pool.k, self.pool.v, prior_tables, n_prior, top_ks,
                    do_sample=True, slots=[i for i, _r, _t, _sh in chunk],
                    rows=[(i, sh, len(t) - sh) for i, _r, t, sh in chunk],
                )
        self._note_prefill_picks(picks, [(i, sh, len(t) - sh) for i, _r, t, sh in chunk])
        self.prefill_tokens_total += sum(len(t) - s for _i, _r, t, s in chunk)
        slot_idxs = [slot_idx for slot_idx, _req, _ids, _sh in chunk]
        for slot_idx in slot_idxs:
            self.slots[slot_idx].pending_first = True
        self._pending_first.append((first, first_lp, slot_idxs, conv_rows))
        for slot_idx, _req, tok_ids, shared in chunk:
            self._radix_insert(slot_idx, tok_ids, shared)

    def _prefill_call(self, fn, *args, slots: Sequence[int] = (), rows=(), **static):
        """One prefill dispatch → (its five results, its picks or None, its
        rows' convolution state or None). A routed family's program also
        takes the pairs counted on the device since the last tick and returns
        them with its own added; its picks stay on the device unless a caller
        asked for them. A family with convolution state hands the page tails
        in (donated) and takes them back, with each row's state after its
        tokens — which stays on the device until ``merge_admitted`` puts it
        in the row's slot. A family with MAMBA layers hands in the snapshot
        pool and the slots' state (both donated) with what each of ``rows``
        (slot, first position, tokens) starts from and which boundaries it
        snapshots (``_snapshot_rows``), and takes both back: the program puts
        a row's state in its slot itself. The sampled first tokens (small,
        donated to nothing) carry its completion stamp, booked on the
        ``prefill`` span of each request in ``slots``."""
        picks = conv_rows = None
        with dispatching("prefill", self.tick_step,
                         [(self.slots[i].trace_id, "prefill") for i in slots]) as stamp:
            if self.routed:
                static["moe_acc"] = self._take_moe_acc()
            if self.slot_state:
                static["tail"] = self.pool.tail
            if self.ssm_state:
                static["conv"] = self.pool.conv
                static["snap"] = self._snapshot_rows(args[1].shape[0], rows, fn is self._prior_prefill_scatter)
            out = list(fn(*args, **static))
            if self.slot_state:
                self.pool.tail = out.pop()
                conv_rows = out.pop()
            if self.ssm_state:
                self.pool.conv, conv_rows = conv_rows, None
                for who, _start, _n in rows:  # its start is read: the snapshot may go
                    slot = self._row_slot(who)
                    if slot is not None and slot.start_snap is not None:
                        self._radix.snap_pin(slot.start_snap, -1)
                        slot.start_snap = None
            if self.routed:
                moe = out.pop()
                self._moe_acc = moe.pop("counts")
                picks = moe if self.keep_choices else None
            stamp.out = out[0]
        return out, picks, conv_rows

    def _take_moe_acc(self):
        """The pairs the prefill programs routed since the last tick, still
        on the device ([4] int32; zeros where none ran), handed on once. The
        zeros lie on the device too, made once: a host array here and a
        device array there are two entries of a program's cache — counted as
        two compilations, the second of which may fall inside a measured
        window when every admission is chunked (PERF.md section 6, PR 38)."""
        acc, self._moe_acc = self._moe_acc, None
        if acc is None:
            if self._moe_zero is None:
                import jax.numpy as jnp

                self._moe_zero = jnp.zeros(4, jnp.int32)
            acc = self._moe_zero
        return acc

    def _note_prefill_picks(self, picks, rows) -> None:
        """``rows``: (slot, first position, tokens) of each row of a prefill
        dispatch whose ``picks {kind: [L, rows, width, k]}`` are still on the
        device. A latent family's dispatch is counted here too: the tokens it
        computed, and the prior tokens (its rows' first positions) it expanded."""
        if self.latent:
            self._prefill_latent_pending["new"] += sum(n for _i, _start, n in rows)
            self._prefill_latent_pending["expanded"] += sum(start for _i, start, _n in rows)
        if self.conv_state:
            # what each row started from (a prior is whole pages, so a start
            # IS a page's end), and the pages this dispatch filled
            for slot_idx, start, n in rows:
                kind = "zero" if not start else \
                    "tail" if start == self.slots[slot_idx].shared_tokens else "carried"
                self._conv_state_pending[kind] += 1
                self._conv_state_pending["pages"] += n // self.page_size
        if self.ssm_state:
            for slot_idx, start, _n in rows:
                kind = "zero" if not start else \
                    "snapshot" if start == self.slots[slot_idx].shared_tokens else "carried"
                self._ssm_state_pending[kind] += 1
        if picks is None:
            return
        for r, (slot_idx, start, n) in enumerate(rows):
            if self.slots[slot_idx].choices is not None:
                self.slots[slot_idx].choice_parts.append((picks, r, start, n))

    def _advance_prefill(self) -> None:
        """Dispatch ONE chunked-prefill segment per tick (bounding how much
        prefill latency any single tick adds to live decodes). The slot with
        the OLDEST submit time goes first — index order would let a steady
        stream of long prompts landing in lower slots starve a higher one
        indefinitely while it pins its pages."""
        waiting = [
            (slot.submit_t, i) for i, slot in enumerate(self.slots)
            if slot.active and slot.prefill_todo is not None
        ]
        if waiting:
            self._prefill_turns_pending["taken"] += 1
            self._prefill_turns_pending["waited"] += len(waiting) - 1
        for _, i in sorted(waiting):
            slot = self.slots[i]
            chunk = self.prefill_chunk
            seg = slot.prefill_todo[:chunk]
            is_last = len(slot.prefill_todo) <= chunk
            prior = slot.shared_tokens + slot.prefill_done
            width = self._prefill_width(len(seg))
            # the segment's own pages start right after the prior blocks in
            # this slot's table (prior is page-aligned: shared and every
            # non-final segment are page multiples)
            pb = prior // self.page_size
            nb = (len(seg) + self.page_size - 1) // self.page_size
            seg_pages = self._page_table[i, pb : pb + nb].tolist()
            n_prior = np.asarray([prior], np.int32)
            ids, lens, temps, top_ks, scat, positions = self._assemble_prefill(
                [(seg, slot.temperature, slot.top_k, seg_pages)], width,
                pos_offset=n_prior[:, None],
            )
            # prior-table width buckets to a power-of-two page count (padded
            # with scratch page 0) so an 8K prompt compiles O(log window)
            # segment variants, not one per (prior, width) pair
            pnb = self._prior_bucket(pb)
            prior_table = np.zeros((1, pnb), np.int32)
            prior_table[0, :pb] = self._page_table[i, :pb]
            with self._phase.phase("prefill_dispatch"):
                (first, first_lp, self.pool.k, self.pool.v, self._rng), picks, conv_rows = \
                    self._prefill_call(
                        self._prior_prefill_scatter,
                        self.params, ids, positions, lens, self._rng, temps,
                        scat, self.pool.k, self.pool.v, prior_table,
                        n_prior, top_ks, do_sample=is_last, slots=[i], rows=[(i, prior, len(seg))],
                    )
            self._note_prefill_picks(picks, [(i, prior, len(seg))])
            self.prefill_tokens_total += len(seg)
            slot.prefill_segments += 1
            if is_last:
                slot.prefill_todo = None
                slot.pending_first = True
                self._pending_first.append((first, first_lp, [i], conv_rows))
                # the final segment completes the prompt's KV — its
                # full-page span can now enter the radix cache
                self._radix_insert(i, slot.prompt_ids, slot.shared_tokens)
            else:
                slot.prefill_todo = slot.prefill_todo[chunk:]
                slot.prefill_done += len(seg)
            return

    def _dispatch_tick(self) -> Optional[dict]:
        """Compute per-row budgets, merge freshly admitted rows into the
        device-carried decode state, and dispatch ONE fused multi-step scan.
        No host fetch happens here — the returned record is harvested later
        (immediately at pipeline depth 1, one step() later at depth 2) into
        the slots it names, whoever holds their lanes by then."""
        pending, self._pending_first = self._pending_first, []
        remaining = np.zeros(self.max_slots, np.int32)
        for i, slot in enumerate(self.slots):
            if not slot.active:
                continue
            if slot.prefill_todo is not None:
                continue  # mid-chunked-prefill: no decode budget, no retire
            remaining[i] = self._remaining(slot)
            if (remaining[i] == 0 and not slot.pending_first
                    and slot.inflight_steps == 0):
                # defensive: a zero-budget row with nothing in flight can't
                # progress
                self._finished_buffer.append(self._retire(slot, "length"))
        # adaptive tick size, scaled by backlog depth: waiting requests
        # (engine queue + the serving layer's inbox, via pressure_hint) cap
        # the tick so admission waits fewer decode sub-steps the deeper the
        # backlog grows — freed slots refill at tick boundaries, so shorter
        # ticks under pressure directly cut queueing delay (round-4 weak #3:
        # 9.6x p95/p50 tail with the old two-size switch). An idle queue
        # runs the big tick so long generations cost few fetches. Each
        # distinct step count is its own compiled variant; the pressured
        # ladder is capped at 3 sizes (+1 idle) to bound compilations —
        # ``tick_step_sizes()`` declares exactly this set for the audit.
        waiting = len(self._queue)
        if self.pressure_hint is not None:
            waiting += int(self.pressure_hint())
        if waiting == 0:
            steps = self.max_tick_steps
        else:
            shrink = 1 << min(waiting // max(self.max_slots, 1), 2)  # 1, 2, 4
            steps = max(self.steps_per_tick // shrink, 2)
        if self.force_tick_steps in self.tick_step_sizes():
            steps = self.force_tick_steps  # warmup rung pin, never off-ladder
        budgets = np.minimum(remaining, steps).astype(np.int32)
        pending_slots = [i for _f, _lp, idxs, _conv in pending for i in idxs
                         if self.slots[i].active]
        # rows sharing THIS fused dispatch — the honest occupancy number
        # (post-tick slot counts miss requests that retire inside the tick)
        self.last_tick_active = int(
            ((budgets > 0) | [s.active and s.pending_first for s in self.slots]).sum()
        )
        if not budgets.any():
            if not pending_slots:
                return None
            # nothing can decode but deferred first tokens need folding
            # (e.g. a max_new_tokens=1 burst): fetch them directly instead
            # of dispatching a fully-masked scan that would stream the
            # weights steps-many times just to echo the inputs back
            for first_dev, first_lp_dev, slot_idxs, _conv in pending:  # rows that retire here: no state to keep
                # a direct fetch of not-yet-ready device arrays BLOCKS —
                # this is device wait, not dispatch cost
                with self._phase.phase("device_wait"):
                    vals = np.asarray(first_dev)
                    lps = np.asarray(first_lp_dev)
                for r, i in enumerate(slot_idxs):
                    slot = self.slots[i]
                    if not slot.active:
                        continue
                    slot.pending_first = False
                    self._note_ttft(slot)
                    slot.lp = (float(lps[r]), float(lps[r]), 1)
                    result = self._fold_and_maybe_retire(slot, int(vals[r]))
                    if result is not None:
                        self._finished_buffer.append(result)
            return None

        # decode state rides ON DEVICE, threaded from the previous tick's
        # outputs (host mirrors seed the first tick); admission's device-
        # resident first tokens / prompt lengths scatter in via the jitted
        # merge. Jit dispatches are async; eager index-update ops and
        # explicit jnp.asarray uploads each block.
        if self._dev_state is None:
            # the first dispatch since construction or a reset: every row that
            # decodes in it was admitted in this step, and the merge below
            # seeds its token, length and logprob accumulators
            tok_in = self._last_tok.copy()
            lens_in = self._lens.copy()
            halted_in = np.zeros(self.max_slots, bool)
            lp_sum_in = np.zeros(self.max_slots, np.float32)
            lp_min_in = np.zeros(self.max_slots, np.float32)
            lp_cnt_in = np.zeros(self.max_slots, np.int32)
        else:
            (tok_in, lens_in, halted_in,
             lp_sum_in, lp_min_in, lp_cnt_in) = self._dev_state
        for first_dev, first_lp_dev, slot_idxs, conv_rows in pending:
            idxs = np.full(first_dev.shape[0], self.max_slots, np.int32)
            idxs[: len(slot_idxs)] = slot_idxs
            new_lens = np.zeros(first_dev.shape[0], np.int32)
            new_lens[: len(slot_idxs)] = [
                self.slots[i].length for i in slot_idxs
            ]
            with dispatching("admit", self.tick_step) as stamp:
                # a family with convolution state: the rows' state into their slots
                state = {"conv": self.pool.conv, "conv_rows": conv_rows} if self.conv_state else {}
                (tok_in, lens_in, halted_in,
                 lp_sum_in, lp_min_in, lp_cnt_in, *conv) = self._merge_admitted(
                    tok_in, lens_in, halted_in, lp_sum_in, lp_min_in, lp_cnt_in,
                    first_dev, first_lp_dev, new_lens, idxs, **state
                )
                if conv:
                    self.pool.conv = conv[0]
                stamp.out = tok_in

        # the tick's packed tokens carry its completion stamp: the harvest
        # fetches them, and no program takes them as an input
        with dispatching("decode", self.tick_step) as stamp:
            if self._spec_tick is not None:
                self._ensure_draft_cache()
                packed, tok_out, lens_out, halted_out, self.pool.k, self.pool.v, \
                    self._spec_dk, self._spec_dv, self._rng = self._spec_tick(
                        self.params, self.draft_params, tok_in, lens_in,
                        halted_in, self._page_table.copy(), self.pool.k,
                        self.pool.v, self._spec_dk, self._spec_dv, self._rng,
                        self._temps.copy(), budgets,
                        # + k + 1 slack: dynamic_update_slice CLAMPS a start
                        # index whose k+1-wide update would overhang, silently
                        # corrupting the tail rounds' token offsets otherwise
                        k=self.spec_k, out_w=int(steps) + self.spec_k + 1,
                    )
                spec = True
                kv_pages = None  # the spec tick does not run the decode kernel
                # the spec tick has its own accept/correct rule and samples no
                # per-token logprobs; the accumulators thread through UNCHANGED
                # (stale first-token seeds) and the host mirrors stay zeroed, so
                # spec results report logprob_count == 0 — the confidence gate
                # reads that as "no signal" and never skips verify on spec mode
                lp_state = None
                lp_sum_out, lp_min_out, lp_cnt_out = lp_sum_in, lp_min_in, lp_cnt_in
            else:
                # a routed family: the prefill programs' pairs ride this tick's fetch
                moe_acc = {"moe_acc": self._take_moe_acc()} if self.routed else {}
                if self.conv_state:
                    moe_acc.update(conv=self.pool.conv, tail=self.pool.tail)
                elif self.ssm_state:  # decode writes no snapshot: the pool stays away
                    moe_acc.update(conv=self.pool.conv)
                (packed, lp_state, tok_out, lens_out, halted_out,
                 lp_sum_out, lp_min_out, lp_cnt_out,
                 self.pool.k, self.pool.v, self._rng, *picks) = self._step_n(
                    self.params,
                    tok_in,
                    lens_in,
                    halted_in,
                    self._page_table.copy(),
                    self.pool.k,
                    self.pool.v,
                    self._rng,
                    self._temps.copy(),
                    self._top_ks.copy(),
                    budgets,
                    lp_sum_in,
                    lp_min_in,
                    lp_cnt_in,
                    steps=steps, **moe_acc,
                )
                if self.slot_state:
                    *picks, self.pool.conv, tail = picks
                    if tail is not None:
                        self.pool.tail = tail
                self.total_sub_steps += steps
                spec = False
                kv_pages = self._kv_pages(budgets, int(steps))
            stamp.out = packed
        self._dev_state = (tok_out, lens_out, halted_out,
                           lp_sum_out, lp_min_out, lp_cnt_out)
        for i, slot in enumerate(self.slots):
            if slot.active:
                slot.inflight_steps += int(budgets[i])
        return {"packed": packed, "budgets": budgets, "spec": spec,
                "stamp": stamp.seq,
                "lp_state": lp_state,
                # a routed family's picks of every sub-step, on the device
                "picks": picks[0] if not spec and picks and self.keep_choices else None,
                # for the row-step count at harvest: the scan's length and
                # the rows that held a request when it was dispatched
                "steps": int(steps),
                "live": sum(s.active for s in self.slots),
                "kv_pages": kv_pages,
                "pending_slots": set(pending_slots),
                # the slots this tick was dispatched with, lane by lane: the
                # harvest folds its tokens into THEM. A lane handed on since
                # (``_spent``), or retired by a cancel and refilled, holds
                # another request's slot by then, which must not be replayed
                # the old one's tokens
                "slots": list(self.slots)}

    def _harvest(self, record: dict) -> list[PagedResult]:
        """Fetch a dispatched tick's packed tokens ([1 + steps, B] — the ONE
        host fetch per tick) and replay the device scan host-side: each
        executed sub-step is exactly one old-style tick — write counted,
        token folded, retirement checked. Execution-mask reconstruction: a
        row runs until its budget (host-known) or the step after its first
        EOS (visible in packed) — identical to the device's halting rule.
        Everything is folded into the RECORD's slots; the lane-indexed host
        mirrors are written only for a slot that still owns its lane (one
        whose lane was handed on retires here without touching it)."""
        budgets = record["budgets"]
        packed = np.asarray(record["packed"])
        harvested(record["stamp"])  # a harvest long after its tick was done: a stall of the pump's
        spec = record.get("spec", False)
        if self.routed and not spec:  # the expert layers' counts: the last four rows
            record["moe"] = dict(zip(MOE_KINDS, (int(n) for n in packed[-4:, 0])))
            packed = packed[:-4]
        # {kind: [steps, L, B, k]}, fetched only where a caller asked for picks
        picks = None if record.get("picks") is None else {
            name: np.asarray(value) for name, value in record["picks"].items()}
        # the tick's final logprob accumulators ([3, B]: sum / min / count),
        # one fetch riding the same dispatch as the packed tokens; refreshed
        # into the host mirrors so a retire inside this harvest reports the
        # request's full-trajectory confidence signal
        lp_state = record.get("lp_state")
        lp_rows = np.asarray(lp_state) if lp_state is not None else None
        finished: list[PagedResult] = []
        useful = 0
        for i, slot in enumerate(record["slots"]):
            if not slot.active:
                continue  # retired since dispatch (cancelled): stale tokens
            owns = self.slots[i] is slot  # else: its lane was handed on
            consumed = int(budgets[i])
            if consumed or i in record["pending_slots"]:
                slot.inflight_steps = max(slot.inflight_steps - consumed, 0)
            else:
                continue
            if lp_rows is not None:
                slot.lp = (float(lp_rows[0, i]), float(lp_rows[1, i]), int(lp_rows[2, i]))
            if slot.pending_first and i in record["pending_slots"]:
                slot.pending_first = False
                self._note_ttft(slot)
                echo = int(packed[i, 0] if spec else packed[0, i])
                if owns:
                    self._last_tok[i] = echo
                result = self._fold_and_maybe_retire(slot, echo)
                if result is not None:
                    finished.append(result)
                    continue
            if spec:
                # spec packed row: [echo, emitted_n, verifies, tokens...] —
                # the device already applied budgets and EOS truncation;
                # fold exactly what it emitted. total_sub_steps counts
                # emitted tokens (the spec analogue of decode sub-steps)
                n = int(packed[i, 1])
                toks = packed[i, 3 : 3 + n]
                self.total_sub_steps += n
                self.spec_emitted_total += n
                self.spec_verifies_total += int(packed[i, 2])
            else:
                n = consumed
                toks = packed[1 : 1 + n, i]
            for s in range(n):
                if picks is not None and slot.choices is not None:
                    # sub-step s fed the token at position ``length``
                    for name, value in picks.items():
                        slot.choices[name][:, slot.length] = value[s, :, i]
                slot.length += 1
                if self.conv_state and slot.length % self.page_size == 0:
                    self._conv_state_pending["pages"] += 1  # this sub-step's token filled a page
                if owns:
                    self._lens[i] = slot.length
                    self._last_tok[i] = int(toks[s])
                useful += 1
                result = self._fold_and_maybe_retire(slot, int(toks[s]))
                if result is not None:
                    finished.append(result)
                    break
        self._count_row_steps(record, useful)
        return finished

    def _count_row_steps(self, record: dict, useful: int) -> None:
        """Book one dispatched tick's ``max_slots x steps`` row-steps:
        ``useful`` of them folded a token into an answer, the rest of the
        rows that held a request were halted, the other rows empty."""
        steps, live = record["steps"], record["live"]
        counts = {"useful": useful, "halted": steps * live - useful,
                  "empty": steps * (self.max_slots - live)}
        for kind, n in counts.items():
            self.row_steps_total[kind] += n
            self.last_tick_row_steps[kind] += n
        self.last_tick_sub_steps += steps
        for kind, n in (record.get("kv_pages") or {}).items():
            self.kv_pages_total[kind] += n
            self.last_tick_kv_pages[kind] += n
        for kind, n in (record.get("moe") or {}).items():
            self.moe_total[kind] += n
            self.last_tick_moe[kind] += n
        for kind, n in self._prefill_latent_pending.items():
            self.prefill_latent_total[kind] += n
            self.last_tick_prefill_latent[kind] += n
            self._prefill_latent_pending[kind] = 0
        for kind, n in self._prefill_turns_pending.items():
            self.prefill_turns_total[kind] += n
            self.last_tick_prefill_turns[kind] += n
            self._prefill_turns_pending[kind] = 0
        for kind, n in self._conv_state_pending.items():
            self.conv_state_total[kind] += n
            self.last_tick_conv_state[kind] += n
            self._conv_state_pending[kind] = 0
        if self.ssm_state and self._radix is not None:  # the slots the radix cache took from their boundaries
            self._ssm_state_pending["evicted"] = self._radix.take_snapshots_evicted()
        if self.ssm_state:  # the tick's one-token state updates: the kernel walks the advancing rows alone
            blocks = len(self.cfg.ssm_layers)
            all_rows = steps * self.max_slots * blocks
            done = useful * blocks if self._ssm_impl is not None else all_rows
            self._ssm_state_pending["row_updates"] += done
            self._ssm_state_pending["row_skips"] += all_rows - done
        for kind, n in self._ssm_state_pending.items():
            self.ssm_state_total[kind] += n
            self.last_tick_ssm_state[kind] += n
            self._ssm_state_pending[kind] = 0

    def _kv_pages(self, budgets, steps: int) -> dict:
        """K/V page blocks of the ``steps`` sub-steps being dispatched, by
        the decode kernel's own rule (``kernels.paged_attention.
        blocks_walked``): a row advancing in sub-step ``s`` (``s`` under its
        budget) is walked at its length then — the host mirror plus what an
        unharvested tick already granted plus ``s`` — every other row for its
        one block. An EOS inside the tick is not known here; the row is
        counted as advancing to its budget. ``behind_window``: the blocks such
        a row holds in layers whose window starts past them. A few integers a
        slot, with no device fetch."""
        from sentio_tpu.kernels.paged_attention import blocks_walked

        sub = np.arange(steps)[None, :]
        at = np.asarray([s.length + s.inflight_steps if s.active else 0
                         for s in self.slots])[:, None] + sub
        # the mean over the layers: a windowed layer's walk starts at the
        # window's first block (one term where all layers are of one kind)
        # (the layers that HAVE pages: a family's attention layers)
        layers = getattr(self.cfg, "attn_layers", range(self.cfg.n_layers))
        windows = Counter(self.cfg.window(i) for i in layers)
        whole = blocks_walked(at, self.page_size, self.max_pages_per_seq)
        walked = {w: whole if w is None else blocks_walked(at, self.page_size, self.max_pages_per_seq, w)
                  for w in windows}
        advancing = sub < np.asarray(budgets)[:, None]
        held = np.where(advancing, sum(n * walked[w] for w, n in windows.items()) / len(layers), 1)
        # and the blocks an advancing row HOLDS in the layers whose window no
        # longer reaches them (one page table serves every layer): what an
        # allocator by layer kind would give back; 0 where no window bites
        behind = np.where(advancing, sum(n * (whole - walked[w]) for w, n in windows.items()) / len(layers), 0)
        return {"held": int(round(float(held.sum()))),
                "tabled": steps * self.max_slots * self.max_pages_per_seq,
                "behind_window": int(round(float(behind.sum())))}

    def _fold_and_maybe_retire(self, slot: _Slot, tok: int) -> Optional[PagedResult]:
        """Fold ``tok`` (sampled, not yet forwarded) into ``slot``; retire
        on EOS / token budget / page capacity. The ONE place the retirement
        conditions live — admission-time and decode-replay paths must never
        diverge, and the decode budgets (``_remaining``) mirror these bounds."""
        self.decode_tokens_total += 1
        hit_eos = tok == self.tokenizer.eos_id and not self.ignore_eos
        if not hit_eos:
            slot.emitted.append(tok)
        hit_len = len(slot.emitted) >= slot.max_new
        capacity = slot.shared_tokens + len(slot.pages) * self.page_size
        out_of_pages = slot.length + 1 >= capacity
        if hit_eos or hit_len or out_of_pages:
            return self._retire(slot, "stop" if hit_eos else "length")
        return None

    def _note_ttft(self, slot: _Slot) -> None:
        """Called exactly where pending_first flips False — the moment the
        first sampled token is host-visible (deferred-fetch admission means
        prefill alone does NOT make it visible)."""
        if slot.submit_t > 0.0:
            self.ttft_samples.append(time.perf_counter() - slot.submit_t)
            self.ttft_count += 1

    def _choices_of(self, slot: _Slot) -> Optional[dict]:
        """What a retiring request's tokens were routed by: its prefill
        dispatches' picks fetched now, its decode picks already written;
        positions the radix cache served stay -1."""
        if slot.choices is None:
            return None
        fed = max(slot.prompt_tokens + len(slot.emitted) - 1, 0)  # the last sampled token never is
        for picks, row, start, n in slot.choice_parts:
            for name, buf in slot.choices.items():
                buf[:, start:start + n] = np.asarray(picks[name][:, row, :n])
        return {name: buf[:, :fed].copy() for name, buf in slot.choices.items()}

    def _retire(self, slot: _Slot, reason: str) -> PagedResult:
        """Free a slot's pages (minus any donated to the radix cache), drop
        its prefix pins, and — where it still owns its lane — zero the
        lane's device-mirror row (a lane handed on is its new request's)."""
        result = PagedResult(
            request_id=slot.request_id,
            text=self.tokenizer.decode(slot.emitted),
            tokens=list(slot.emitted),
            prompt_tokens=slot.prompt_tokens,
            finish_reason=reason,
            prefill_tokens=slot.prompt_tokens - slot.shared_tokens,
            prefix_hit_tokens=slot.shared_tokens,
            logprob_sum=slot.lp[0],
            logprob_min=slot.lp[1],
            logprob_count=slot.lp[2],
            admit_t=slot.admit_t,
            prefill_segments=slot.prefill_segments,
            choices=self._choices_of(slot),
        )
        slot.choices, slot.choice_parts = None, []
        if slot.donated:
            donated = set(slot.donated)
            self.allocator.free([p for p in slot.pages if p not in donated])
        else:
            self.allocator.free(slot.pages)
        if self._radix is not None:
            self._radix.unlock(slot.prefix_node)
            if slot.start_snap is not None:  # retired before its prefill read it
                self._radix.snap_pin(slot.start_snap, -1)
            for _boundary, snap in slot.snaps_written:  # never told of: cancelled mid-prefill
                self._radix.snap_free(snap)
        # a slot is never admitted into again: it has only to read as holding
        # nothing to whoever still names it (its lane until the next
        # admission, a record in flight)
        slot.active = False
        slot.pages, slot.donated, slot.prefix_node, slot.prompt_ids = [], [], None, None
        slot.start_snap, slot.snaps_written = None, []
        i = slot.lane
        if self.slots[i] is slot:
            self._page_table[i] = 0
            self._lens[i] = 0
            self._temps[i] = 0.0
            self._top_ks[i] = 0
            self._last_tok[i] = 0
        return result

    # ---------------------------------------------------------------- stats

    def stats(self) -> dict:
        active = sum(s.active for s in self.slots)
        out = {
            "active_slots": active,
            "max_slots": self.max_slots,
            "queued": len(self._queue),
            "free_pages": self.allocator.free_pages,
            "total_pages": self.allocator.num_pages,
            "page_size": self.page_size,
            "kv_quant": self.kv_quant,
            # which decode-attention path the constructor SELECTED (the
            # Pallas page-table walk on TPU, the XLA gather elsewhere)
            "paged_attention": "pallas" if self._attn_impl is not None else "xla",
            # and which attention its prefill programs run: the flash kernel
            # that knows a prior, or the family's XLA form
            "prefill_attention": "pallas" if self._prefill_attn is not None else "xla",
            # and how a decode step writes its K and V rows into the pool: the
            # kernel that leaves the pool in HBM, or the XLA scatter
            "page_write": "pallas" if self._write_impl is not None else "xla",
            # and, of a family with Mamba layers, how it updates a slot's state
            # (``kernels/ssm_update.py`` or the XLA form; null for the others)
            "ssm_update": ("pallas" if self._ssm_impl is not None else "xla") if self.ssm_state else None,
            # a routed family: the tile ``[rows, tk, tn]`` of each of a layer's
            # three grouped matmuls in the decode program and the grid steps an
            # expert costs (the chip's kernel; ``ragged_dot`` elsewhere takes none)
            "expert_tiles": self._expert_tiles,
            "pool_hbm_bytes": self.pool.hbm_bytes,
            "kv_bytes_per_token": self.pool.token_bytes,
            "head_skips": self._head_skips,
            "ttft_count": self.ttft_count,
            "prefill_tokens": self.prefill_tokens_total,
            "decode_tokens": self.decode_tokens_total,
            # admissions by the lane they took: one that held no request, or
            # one handed on while its row's last tick was in flight
            **{f"lane_admissions_{kind}": n for kind, n in self.lane_admissions_total.items()},
        }
        if self.routed:
            out.update({f"moe_{kind}": n for kind, n in self.moe_total.items()})
        if self.conv_state:
            # of ``pool_hbm_bytes``, the state per slot and per page
            out["conv_state_bytes"] = self.pool.conv_state_bytes
            out.update({f"conv_state_{kind}": n for kind, n in self.conv_state_total.items()})
        if self.ssm_state:
            # of ``pool_hbm_bytes``: the slots' state and the snapshot pool
            # together, the pool alone, its slots and those handed out
            out["ssm_state_bytes"] = self.pool.conv_state_bytes
            out["ssm_snapshot_bytes"] = self.pool.snapshot_bytes
            out["ssm_snapshots"] = self._snapshots
            out["ssm_snapshots_held"] = self._radix.snapshots_held if self._radix is not None else 0
            out.update({f"ssm_state_{kind}": n for kind, n in self.ssm_state_total.items()})
        if self.latent:
            # what ONE token leaves in the pool a layer (bf16)
            out["pool_token_layer_bytes"] = self.cfg.latent_dim * np.dtype(self.pool.k.dtype).itemsize
            out.update({f"prefill_latent_{kind}": n for kind, n in self.prefill_latent_total.items()})
        if self._radix is not None:
            hit, miss = self.prefix_hit_tokens_total, self.prefix_miss_tokens_total
            out["prefix_hits"] = self.prefix_hits
            out["prefix_misses"] = self.prefix_misses
            out["prefix_hit_tokens"] = hit
            out["prefix_miss_tokens"] = miss
            if hit + miss:
                out["prefix_hit_token_ratio"] = round(hit / (hit + miss), 4)
            out["prefix_cache_pages"] = self._radix.pages_held
            out["prefix_cache_nodes"] = self._radix.node_count
        if self.ttft_samples:
            s = sorted(self.ttft_samples)
            out["ttft_p50_ms"] = round(s[len(s) // 2] * 1e3, 2)
            out["ttft_p95_ms"] = round(s[int(len(s) * 0.95)] * 1e3, 2)
        if self.spec_verifies_total:
            out["spec_tokens_per_verify"] = round(
                self.spec_emitted_total / self.spec_verifies_total, 2
            )
            out["spec_verifies"] = self.spec_verifies_total
            out["spec_emitted"] = self.spec_emitted_total
        return out
