"""The main path's kernels, compiled for the real chip without the chip.

Interpret-mode parity (tests/test_kernels.py, tests/test_paged_attn_quant.py)
cannot see what the TPU's compiler refuses: the int8 paged kernel passed
every interpret test and was refused by Mosaic for its float16 scale pages.
The v5e compiler is installed here and compiles for a chip that is described
and not attached (``get_topology_desc``), so each kernel of the serving path
is compiled at Llama-3-8B / encoder widths — about two seconds a case, no
chip time. A compile that passes is not a chip run; it only says the chip's
compiler accepts the program.
"""

import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from sentio_tpu.kernels.flash_attention import flash_attention
from sentio_tpu.kernels.page_write import make_page_write_impl, page_write, page_write_path
from sentio_tpu.kernels.prefill_attention import make_prefill_attn_fn, prefill_attention
from sentio_tpu.kernels.selective_scan import selective_scan_kernel
from sentio_tpu.kernels.ssm_update import make_ssm_update_impl, selective_update, ssm_update, ssm_update_path
from sentio_tpu.kernels.paged_attention import (
    make_paged_attn_impl,
    paged_attention,
    paged_attention_quant,
)
from sentio_tpu.models.llama import LlamaConfig, init_cache, init_llama, llama_forward, serving_layout
from sentio_tpu.parallel.mesh import MESH_AXES
from sentio_tpu.parallel.sharding import LLAMA_TP_RULES, make_param_shardings
from sentio_tpu.runtime.paged import _latent_tokens, paged_decode_forward, scatter_prefill

H, HKV, D = 32, 8, 128  # LlamaConfig.llama3_8b: 32 query / 8 KV heads of 128
LAYERS = 2  # of the pool: the kernel takes it whole and a layer's index


@pytest.fixture(scope="module")
def v5e():
    """Four described v5e chips (a 2x2 host). Skipped only where the
    topology cannot be described (no TPU compiler in the image)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"v5e topology cannot be described here: {exc}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _paged_args(place, quant: bool, page: int, slots: int = 8, nb: int = 32,
                hkv: int = HKV, hkv_spec=None, scale_spec=None, heads: int = H):
    """Abstract arguments of one decode-attention call at serve geometry."""
    num_pages = 1 + slots * nb
    q = place((slots, heads, D), jnp.bfloat16)
    layer = place((), jnp.int32)
    table, lens = place((slots, nb), jnp.int32), place((slots,), jnp.int32)
    if not quant:
        pages = place((LAYERS, num_pages, page, hkv, D), jnp.bfloat16, hkv_spec)
        return q, pages, pages, layer, table, lens
    pages = place((LAYERS, num_pages, page, hkv, D), jnp.int8, hkv_spec)
    scales = place((LAYERS, num_pages, hkv, page), jnp.bfloat16, scale_spec)
    return q, pages, scales, pages, scales, layer, table, lens


def _on_one_chip(topo):
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype, _spec=None: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)


@contextlib.contextmanager
def _the_chips_grouped_matmul():
    """``models/moe.py`` asks the backend which grouped matmul to take and
    sees the CPU here: the test hands it the chip's, for the length of its
    compiles."""
    from sentio_tpu.models import moe

    was, moe.grouped_matmul = moe.grouped_matmul, moe.expert_matmul
    try:
        yield moe
    finally:
        moe.grouped_matmul = was


def _paged_case(quant: bool, page: int, window=None, **geometry):
    def build(topo):
        fn = paged_attention_quant if quant else paged_attention
        if window is not None:  # static: the walk starts at the window's first block
            fn = functools.partial(fn, window=window)
        return fn, _paged_args(_on_one_chip(topo), quant, page, **geometry)

    return build


def _flash_case(shape: tuple, causal: bool):
    def build(topo):
        place = _on_one_chip(topo)
        q = place(shape, jnp.bfloat16)
        return (lambda q, k, v, n: flash_attention(q, k, v, n, causal=causal),
                (q, q, q, place(shape[:1], jnp.int32)))

    return build


PRIOR_KEYS = 40 * 128 + 512   # the long mix's widest program: a 40-page prior and the segment


def _prefill_attn_case(rows: int, heads: int, hkv: int, window=None, rope: int = 0):
    """The prefill's flash kernel (kernels/prefill_attention.py) at a cell's
    shape: a 512-token segment of ``rows`` rows over a 40-page prior bucket;
    ``rope`` > 0 adds the latent family's second score term (ONE rotated key
    a position) and its own softmax scale."""

    def build(topo):
        place = _on_one_chip(topo)
        q, kv = place((rows, 512, heads, D), jnp.bfloat16), place((rows, PRIOR_KEYS, hkv, D), jnp.bfloat16)
        args = [q, kv, kv, place((rows,), jnp.int32)]
        if rope:
            args += [place((rows, 512, heads, rope), jnp.bfloat16), place((rows, PRIOR_KEYS, rope), jnp.bfloat16)]
        return (functools.partial(prefill_attention, window=window, sm_scale=0.1147 if rope else None),
                tuple(args))

    return build


def _page_write_case(rows: int, slots: int = 16, nb: int = 10, layers: int = LAYERS):
    """The decode step's page write (kernels/page_write.py) alone: K and V of
    ``slots`` rows into pools whose positions hold ``rows`` rows of 128 lanes."""

    def build(topo):
        place = _on_one_chip(topo)
        pool = place((layers, 1 + slots * nb, 128, rows, D), jnp.bfloat16)
        val, ids = place((slots, rows, D), jnp.bfloat16), place((slots,), jnp.int32)
        return (lambda k, v, layer, ids, offsets, a, b: page_write((k, v), layer, ids, offsets, (a, b)),
                (pool, pool, place((), jnp.int32), ids, ids, val, val))

    return build


def _ssm_update_case():
    """The decode step's Mamba state update (kernels/ssm_update.py) alone, at
    the nemotron cell's state: six blocks, 16 slots, 64 heads of 64 x 128 in
    8 groups."""

    def build(topo):
        place = _on_one_chip(topo)
        f32 = jnp.float32
        return ssm_update, (place((6, 16, 64, 64, 128), f32), place((), jnp.int32), place((16,), bool),
                            place((16, 64), f32), place((16, 64, 64), f32),
                            place((16, 8, 128), f32), place((16, 8, 128), f32))

    return build


def _selective_update_case():
    """The decode step's Mamba-1 state update (kernels/ssm_update.py::
    selective_update) alone, at the jamba cell's state: 26 layers, 8 slots, a
    row ``[16, 5120]`` float32."""

    def build(topo):
        place = _on_one_chip(topo)
        f32 = jnp.float32
        return selective_update, (place((26, 8, 16, 5120), f32), place((), jnp.int32), place((8,), bool),
                                  place((8, 5120), f32), place((8, 5120), f32), place((8, 16), f32),
                                  place((8, 16), f32), place((16, 5120), f32))

    return build


def _selective_scan_case():
    """The prefill's selective scan (kernels/selective_scan.py) alone: one row
    of 512 tokens over a ``[16, 5120]`` float32 state, the state written out
    every 16 tokens."""

    def build(topo):
        place = _on_one_chip(topo)
        row = place((1, 512, 5120), jnp.float32)
        cols = place((1, 512, 16), jnp.float32)
        return (functools.partial(selective_scan_kernel, snap=16),
                (row, row, place((16, 5120), jnp.float32), cols, cols, place((1, 16, 5120), jnp.float32)))

    return build


def _tp4_case(quant: bool, hkv: int = HKV):
    """The decode kernel inside shard_map over a tp=4 mesh of the four
    described chips: pool and query heads sharded the way init_pool and the
    wq column rule place them."""

    def build(topo):
        mesh = Mesh(np.array(topo.devices).reshape(1, 1, 1, 1, 1, 4), MESH_AXES)

        def place(shape, dtype, spec=None):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec or P()))

        args = _paged_args(place, quant, 128, hkv=hkv,
                           hkv_spec=P(None, None, None, "tp", None),
                           scale_spec=P(None, None, "tp", None))
        q, *pool, layer, table, lens = args
        q = place((q.shape[0], 1, H, D), q.dtype, P(None, None, "tp", None))
        impl = make_paged_attn_impl(interpret=False, mesh=mesh)
        if quant:
            kq, ks, vq, vs = pool
            return (lambda q, kq, ks, vq, vs, ly, t, n: impl(
                q, {"q": kq, "s": ks}, {"q": vq, "s": vs}, ly, t, n, H // hkv),
                (q, kq, ks, vq, vs, layer, table, lens))
        return (lambda q, k, v, ly, t, n: impl(q, k, v, ly, t, n, H // hkv),
                (q, *pool, layer, table, lens))

    return build


CASES = {
    "paged-bf16-page128": _paged_case(quant=False, page=128),
    "paged-int8-page128": _paged_case(quant=True, page=128),
    "paged-bf16-page16": _paged_case(quant=False, page=16),
    "paged-int8-page16": _paged_case(quant=True, page=16),
    # decoder prefill: one 2048-token row at 32 heads of 128
    "flash-causal-d128": _flash_case((1, 2048, H, D), causal=True),
    # EncoderConfig.base: 16 heads of 64 — the d_pad branch of the kernel
    "flash-bidirectional-d64": _flash_case((16, 512, 16, 64), causal=False),
    "paged-bf16-tp4-mesh": _tp4_case(quant=False),
    "paged-int8-tp4-mesh": _tp4_case(quant=True),
    # the benchmark's cells as they are served (benchmark/configs/*.json):
    # 16 slots of 18 pages at 8 kv heads, 32 slots of 10 pages at 4
    "paged-bf16-cell-mistral": _paged_case(quant=False, page=128, slots=16, nb=18, hkv=8),
    "paged-bf16-cell-yi": _paged_case(quant=False, page=128, slots=32, nb=10, hkv=4),
    # the command-a cell: 128 query heads over 8 kv heads (16 a kv head), its
    # sliding layers' window of 4,096; and a window SHORTER than a table (the
    # walk then starts past block 0), bf16 and int8
    "paged-bf16-cell-commanda-window": _paged_case(
        quant=False, page=128, slots=32, nb=10, hkv=8, heads=128, window=4096),
    "paged-bf16-window-inside-table": _paged_case(
        quant=False, page=128, slots=8, nb=40, hkv=8, heads=128, window=4096),
    "paged-int8-window-inside-table": _paged_case(quant=True, page=128, window=1024),
    # 4 kv heads over tp=4: one a device
    "paged-bf16-tp4-mesh-1kv": _tp4_case(quant=False, hkv=4),
    "paged-int8-tp4-mesh-1kv": _tp4_case(quant=True, hkv=4),
    # the prefill's flash kernel at the cells' shapes: mistral's 4 query heads
    # a kv head and yi's 8, a segment alone and four rows of an admission; the
    # latent family's 128 heads with keys 128 + 64 wide over values of 128;
    # command-a's 16 query heads a kv head inside its window
    "prefill-attn-mistral-rows1": _prefill_attn_case(1, H, 8),
    "prefill-attn-mistral-rows4": _prefill_attn_case(4, H, 8),
    "prefill-attn-yi-rows1": _prefill_attn_case(1, H, 4),
    "prefill-attn-yi-rows4": _prefill_attn_case(4, H, 4),
    "prefill-attn-dsv2-second-term": _prefill_attn_case(1, 128, 128, rope=64),
    "prefill-attn-commanda-window": _prefill_attn_case(1, 128, 8, window=4096),
    # the page write at the two cells that take it — lfm2's lane-packed rows
    # (8 kv heads of 64, two to a row) and nemotron's two kv heads — and at 8
    # kv heads, which the reference check's two-layer dense engines write
    "page-write-cell-lfm2": _page_write_case(rows=4),
    "page-write-cell-nemotron": _page_write_case(rows=2),
    "page-write-8kv": _page_write_case(rows=8, slots=8, nb=4),
    # the Mamba state update at the one cell that takes it
    "ssm-update-cell-nemotron": _ssm_update_case(),
    # multi-query attention, 20 query heads on ONE kv head of 128 (the jamba cell): a page of 128 x 1
    # vectors is eight 16-row tiles, a group that is no multiple of 8 sublanes — the decode walk over
    # 8 slots x 40 pages, and the prefill's flash kernel for a 512-token segment behind a 40-page prior
    "paged-bf16-20q-1kv": _paged_case(False, 128, hkv=1, heads=20, nb=40),
    "prefill-attn-jamba-rows1": _prefill_attn_case(1, 20, 1),
    # the selective scan of one layer over a 512-token row at the jamba cell's widths
    "selective-scan-cell-jamba": _selective_scan_case(),
    # the Mamba-1 state update at the one cell that takes it
    "selective-update-cell-jamba": _selective_update_case(),
}
# the geometries whose pages the chip's DMA cannot bring (kernels/
# paged_attention.py ``untiled``): XLA does not store such a pool in the
# order of its shape, and the grid-of-cells kernel (until PR 29) compiled
# there only because XLA handed it a copy of the pool, every layer-call.
# The walk refuses them; the engine serves them through the XLA gather path
REFUSED = {"paged-int8-page16": "scale page", "paged-int8-tp4-mesh-1kv": "kv head"}


def _copied_pools(hlo_text: str, args) -> list:
    """Instructions of the compiled text that MAKE an array as large as one
    device's share of the smallest pool among ``args`` (anything with more
    than three dims): a copy, a pad, a transpose or a fusion. A bitcast — the
    view the walk takes of a pool — makes nothing."""
    pools = [a for a in args if len(a.shape) > 3 and a.shape[1] > 8]
    share = min(int(np.prod(a.shape)) // len(a.sharding.device_set) for a in pools)
    found = []
    for line in hlo_text.splitlines():
        inst = re.match(
            r"\s+(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* (copy|pad|transpose|fusion|copy-start)\(",
            line)
        if inst and np.prod([int(n) for n in inst.group(1).split(",")]) >= share:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    fn, args = CASES[case](v5e)
    if case in REFUSED:
        with pytest.raises(ValueError, match=REFUSED[case]):
            jax.jit(fn).lower(*args)
        return
    compiled = jax.jit(fn).lower(*args).compile()  # raises what the chip would
    text = compiled.as_text()
    assert "tpu_custom_call" in text, (
        f"{case}: the compiled program holds no Pallas kernel")
    if case.startswith("paged"):
        assert _copied_pools(text, args) == [], f"{case}: a pool is copied on the way"


def _pool_shaped(hlo_text: str, shapes: tuple) -> list:
    """(name, shape, what made it) of every instruction of the compiled text
    whose result is an array of one of ``shapes``. A fusion is named for its
    body's root: ``fusion:scatter`` is the update in place."""
    roots, body = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            body = head.group(1)
        root = re.match(r"\s+ROOT %?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if root and body:
            roots[body] = root.group(1)
    found = []
    for line in hlo_text.splitlines():
        inst = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(", line)
        if not inst or inst.group(2) not in shapes:
            continue
        name, shape, op = inst.groups()
        if op == "fusion":
            op = "fusion:" + roots.get(re.search(r"calls=%?([\w.\-]+)", line).group(1), "?")
        found.append((name, shape, op))
    return found


def test_decode_step_reads_the_pool_where_it_lies(decoder_programs):
    """Two layers of ``paged_decode_forward`` in a scan over a donated pool,
    as ``step_n`` runs them: beside the kernel, the only thing that may make
    an array the size of the pool is the scatter that updates it in place,
    and nothing makes one the size of a layer of it. The Pallas call cannot
    fuse its operands, so a ``pages[layer]`` handed to it is a copy of every
    page of the layer, per layer, per sub-step (35 % of the device's time
    until PR 26)."""
    # 16 slots of 18 pages, as the benchmark's mistral cell serves: 151 MB a
    # pool. (One of 34 MB the compiler prefetches whole into fast memory.)
    w = WIDTHS["mistral"]
    pool_shape = (LAYERS, 1 + w["slots"] * w["nb"], 128, w["n_kv_heads"], D)
    text = decoder_programs("mistral")[1]["step"]
    # too large for the compiler to place (151 MB here, 1.2 GB at the cell's 16 layers): the
    # write stays the scatter the parse below finds, and the walk the one Pallas call a layer
    assert page_write_path(jax.ShapeDtypeStruct(pool_shape, jnp.bfloat16)) == "xla"

    assert text.count('custom_call_target="tpu_custom_call"') == LAYERS

    def hlo_shape(dims):
        return "bf16[" + ",".join(map(str, dims)) + "]"

    made = _pool_shaped(text, (hlo_shape(pool_shape), hlo_shape(pool_shape[1:])))
    in_place = {"parameter", "get-tuple-element", "scatter", "fusion:scatter"}
    assert [m for m in made if m[2] not in in_place] == []
    # K and V, each layer: the parse found the updates it is there to allow
    assert sum(m[2] == "fusion:scatter" for m in made) == 2 * LAYERS


# ---------------------------------------------------------------- weights
#
# A parameter lies row-major; the three attention projections, each feeding a
# head reshape and RoPE, are wanted column-major by the v5e compiler, so every
# call of a program that takes the canonical tree begins by transposing them
# (``copy`` in the device trace: 0.7-0.8 GB read and written a call at the
# benchmark's widths, until PR 31). The tree the engine serves stores them
# [out, in] (``serving_layout``): that order, read where it lies.

# the benchmark's two configurations (benchmark/configs/*.json), 2 layers, and
# a prefill segment each cell runs behind 512 prior tokens: mistral's 512, yi's
# 256 (the rest of its 0.7k-token prompts; 512 rows of 4096 would have the
# shape of yi's own wk and the parse below could not tell them apart)
WIDTHS = {
    "mistral": dict(n_kv_heads=8, mlp_dim=14336, vocab_size=32768, slots=16, nb=18, segment=512),
    "yi": dict(n_kv_heads=4, mlp_dim=11008, vocab_size=64000, slots=32, nb=10, segment=256),
}
WEIGHT_ELEMENTS = 2 ** 21  # the smallest projection (yi's wk) holds exactly this


def _weight_copies(hlo_text: str, params) -> list:
    """Instructions of the compiled text that MAKE an array of a weight's
    shape (one device's share of it, either way round) of 2**21 elements or
    more: a copy, a transpose, a pad, or a fusion that neither holds a matmul
    (what it makes is an activation) nor is a bitcast alone (a view)."""
    shapes = set()
    for leaf in jax.tree_util.tree_leaves(params):
        dims = leaf.sharding.shard_shape(leaf.shape)
        if len(dims) == 2 and dims[0] * dims[1] >= WEIGHT_ELEMENTS:
            shapes |= {dims, dims[::-1]}
    makes_nothing, body = set(), None  # bodies that hold a matmul, or only a view
    for line in hlo_text.splitlines():
        head = re.match(r"%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            body = head.group(1)
        elif body and re.search(r" (dot|convolution)\(|ROOT \S+ = \S+ bitcast\(", line):
            makes_nothing.add(body)
    found = []
    for line in hlo_text.splitlines():
        inst = re.match(
            r"\s+(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* (copy|pad|transpose|fusion)\(", line)
        if not inst or tuple(int(n) for n in inst.group(1).split(",")) not in shapes:
            continue
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if inst.group(2) != "fusion" or called.group(1) not in makes_nothing:
            found.append(line.strip()[:160])
    return found


def _decoder_programs(topo, width: str, served: bool, tp: int = 1, compiled: bool = True):
    """→ (params, {program: its compiled text}) for two layers at one of the
    benchmark's widths, weights in bf16 as a checkpoint holds them: the decode
    step as ``step_n`` runs it (a scan over the donated pool, the engine's
    Pallas kernel) and one prefill segment behind 512 prior tokens.
    ``compiled=False``: the lowered text, nothing compiled."""
    w = dict(WIDTHS[width])
    slots, nb, segment, page = w.pop("slots"), w.pop("nb"), w.pop("segment"), 128
    cfg = LlamaConfig(n_layers=LAYERS, **w)
    mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, 1, 1, 1, 1, tp), MESH_AXES)

    def place(shape, dtype, spec=None):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec or P()))

    def tree():
        canonical = init_llama(jax.random.PRNGKey(0), cfg)
        return serving_layout(canonical) if served else canonical

    params = jax.eval_shape(tree)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.ndim == 2 else a.dtype, sharding=s),
        params, make_param_shardings(params, mesh, LLAMA_TP_RULES))
    heads = P(None, None, None, "tp", None)
    pool = place((cfg.n_layers, 1 + slots * nb, page, cfg.n_kv_heads, cfg.head_dim),
                 jnp.bfloat16, heads)
    impl = make_paged_attn_impl(interpret=False, mesh=mesh if tp > 1 else None)

    def step(params, tok, lens, table, k_pages, v_pages):
        def body(carry, _):
            tok, lens, k_pages, v_pages = carry
            logits, k_pages, v_pages = paged_decode_forward(
                params, cfg, tok, lens, table, k_pages, v_pages,
                attn_impl=impl, write_mask=lens < nb * page - 1)
            return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1,
                    k_pages, v_pages), None

        return jax.lax.scan(body, (tok, lens, k_pages, v_pages), None, length=2)[0]

    # as the engine binds it: the flash kernel on one chip, the XLA form under a mesh
    attn_fn = make_prefill_attn_fn(interpret=False) if tp == 1 else None

    def prefill(params, ids, positions, cache, n_prior):
        return llama_forward(params, cfg, ids, positions=positions, cache=cache,
                             cache_index=n_prior, attn_fn=attn_fn)

    cache = place((cfg.n_layers, 1, 512 + segment, cfg.n_kv_heads, cfg.head_dim),
                  jnp.bfloat16, heads)
    lowered = {
        "step": jax.jit(step, donate_argnums=(4, 5)).lower(
            params, place((slots,), jnp.int32), place((slots,), jnp.int32),
            place((slots, nb), jnp.int32), pool, pool),
        "prefill": jax.jit(prefill, donate_argnums=(3,)).lower(
            params, place((1, segment), jnp.int32), place((1, segment), jnp.int32),
            {"k": cache, "v": cache}, place((1,), jnp.int32)),
    }
    return params, {name: (low.compile() if compiled else low).as_text() for name, low in lowered.items()}


@pytest.fixture(scope="module")
def decoder_programs(v5e):
    made = {}

    def get(width, served=True, tp=1):
        key = (width, served, tp)
        if key not in made:
            made[key] = _decoder_programs(v5e, width, served, tp)
        return made[key]

    return get


@pytest.mark.parametrize("tp", [1, 4])
@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_serving_programs_read_their_weights_where_they_lie(
        decoder_programs, width, program, tp):
    """The tree the engine serves, at both cells' widths, on one chip and
    split by ``LLAMA_TP_RULES`` over four: nothing in the compiled decode step
    or prefill segment makes an array with a weight's shape."""
    params, texts = decoder_programs(width, tp=tp)
    assert any("wq_t" in jax.tree_util.keystr(path) for path, _ in
               jax.tree_util.tree_flatten_with_path(params)[0])
    assert _weight_copies(texts[program], params) == []


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_canonical_tree_is_copied_every_call(decoder_programs, program):
    """The control: with ``wq``, ``wk``, ``wv`` apart the same parse finds the
    transposing copies, two layers of three, so the test above cannot pass by
    looking in the wrong place."""
    params, texts = decoder_programs("mistral", served=False)
    copies = _weight_copies(texts[program], params)
    assert len(copies) == 6 and all(" copy(" in c for c in copies), copies


def _scatter_makes(topo, width: str, scatter) -> list:
    """What makes a pool-sized array (its shape, or the kernel's view of it)
    in ``scatter`` compiled over donated pools at one of the cells'
    geometries: two 512-token rows of fresh K and V into their pages."""
    w = WIDTHS[width]
    place = _on_one_chip(topo)
    shape = (LAYERS, 1 + w["slots"] * w["nb"], 128, w["n_kv_heads"], D)
    pool = place(shape, jnp.bfloat16)
    cache = place((LAYERS, 2, 512, w["n_kv_heads"], D), jnp.bfloat16)
    text = jax.jit(scatter, donate_argnums=(0, 1)).lower(
        pool, pool, cache, cache, place((2, 4), jnp.int32)).compile().as_text()
    view = (*shape[:2], shape[2] * shape[3], shape[4])
    return [op for _, _, op in _pool_shaped(text, tuple(
        "bf16[" + ",".join(map(str, dims)) + "]" for dims in (shape, view)))]


IN_PLACE = {"parameter", "bitcast", "scatter", "fusion:scatter"}


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_prefill_scatter_writes_the_pool_where_it_lies(v5e, width):
    """What every prefill dispatch ends with. Until PR 31 yi's (4 kv heads)
    copied both pools twice a call: 4 x 673 MB, 8 ms of a 26 ms call."""
    made = _scatter_makes(v5e, width, scatter_prefill)
    assert set(made) <= IN_PLACE, made
    assert made.count("fusion:scatter") == 2  # K and V: the parse sees the update


def test_scatter_of_page_windows_copies_the_pool_at_4_kv_heads(v5e):
    """The control, and the pitfall: the same scatter written over
    ``[page, Hkv, D]`` windows makes the compiler turn the whole pool
    head-major and back, K and V."""

    def windows(k_pages, v_pages, k_cache, v_cache, table):
        def one(pages, cache):
            lcount, b, s, hkv, hd = cache.shape
            return pages.at[:, table].set(cache.reshape(lcount, b, s // 128, 128, hkv, hd))

        return one(k_pages, k_cache), one(v_pages, v_cache)

    assert _scatter_makes(v5e, "yi", windows).count("copy") == 4
    assert set(_scatter_makes(v5e, "mistral", windows)) <= IN_PLACE  # 8 kv heads: none


# ------------------------------------------------- a family of another shape
#
# ``cohere2_moe`` (models/cohere2_moe.py) at the widths of the benchmark's
# ``command-a-plus-ep8-l4``: attention FOUR times the hidden width (128 query
# heads of 128 at hidden 4096), a sliding and a full layer, 16 held experts of
# 4096 x 4096 behind the megablox grouped matmul, a head that reads the
# embedding. The copy-free controls above, extended to this geometry.


@pytest.fixture(scope="module")
def commanda_programs(v5e):
    from sentio_tpu.models.cohere2_moe import (
        FULL, SLIDING, Cohere2MoeConfig, cohere2_forward, init_cohere2_moe)

    cfg = Cohere2MoeConfig(n_layers=LAYERS, layer_kinds=f"{SLIDING},{FULL}")
    place = _on_one_chip(v5e)
    params = jax.eval_shape(lambda: serving_layout(init_cohere2_moe(jax.random.PRNGKey(0), cfg)))
    params = jax.tree_util.tree_map(
        lambda a: place(a.shape, jnp.bfloat16 if a.ndim >= 2 else a.dtype), params)
    slots, nb, page, segment = 32, 10, 128, 512
    pool = place((LAYERS, 1 + slots * nb, page, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    impl = make_paged_attn_impl(interpret=False)

    def step(params, tok, lens, table, k_pages, v_pages):
        def body(carry, _):
            tok, lens, k_pages, v_pages = carry
            logits, k_pages, v_pages, routed = paged_decode_forward(
                params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=impl,
                write_mask=lens < nb * page - 1, return_routed=True)
            return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1,
                    k_pages, v_pages), routed["experts"]

        return jax.lax.scan(body, (tok, lens, k_pages, v_pages), None, length=2)

    def prefill(params, ids, positions, cache, n_prior):
        return cohere2_forward(params, cfg, ids, positions=positions, cache=cache,
                               cache_index=n_prior, attn_fn=make_prefill_attn_fn(interpret=False))

    cache = place((LAYERS, 1, 512 + segment, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)

    def lowered():
        # (a new function each time: a second trace of the same one would come from jit's cache)
        return {
            "step": jax.jit(lambda *a: step(*a), donate_argnums=(4, 5)).lower(
                params, place((slots,), jnp.int32), place((slots,), jnp.int32),
                place((slots, nb), jnp.int32), pool, pool),
            "prefill": jax.jit(lambda *a: prefill(*a), donate_argnums=(3,)).lower(
                params, place((1, segment), jnp.int32), place((1, segment), jnp.int32),
                {"k": cache, "v": cache}, place((1,), jnp.int32)),
        }

    with _the_chips_grouped_matmul() as moe:
        ours = lowered()
        texts = {name: low.compile().as_text() for name, low in ours.items()}
        texts.update({f"{name}.lowered": low.as_text() for name, low in ours.items()})
        # the same two programs under the ONE tile every family had until PR 43
        was, moe.expert_tile = moe.expert_tile, lambda k, n, *_: (min(4096, k), min(512, n))
        try:
            texts.update({f"{name}.parent": low.as_text() for name, low in lowered().items()})
        finally:
            moe.expert_tile = was
    return cfg, params, texts


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_commanda_programs_read_their_weights_where_they_lie(commanda_programs, program):
    """The v5e compiler takes both programs (Mosaic: 128 query heads in the
    decode walk's VMEM, the grouped matmul's tiles), and nothing in them makes
    an array with the shape of a projection ([16384, 4096]: attention wider
    than the model), of the embedding the head reads, or of a stack of
    experts."""
    cfg, params, texts = commanda_programs
    assert params["layers_0"]["attn"]["wq_t"]["kernel"].shape == (cfg.n_heads * cfg.head_dim, cfg.dim)
    assert "lm_head" not in params
    assert _weight_copies(texts[program], params) == []
    stack = f"bf16[{cfg.experts_held},{cfg.dim},{cfg.mlp_dim}]"
    assert [m for m in _pool_shaped(texts[program], (stack,))
            if m[2] not in ("parameter", "get-tuple-element", "bitcast")] == []


def test_commanda_decode_step_holds_its_kernels(commanda_programs):
    """A layer of the decode step: one walk of the pages (the sliding layer's
    starting at its window's first block) and three grouped expert matmuls,
    each a Pallas call; the pool updated in place."""
    cfg, _, texts = commanda_programs
    # (168 MB a pool at these two layers, 337 at the cell's four: the page write stays the scatter)
    assert page_write_path(jax.ShapeDtypeStruct((LAYERS, 321, 128, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)) == "xla"
    assert texts["step"].count('custom_call_target="tpu_custom_call"') == LAYERS * 4
    assert len(re.findall(r"%gmm[.\d]* = ", texts["step"])) == LAYERS * 3


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_commanda_programs_are_the_parents(commanda_programs, program):
    """At 4096 x 4096 experts the tile rule answers the constant it replaced
    (``(4096, 512)``, PR 33's sweep), so both programs lower to the text they
    had: the cell is kept out of PR 43's change by construction."""
    _, _, texts = commanda_programs
    assert "tpu_custom_call" in texts[f"{program}.lowered"]
    assert texts[f"{program}.lowered"] == texts[f"{program}.parent"]


# ------------------------------------------------- a family with a latent pool
#
# ``deepseek_v2`` (models/deepseek_v2.py) at the widths of the benchmark's
# ``deepseek-v2-ep8-l8``: a dense and a routed layer, 128 heads over ONE
# 576-wide latent a position (the pool latent-major, ``[L, P, 576, page]``),
# absorbed decode through ``kernels/latent_attention.py``, expanded prefill
# over a primed latent cache of 40 pages, 20 held experts of 5120 x 1536 in
# 8 groups behind the megablox grouped matmul.


@pytest.fixture(scope="module")
def deepseek_programs(v5e):
    from sentio_tpu.kernels.latent_attention import make_latent_attn_impl
    from sentio_tpu.models.deepseek_v2 import DeepseekV2Config, deepseek_v2_forward, init_deepseek_v2

    cfg = DeepseekV2Config(n_layers=LAYERS, vocab_size=12_800)
    place = _on_one_chip(v5e)
    params = jax.eval_shape(lambda: serving_layout(init_deepseek_v2(jax.random.PRNGKey(0), cfg)))
    params = jax.tree_util.tree_map(
        lambda a: place(a.shape, jnp.bfloat16 if a.ndim >= 2 else a.dtype), params)
    slots, nb, page, segment = 8, 40, 128, 512
    pool = place((LAYERS, 1 + slots * nb, cfg.latent_dim, page), jnp.bfloat16)
    impl = make_latent_attn_impl(interpret=False)

    def step(params, tok, lens, table, pages):
        def body(carry, _):
            tok, lens, pages = carry
            logits, pages, _none, routed = paged_decode_forward(
                params, cfg, tok, lens, table, pages, None, attn_impl=impl,
                write_mask=lens < nb * page - 1, return_routed=True)
            return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1, pages), routed["experts"]

        return jax.lax.scan(body, (tok, lens, pages), None, length=2)

    def prefill(params, ids, positions, pages, prior_table, n_prior, scat):
        # a segment as ``paged.prior_prefill_scatter`` runs it: the prior's latents primed from
        # the pool, the forward (which expands them), the segment's own scattered back
        cache = jnp.zeros((LAYERS, 1, nb * page + segment, 1, cfg.latent_dim), jnp.bfloat16)
        cache = cache.at[:, :, : nb * page, 0].set(_latent_tokens(pages, (slice(None), prior_table)))
        logits, cache, routed = deepseek_v2_forward(params, cfg, ids, positions=positions,
                                                    cache={"k": cache, "v": None}, cache_index=n_prior,
                                                    attn_fn=make_prefill_attn_fn(interpret=False))
        new = jax.lax.dynamic_slice_in_dim(cache["k"], n_prior[0], segment, axis=2)
        return logits[:, -1], scatter_prefill(pages, None, new, None, scat)[0], routed["counts"]

    with _the_chips_grouped_matmul():
        compiled = {
            "step": jax.jit(step, donate_argnums=(4,)).lower(
                params, place((slots,), jnp.int32), place((slots,), jnp.int32),
                place((slots, nb), jnp.int32), pool).compile(),
            "prefill": jax.jit(prefill, donate_argnums=(3,)).lower(
                params, place((1, segment), jnp.int32), place((1, segment), jnp.int32), pool,
                place((1, nb), jnp.int32), place((1,), jnp.int32), place((1, segment // page), jnp.int32)).compile(),
        }
    return cfg, params, {k: c.as_text() for k, c in compiled.items()}, \
        {k: c.memory_analysis() for k, c in compiled.items()}


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_deepseek_programs_read_their_weights_where_they_lie(deepseek_programs, program):
    """The v5e compiler takes both programs (Mosaic: the latent walk's
    latent-major pages and 128 heads in VMEM; the grouped matmul at a
    contraction of 5120: a tile of 4096 and a masked rest), and nothing in them makes an
    array with the shape of a projection, of a half of ``kv_b_proj``, of the
    head, or of a stack of experts."""
    cfg, params, texts, _ = deepseek_programs
    assert params["layers_0"]["attn"]["w_uk"].shape == (128, 128, 512) and "moe" not in params["layers_0"]
    assert params["layers_0"]["attn"]["wq_b_t"]["kernel"].shape == (128 * 192, 1536)
    assert _weight_copies(texts[program], params) == []
    stacks = (f"bf16[{cfg.experts_held},{cfg.dim},{cfg.moe_mlp_dim}]", f"bf16[{cfg.experts_held},{cfg.moe_mlp_dim},{cfg.dim}]",
              "bf16[128,128,512]")
    # a view, or the compiler's own prefetch of a 16 MB half of ``kv_b_proj`` into nearer memory
    # (slices joined by a ConcatBitcast custom call): neither is a relayout
    assert [m for m in _pool_shaped(texts[program], stacks)
            if m[2] not in ("parameter", "get-tuple-element", "bitcast", "fusion:bitcast", "custom-call")] == []


def test_deepseek_decode_step_holds_its_kernels_and_its_pool(deepseek_programs):
    """The decode step: one walk of the latent pages a layer and three grouped
    expert matmuls in the routed layer, each a Pallas call; the pool — one
    array, 1,152 B a token a layer — updated in place, never copied."""
    cfg, _, texts, memory = deepseek_programs
    assert texts["step"].count('custom_call_target="tpu_custom_call"') == LAYERS + 3
    assert len(re.findall(r"%latent_attention[.\d]* = ", texts["step"])) == LAYERS
    assert len(re.findall(r"%gmm[.\d]* = ", texts["step"])) == 3
    pool = f"bf16[{LAYERS},321,{cfg.latent_dim},128]"
    made = [m for m in _pool_shaped(texts["step"], (pool,))
            if m[2] not in ("parameter", "get-tuple-element", "bitcast", "while", "tuple", "custom-call")]
    assert all(what in ("fusion:scatter", "fusion:dynamic-update-slice", "scatter", "dynamic-update-slice")
               for _n, _s, what in made), made
    # beside arguments it donates, the step needs little: no pool-sized temporary
    assert memory["step"].temp_size_in_bytes < 2 * LAYERS * 321 * 128 * 1152


# ------------------------------------------- the prefill's attention (PR 41)
#
# ``paged.prior_prefill_scatter`` whole, once a family, as the engine runs it on
# the chip: the prior primed from the pool, the forward with the flash kernel
# bound (kernels/prefill_attention.py), the segment's own rows scattered back.
# What the kernel is there for: no float32 array whose last dimension is the
# keys' — the ``[.., T, S]`` scores the XLA forms wrote to HBM, masked, softmaxed
# and read again — and what PR 29 and PR 31 trapped: nothing but the scatter in
# place makes an array the size of the pool.


def _scores_in_hbm(text: str, keys: int) -> list:
    """Instructions of the compiled text that make a float32 array whose last
    dimension is the keys' and that has more than one row of them."""
    found = []
    for line in text.splitlines():
        inst = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = f32\[([\d,]+)\]\S* [\w\-]+\(", line)
        if inst:
            dims = [int(n) for n in inst.group(1).split(",")]
            if dims[-1] == keys and np.prod(dims) > keys:
                found.append(line.strip()[:120])
    return found


def _dense_prior_prefill(topo, kernel: bool) -> tuple:
    """mistral's widths, two layers: one 512-token segment behind a 40-page
    prior out of a pool of 16 slots x 40 pages → (compiled text, pool shape)."""
    w = dict(WIDTHS["mistral"])
    slots, nb, segment, page = w.pop("slots"), 40, w.pop("segment"), 128
    w.pop("nb")
    cfg = LlamaConfig(n_layers=LAYERS, max_len=8192, **w)
    place = _on_one_chip(topo)
    params = jax.eval_shape(lambda: serving_layout(init_llama(jax.random.PRNGKey(0), cfg)))
    params = jax.tree_util.tree_map(
        lambda a: place(a.shape, jnp.bfloat16 if a.ndim == 2 else a.dtype), params)
    pool_shape = (LAYERS, 1 + slots * nb, page, cfg.n_kv_heads, cfg.head_dim)
    attn_fn = make_prefill_attn_fn(interpret=False) if kernel else None

    def prior_prefill(params, ids, positions, k_pages, v_pages, prior_table, n_prior, scat):
        cache = init_cache(cfg, 1, nb * page + segment)

        def prime(arr, pages):
            dense = pages[:, prior_table]                      # [L, B, PNB, page, Hkv, D]
            return arr.at[:, :, : nb * page].set(dense.reshape(LAYERS, 1, nb * page, *dense.shape[4:]))

        cache = {"k": prime(cache["k"], k_pages), "v": prime(cache["v"], v_pages)}
        logits, cache = llama_forward(params, cfg, ids, positions=positions, cache=cache,
                                      cache_index=n_prior, attn_fn=attn_fn)
        new = [jax.lax.dynamic_slice_in_dim(cache[name], n_prior[0], segment, axis=2) for name in "kv"]
        return (logits[:, -1], *scatter_prefill(k_pages, v_pages, *new, scat))

    pool = place(pool_shape, jnp.bfloat16)
    text = jax.jit(prior_prefill, donate_argnums=(3, 4)).lower(
        params, place((1, segment), jnp.int32), place((1, segment), jnp.int32), pool, pool,
        place((1, nb), jnp.int32), place((1,), jnp.int32),
        place((1, segment // page), jnp.int32)).compile().as_text()
    return text, pool_shape


@pytest.mark.parametrize("kernel", [True, False], ids=["flash-kernel", "xla-form-control"])
def test_dense_prior_prefill_keeps_its_scores_out_of_hbm(v5e, kernel):
    text, pool_shape = _dense_prior_prefill(v5e, kernel)
    scores = _scores_in_hbm(text, PRIOR_KEYS)
    if not kernel:   # the control: the parse finds what the XLA form writes, a layer
        assert len(scores) >= LAYERS, scores
        assert "%prefill_attention" not in text
        return
    assert scores == []
    assert len(re.findall(r"%prefill_attention[.\d]* = ", text)) == LAYERS
    view = (*pool_shape[:2], pool_shape[2] * pool_shape[3], pool_shape[4])
    made = _pool_shaped(text, tuple("bf16[" + ",".join(map(str, dims)) + "]" for dims in (pool_shape, view)))
    assert {op for _n, _s, op in made} <= {*IN_PLACE, "get-tuple-element"}, made
    assert sum(op == "fusion:scatter" for _n, _s, op in made) == 2     # K and V, in place


def test_latent_prior_prefill_holds_the_flash_kernel(deepseek_programs):
    """The latent family's segment behind a 40-page prior: one kernel call a
    layer with the second score term, the expansion writing K and V where the
    kernel reads them (no copy of a ``[S, 128 x 128]`` array), no float32 score
    block, and the pool — primed from and scattered to — updated in place."""
    cfg, _, texts, memory = deepseek_programs
    text = texts["prefill"]
    assert len(re.findall(r"%prefill_attention[.\d]* = ", text)) == LAYERS
    assert _scores_in_hbm(text, PRIOR_KEYS) == []
    expanded = PRIOR_KEYS * cfg.n_heads * cfg.v_head_dim
    for line in text.splitlines():
        inst = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]+)\]\S* (copy|transpose|reshape)\(", line)
        assert not (inst and np.prod([int(n) for n in inst.group(1).split(",")]) >= expanded), line[:160]
    pool = f"bf16[{LAYERS},321,{cfg.latent_dim},128]"
    made = [op for _n, _s, op in _pool_shaped(text, (pool,))]
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast", "scatter", "fusion:scatter"}, made
    # K and V of two layers' expansions and no 256 MB score block beside them
    assert memory["prefill"].temp_size_in_bytes < 3 * 2 * expanded


def test_commanda_prefill_holds_the_flash_kernel_on_both_layer_kinds(commanda_programs):
    """A sliding and a full layer: one kernel call each (the window a static
    term of the same kernel), 16 query heads a kv head by index."""
    _, _, texts = commanda_programs
    assert len(re.findall(r"%prefill_attention[.\d]* = ", texts["prefill"])) == LAYERS
    assert _scores_in_hbm(texts["prefill"], 512 + 512) == []


# ------------------------- a family with convolution state beside the pages
#
# ``lfm2_moe`` (models/lfm2_moe.py) at the widths of the benchmark's
# ``lfm2-24b-a2b-l10``, its first four layers (the check's depth: conv and
# dense twice, attention and routed, conv and routed): 64-wide heads read by
# the decode walk from a LANE-PACKED pool ``[La, P, page, 4, 128]`` (8 kv heads
# of 64, two to a row), the convolution state per slot ``[Lc, 16, 2, 2048]``
# and per page ``[Lc, P, 2, 2048]`` carried through the scan beside it, 64
# experts of 2048 x 1536 picked under a bias behind the megablox grouped matmul.


@pytest.fixture(scope="module")
def lfm2_programs(v5e):
    from sentio_tpu.kernels.paged_attention import lane_packing
    from sentio_tpu.models.lfm2_moe import CONV, FULL, Lfm2MoeConfig, init_lfm2_cache, init_lfm2_moe, lfm2_forward

    cfg = Lfm2MoeConfig(n_layers=4, layer_types=(CONV, CONV, FULL, CONV))
    place = _on_one_chip(v5e)
    params = jax.eval_shape(lambda: serving_layout(init_lfm2_moe(jax.random.PRNGKey(0), cfg)))
    params = jax.tree_util.tree_map(
        lambda a: place(a.shape, jnp.bfloat16 if a.ndim >= 2 and a.shape[-1] > 3 else a.dtype), params)
    # (a segment of 256: at 512 its 2,048 pairs of token and pick make activations
    # [2048, 2048], the shape of a projection, and the weight control cannot tell them apart)
    slots, nb, page, segment = 16, 10, 128, 256
    pack = lane_packing(cfg.n_kv_heads, cfg.head_dim)
    assert pack == 2
    pages = 1 + slots * nb
    # (two pool layers, as the cell's ten model layers have; these four use the first)
    pool = place((2, pages, page, cfg.n_kv_heads // pack, cfg.head_dim * pack), jnp.bfloat16)
    conv = place((3, slots, 2, cfg.dim), jnp.bfloat16)
    tail = place((3, pages, 2, cfg.dim), jnp.bfloat16)
    impl = make_paged_attn_impl(interpret=False)
    assert page_write_path(pool) == "pallas"     # 42 MB: the compiler could place it
    write = make_page_write_impl(interpret=False)

    def step(params, tok, lens, table, k_pages, v_pages, conv, tail):
        def body(carry, _):
            tok, lens, k_pages, v_pages, conv, tail = carry
            logits, k_pages, v_pages, routed, conv, tail = paged_decode_forward(
                params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=impl, write_impl=write,
                write_mask=lens < nb * page - 1, return_routed=True, conv=conv, tail=tail)
            return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1,
                    k_pages, v_pages, conv, tail), routed["experts"]

        return jax.lax.scan(body, (tok, lens, k_pages, v_pages, conv, tail), None, length=2)

    def prefill(params, ids, positions, lens, k_pages, v_pages, tail, prior_table, n_prior, scat):
        # a segment as ``paged.prior_prefill_scatter`` runs it: K and V primed from two prior
        # pages, the state from the last one's tail, the segment's own scattered back
        cache = init_lfm2_cache(cfg, 1, 2 * page + segment, segment // page)
        for name, pool_ in (("k", k_pages), ("v", v_pages)):
            cache[name] = cache[name].at[:, :, : 2 * page].set(
                pool_[:1, prior_table].reshape(1, 1, 2 * page, cfg.n_kv_heads, cfg.head_dim))
        cache["conv"] = tail[:, prior_table[:, 1]]
        logits, cache, routed = lfm2_forward(params, cfg, ids, positions=positions, cache=cache,
                                             cache_index=n_prior,
                                             pad_mask=jnp.arange(segment)[None, :] < lens[:, None])
        new = [jax.lax.dynamic_slice_in_dim(cache[name], n_prior[0], segment, axis=2) for name in ("k", "v")]
        k_pages, v_pages = scatter_prefill(k_pages, v_pages, *(jnp.concatenate([a, a]) for a in new), scat)
        return logits[:, -1], k_pages, v_pages, cache["conv"], tail.at[:, scat].set(cache["tail"]), routed["counts"]

    with _the_chips_grouped_matmul():
        compiled = {
            "step": jax.jit(step, donate_argnums=(4, 5, 6, 7)).lower(
                params, place((slots,), jnp.int32), place((slots,), jnp.int32),
                place((slots, nb), jnp.int32), pool, pool, conv, tail).compile(),
            "prefill": jax.jit(prefill, donate_argnums=(4, 5, 6)).lower(
                params, place((1, segment), jnp.int32), place((1, segment), jnp.int32), place((1,), jnp.int32),
                pool, pool, tail, place((1, 2), jnp.int32), place((1,), jnp.int32),
                place((1, segment // page), jnp.int32)).compile(),
        }
    return cfg, params, {k: c.as_text() for k, c in compiled.items()}, \
        {k: c.memory_analysis() for k, c in compiled.items()}, (pool, tail)


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_lfm2_programs_read_their_weights_where_they_lie(lfm2_programs, program):
    """The v5e compiler takes both programs (Mosaic: the decode walk over a
    lane-packed pool of 64-wide heads; the grouped matmul over 64 experts of
    2048 x 1536), and nothing in them makes an array with the shape of a
    projection, of the table the head reads, or of a stack of experts."""
    cfg, params, texts, _, _ = lfm2_programs
    assert params["layers_0"]["conv"]["w_in"]["kernel"].shape == (2048, 6144) and "lm_head" not in params
    assert params["layers_2"]["attn"]["wq_t"]["kernel"].shape == (2048, 2048)
    assert _weight_copies(texts[program], params) == []
    stacks = (f"bf16[{cfg.n_experts},{cfg.dim},{cfg.moe_mlp_dim}]", f"bf16[{cfg.n_experts},{cfg.moe_mlp_dim},{cfg.dim}]")
    assert [m for m in _pool_shaped(texts[program], stacks)
            if m[2] not in ("parameter", "get-tuple-element", "bitcast")] == []


def test_lfm2_decode_step_holds_its_kernels_and_its_state(lfm2_programs):
    """The decode step: ONE walk of the pages (the one attention layer, over
    the lane-packed pool), ONE write of the step's K and V rows before it
    (kernels/page_write.py) and three grouped expert matmuls in each of the two
    routed layers, each a Pallas call; the page tails updated in place; the
    pool touched by the two kernels alone — until PR 45 the compiler moved each
    42 MB pool into nearer memory (``S(1)``) for the XLA scatter and back,
    every sub-step (``copy-start`` / ``copy-done`` of its shape)."""
    cfg, _, texts, memory, (pool, tail) = lfm2_programs
    assert texts["step"].count('custom_call_target="tpu_custom_call"') == 1 + 1 + 2 * 3
    assert len(re.findall(r"%paged_attention[.\d]* = ", texts["step"])) == 1
    assert len(re.findall(r"%page_write[.\d]* = ", texts["step"])) == 1
    assert len(re.findall(r"%gmm[.\d]* = ", texts["step"])) == 6
    shapes = tuple(f"bf16[{','.join(str(n) for n in a.shape)}]" for a in (pool, tail))
    # (a ``copy-done`` of the pool's shape, which this list allowed until PR 45, now fails it)
    made = [m for m in _pool_shaped(texts["step"], shapes)
            if m[2] not in ("parameter", "get-tuple-element", "bitcast", "while", "tuple", "custom-call")]
    assert all(what in ("fusion:scatter", "fusion:dynamic-update-slice", "scatter", "dynamic-update-slice")
               for _n, _s, what in made), made
    assert not re.search(r"= bf16\[2,161,128,4,128\]\S* (copy|transpose|pad)\(", texts["step"])
    # beside arguments it donates, the step needs little: no pool-sized temporary
    assert memory["step"].temp_size_in_bytes < int(np.prod(pool.shape)) * 2


def test_lfm2_prefill_writes_pool_and_tails_where_they_lie(lfm2_programs):
    """The prefill over a prior: K, V and the page tails are updated in place
    (the UPDATE takes the lane-packed pool's shape, never the pool the
    update's)."""
    _, _, texts, _, (pool, tail) = lfm2_programs
    shapes = tuple(f"bf16[{','.join(str(n) for n in a.shape)}]" for a in (pool, tail))
    made = [m for m in _pool_shaped(texts["prefill"], shapes)
            if m[2] not in ("parameter", "get-tuple-element", "bitcast", "tuple", "custom-call")]
    assert all(what in ("fusion:scatter", "fusion:dynamic-update-slice", "scatter", "dynamic-update-slice")
               for _n, _s, what in made), made



# ----------------------------- a family with a matrix state beside the pages
#
# ``nemotron_h`` (models/nemotron_h.py) at the widths of the benchmark's
# ``nemotron-3-nano-30b-a3b-ep2-l14``, its first seven blocks (the check's
# depth, ``MEMEM*E``: every kind): the decode walk at TWO kv heads of 128, the
# Mamba state per slot ``[3, 16, 64, 64, 128]`` float32 carried through the
# scan, 64 held experts of 2688 x 1856 — served padded to 1,920, whole tiles
# of lanes — behind two grouped matmuls a block, and a prefill segment that
# starts from a snapshot and leaves two.


@pytest.fixture(scope="module")
def nemotron_programs(v5e):
    from sentio_tpu.models.nemotron_h import NemotronHConfig, init_nemotron_cache, init_nemotron_h, nemotron_h_forward

    cfg = NemotronHConfig(n_layers=7, pattern="MEMEM*E")
    place = _on_one_chip(v5e)
    params = jax.eval_shape(lambda: serving_layout(init_nemotron_h(jax.random.PRNGKey(0), cfg)))
    params = jax.tree_util.tree_map(
        lambda a: place(a.shape, jnp.bfloat16 if a.ndim >= 2 and a.shape[-1] > 4 else a.dtype), params)
    slots, nb, page, segment, snapshots = 16, 10, 128, 256, 64
    pages = 1 + slots * nb
    # (two pool layers, as the cell's fourteen blocks have; these seven use the first)
    pool = place((2, pages, page, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    state = {name: place(shape, dtype) for name, (shape, dtype) in cfg.state_shapes(slots).items()}
    snaps = {name: place(shape, dtype) for name, (shape, dtype) in cfg.state_shapes(snapshots).items()}
    impl = make_paged_attn_impl(interpret=False)
    assert page_write_path(pool) == "pallas"     # 21 MB: the compiler could place it
    write = make_page_write_impl(interpret=False)
    assert ssm_update_path(state["ssm"]) == "pallas"   # float32, a head 64 x 128: whole tiles
    update = make_ssm_update_impl(interpret=False)

    def step(params, tok, lens, table, k_pages, v_pages, state):
        def body(carry, _):
            tok, lens, k_pages, v_pages, state = carry
            logits, k_pages, v_pages, routed, state, _ = paged_decode_forward(
                params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=impl, write_impl=write, ssm_impl=update,
                write_mask=lens < nb * page - 1, return_routed=True, conv=state)
            return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1, k_pages, v_pages, state), routed["experts"]

        return jax.lax.scan(body, (tok, lens, k_pages, v_pages, state), None, length=2)

    def prefill(params, ids, positions, lens, k_pages, v_pages, state, snaps, prior_table, n_prior, scat, snap):
        # a segment as ``paged.prior_prefill_scatter`` runs it: K and V primed from two prior
        # pages, the state from a snapshot, the row's end state into its slot, two boundaries kept
        cache = init_nemotron_cache(cfg, 1, 2 * page + segment, 2)
        for name, pool_ in (("k", k_pages), ("v", v_pages)):
            cache[name] = cache[name].at[:, :, : 2 * page].set(
                pool_[:1, prior_table].reshape(1, 1, 2 * page, cfg.n_kv_heads, cfg.head_dim))
        cache["state"] = {name: snaps[name][:, snap["start"]] for name in snaps}
        cache["snap_at"] = snap["at"]
        logits, cache, routed = nemotron_h_forward(
            params, cfg, ids, positions=positions, cache=cache, cache_index=n_prior,
            pad_mask=jnp.arange(segment)[None, :] < lens[:, None], logits_at=lens - 1)
        new = [jax.lax.dynamic_slice_in_dim(cache[name], n_prior[0], segment, axis=2) for name in ("k", "v")]
        k_pages, v_pages = scatter_prefill(k_pages, v_pages, *(jnp.concatenate([a, a]) for a in new), scat)
        state = {name: state[name].at[:, snap["slot"]].set(cache["state"][name], mode="drop") for name in state}
        snaps = {name: snaps[name].at[:, snap["ids"]].set(cache["snaps"][name], mode="drop") for name in snaps}
        return logits[:, 0], k_pages, v_pages, state, snaps, routed["counts"]

    snap = {"slot": place((1,), jnp.int32), "start": place((1,), jnp.int32),
            "at": place((1, 2), jnp.int32), "ids": place((1, 2), jnp.int32)}
    with _the_chips_grouped_matmul():
        compiled = {
            "step": jax.jit(step, donate_argnums=(4, 5, 6)).lower(
                params, place((slots,), jnp.int32), place((slots,), jnp.int32),
                place((slots, nb), jnp.int32), pool, pool, state).compile(),
            "prefill": jax.jit(prefill, donate_argnums=(4, 5, 6, 7)).lower(
                params, place((1, segment), jnp.int32), place((1, segment), jnp.int32), place((1,), jnp.int32),
                pool, pool, state, snaps, place((1, 2), jnp.int32), place((1,), jnp.int32),
                place((1, segment // page), jnp.int32), snap).compile(),
        }
    return cfg, params, {k: c.as_text() for k, c in compiled.items()}, \
        {k: c.memory_analysis() for k, c in compiled.items()}, (pool, state["ssm"], snaps["ssm"])


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_nemotron_programs_read_their_weights_where_they_lie(nemotron_programs, program):
    """The v5e compiler takes both programs (Mosaic: the decode walk at two kv
    heads; the grouped matmul over 64 experts of 2688 x 1920), and nothing in
    them makes an array with the shape of a projection, of the head, or of a
    stack of experts: the serving tree holds ``w_in`` [out, in] and the
    experts' width as whole tiles of lanes (at 1,856 the compiler copies every
    stack at the head of every program: 0.64 GB a block)."""
    cfg, params, texts, _, _ = nemotron_programs
    assert params["layers_0"]["mamba"]["w_in_t"]["kernel"].shape == (10304, 2688)
    assert params["layers_5"]["attn"]["wq_t"]["kernel"].shape == (4096, 2688)
    assert params["layers_1"]["moe"]["w_up"].shape == (64, 2688, 1920)
    assert _weight_copies(texts[program], params) == []
    stacks = ("bf16[64,2688,1920]", "bf16[64,1920,2688]")
    assert [m for m in _pool_shaped(texts[program], stacks)
            if m[2] not in ("parameter", "get-tuple-element", "bitcast")] == []


def test_nemotron_decode_step_holds_its_kernels_and_its_state(nemotron_programs):
    """The decode step: ONE walk of the pages (the one attention block), ONE
    write of the step's K and V rows before it, two grouped expert matmuls in
    each of the three routed blocks and ONE state update in each of the three
    Mamba blocks, each a Pallas call; the 21 MB pools and the slots' 100 MB
    of state touched by their kernels alone — no XLA instruction reads or
    makes the state or a block's slice of it (until PR 46 two fusions a block
    did: the ``y`` reduce and the masked write), and none is moved into
    nearer memory and back."""
    cfg, _, texts, memory, (pool, state, _) = nemotron_programs
    assert texts["step"].count('custom_call_target="tpu_custom_call"') == 1 + 1 + 3 * 2 + 3
    assert len(re.findall(r"%paged_attention[.\d]* = ", texts["step"])) == 1
    assert len(re.findall(r"%page_write[.\d]* = ", texts["step"])) == 1
    assert len(re.findall(r"%gmm[.\d]* = ", texts["step"])) == 6
    assert len(re.findall(r"%ssm_update[.\d]* = ", texts["step"])) == 3
    shapes = ("bf16[2,161,128,2,128]", "f32[3,16,64,64,128]", "f32[16,64,64,128]")
    carried = ("parameter", "get-tuple-element", "bitcast", "while", "tuple", "custom-call")
    # nothing but the calls MAKES a pool, the state or a block's slice of it (a ``copy-done`` fails this) ...
    made = [m for m in _pool_shaped(texts["step"], shapes) if m[2] not in carried]
    assert made == [], made
    # ... and nothing but the calls READS one
    held = {name for name, _shape, _op in _pool_shaped(texts["step"], shapes)}
    readers = [line.strip()[:160] for line in texts["step"].splitlines()
               if (inst := re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\((.*)", line))
               and inst.group(1) not in carried and held & set(re.findall(r"%([\w.\-]+)", inst.group(2)))]
    assert readers == [], readers
    # beside arguments it donates, the step needs little: no temporary the size of the slots' state
    assert memory["step"].temp_size_in_bytes < int(np.prod(state.shape)) * 4


def test_nemotron_prefill_writes_pool_state_and_snapshots_where_they_lie(nemotron_programs):
    """The prefill over a prior: K, V, the row's slot and the two snapshots
    are updated in place."""
    _, _, texts, _, _ = nemotron_programs
    shapes = ("bf16[2,161,128,2,128]", "f32[3,16,64,64,128]", "f32[3,64,64,64,128]")
    made = [m for m in _pool_shaped(texts["prefill"], shapes)
            if m[2] not in ("parameter", "get-tuple-element", "bitcast", "tuple", "custom-call")]
    assert all(what in ("fusion:scatter", "fusion:dynamic-update-slice", "scatter", "dynamic-update-slice")
               for _n, _s, what in made), made


# ------------------------- a family whose recurrence is a selective scan (PR 48)
#
# ``jamba`` (models/jamba.py) at the published widths of the benchmark's
# ``ai21-jamba2-3b``, its first eight layers (the check's depth: seven Mamba-1
# layers and the attention layer): the decode walk at ONE kv head under 20
# query heads, the state per slot ``[7, 8, 16, 5120]`` float32 (``S``
# transposed) carried through the scan and updated in place by ``kernels/
# ssm_update.py::selective_update`` (PR 49), and a 512-token prefill segment — the
# selective scan a kernel call a Mamba layer, the flash kernel over a 40-page
# prior bucket — that starts from a snapshot and leaves two.


@pytest.fixture(scope="module")
def jamba_programs(v5e):
    from sentio_tpu.models import jamba
    from sentio_tpu.models.jamba import JambaConfig, init_jamba, init_jamba_cache, jamba_forward

    cfg = JambaConfig(n_layers=8)
    place = _on_one_chip(v5e)
    params = jax.eval_shape(lambda: serving_layout(init_jamba(jax.random.PRNGKey(0), cfg)))
    params = jax.tree_util.tree_map(
        lambda a: place(a.shape, jnp.bfloat16 if a.ndim == 2 and a.shape[-1] > 4 and a.shape[0] > 16 else a.dtype),
        params)
    slots, nb, page, segment, snapshots = 8, 40, 128, 512, 64
    # (two pool layers, as the cell's 28 layers have; these eight use the first)
    pool = place((2, 1 + slots * nb, page, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    state = {name: place(shape, dtype) for name, (shape, dtype) in cfg.state_shapes(slots).items()}
    snaps = {name: place(shape, dtype) for name, (shape, dtype) in cfg.state_shapes(snapshots).items()}
    impl = make_paged_attn_impl(interpret=False)
    # one row of lanes a position: Mosaic refuses the half-sublane slice, the scatter writes K and V; the
    # state, float32 and a row's [16, 5120] whole tiles, takes the update kernel (PR 49)
    assert page_write_path(pool) == "xla" and ssm_update_path(state["ssm"]) == "pallas"
    update = make_ssm_update_impl(interpret=False)
    attn_fn = make_prefill_attn_fn(interpret=False)

    def step(params, tok, lens, table, k_pages, v_pages, state):
        def body(carry, _):
            tok, lens, k_pages, v_pages, state = carry
            logits, k_pages, v_pages, _, state, _ = paged_decode_forward(
                params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=impl, ssm_impl=update,
                write_mask=lens < nb * page - 1, conv=state)
            return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1, k_pages, v_pages, state), None

        return jax.lax.scan(body, (tok, lens, k_pages, v_pages, state), None, length=2)[0]

    def prefill(params, ids, positions, lens, k_pages, v_pages, state, snaps, prior_table, n_prior, scat, snap):
        # a segment as ``paged.prior_prefill_scatter`` runs it: K and V primed from a 40-page prior
        # bucket, the state from a snapshot, the row's end state into its slot, two boundaries kept
        cache = init_jamba_cache(cfg, 1, nb * page + segment, 2)
        for name, pool_ in (("k", k_pages), ("v", v_pages)):
            cache[name] = cache[name].at[:, :, : nb * page].set(
                pool_[:1, prior_table].reshape(1, 1, nb * page, cfg.n_kv_heads, cfg.head_dim))
        cache["state"] = {name: snaps[name][:, snap["start"]] for name in snaps}
        cache["snap_at"] = snap["at"]
        logits, cache = jamba_forward(
            params, cfg, ids, positions=positions, cache=cache, cache_index=n_prior, attn_fn=attn_fn,
            pad_mask=jnp.arange(segment)[None, :] < lens[:, None], logits_at=lens - 1)
        new = [jax.lax.dynamic_slice_in_dim(cache[name], n_prior[0], segment, axis=2) for name in ("k", "v")]
        k_pages, v_pages = scatter_prefill(k_pages, v_pages, *(jnp.concatenate([a, a]) for a in new), scat)
        state = {name: state[name].at[:, snap["slot"]].set(cache["state"][name], mode="drop") for name in state}
        snaps = {name: snaps[name].at[:, snap["ids"]].set(cache["snaps"][name], mode="drop") for name in snaps}
        return logits[:, 0], k_pages, v_pages, state, snaps

    snap = {"slot": place((1,), jnp.int32), "start": place((1,), jnp.int32),
            "at": place((1, 2), jnp.int32), "ids": place((1, 2), jnp.int32)}
    was, jamba.SCAN_FORM = jamba.SCAN_FORM, "pallas"   # what a TPU backend picks; the CPU here would take the loop
    try:
        compiled = {
            "step": jax.jit(step, donate_argnums=(4, 5, 6)).lower(
                params, place((slots,), jnp.int32), place((slots,), jnp.int32),
                place((slots, nb), jnp.int32), pool, pool, state).compile(),
            "prefill": jax.jit(prefill, donate_argnums=(4, 5, 6, 7)).lower(
                params, place((1, segment), jnp.int32), place((1, segment), jnp.int32), place((1,), jnp.int32),
                pool, pool, state, snaps, place((1, nb), jnp.int32), place((1,), jnp.int32),
                place((1, segment // page), jnp.int32), snap).compile(),
        }
    finally:
        jamba.SCAN_FORM = was
    return cfg, params, {k: c.as_text() for k, c in compiled.items()}, \
        {k: c.memory_analysis() for k, c in compiled.items()}


JAMBA_HELD = ("bf16[2,321,128,1,128]", "f32[7,8,16,5120]", "bf16[7,8,3,5120]", "f32[7,64,16,5120]",
              "bf16[7,64,3,5120]")


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_jamba_programs_read_their_weights_where_they_lie(jamba_programs, program):
    """The v5e compiler takes both programs (Mosaic: the decode walk and the
    flash prefill kernel at 20 query heads on one kv head), and nothing in
    them makes an array with the shape of a projection, of the SwiGLU or of
    the tied table: the serving tree holds ``w_in`` [out, in]."""
    cfg, params, texts, _ = jamba_programs
    assert params["layers_0"]["mamba"]["w_in_t"]["kernel"].shape == (10240, 2560)
    assert params["layers_7"]["attn"]["wq_t"]["kernel"].shape == (2560, 2560)
    assert params["layers_7"]["attn"]["wk_t"]["kernel"].shape == (128, 2560)
    assert params["layers_0"]["mamba"]["a_log"].dtype == jnp.float32
    assert _weight_copies(texts[program], params) == []


def test_jamba_decode_step_holds_its_walk_and_relays_neither_pool_nor_state(jamba_programs):
    """The decode step: 1 + 7 Pallas calls — the walk of the pages in the one
    attention layer (K and V are written by the scatter) and ONE state update
    in each of the seven Mamba-1 layers. The pools are made by nothing. The
    slots' float32 state — 18 MB here, 68 MB at the cell's 26 layers — is made
    by the update calls and by nothing else, and read by nothing else: until
    PR 49 the compiler PLACED it in nearer memory at the head of every
    sub-step and copied it back at its end (a ``copy`` / ``copy-done`` of its
    shape, 136 MB a sub-step at the cell's depth) and seven fusions updated
    every slot's. The convolution's columns keep the masked write, a
    dynamic-update-slice a layer in place; the step's temporaries stay under
    the state's size."""
    cfg, _, texts, memory = jamba_programs
    assert texts["step"].count('custom_call_target="tpu_custom_call"') == 1 + 7
    assert len(re.findall(r"%paged_attention[.\d]* = ", texts["step"])) == 1
    assert len(re.findall(r"%ssm_update[.\d]* = ", texts["step"])) == 7
    carried = ("parameter", "get-tuple-element", "bitcast", "while", "tuple")
    made = [m for m in _pool_shaped(texts["step"], JAMBA_HELD) if m[2] not in carried]
    assert not [m for m in made if m[1] == JAMBA_HELD[0]], made                  # the pools: made by nothing
    views = ("f32[7,8,16,5120]", "f32[8,16,5120]")     # the state, a layer's slice of it
    # nothing but the calls MAKES the state or a layer's slice of it (each call's first result, aliased to its operand) ...
    assert [m for m in _pool_shaped(texts["step"], views) if m[2] not in carried] == []
    assert len(re.findall(r"%ssm_update[.\d]* = \(f32\[7,8,16,5120\].*output_to_operand_aliasing=\{\{0\}: \(7, \{\}\)\}",
                          texts["step"])) == 7
    assert not re.search(r"= f32\[7,8,16,5120\]\S* (copy|copy-start|copy-done)\(", texts["step"])
    # ... and nothing but the calls READS it
    held = {name for name, _shape, _op in _pool_shaped(texts["step"], views)}
    readers = [line.strip()[:160] for line in texts["step"].splitlines()
               if (inst := re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\((.*)", line))
               and inst.group(1) not in (*carried, "custom-call") and held & set(re.findall(r"%([\w.\-]+)", inst.group(2)))]
    assert readers == [], readers
    columns = [op for _n, shape, op in made if shape == "bf16[7,8,3,5120]"]
    assert columns.count("fusion:dynamic-update-slice") == 7, columns
    assert memory["step"].temp_size_in_bytes < 7 * 8 * 16 * 5120 * 4


def test_jamba_prefill_holds_the_flash_kernel_and_writes_state_and_snapshots_where_they_lie(jamba_programs):
    """The prefill over a 40-page prior: one flash kernel call (the attention
    layer), the selective scan ONE kernel call a Mamba layer and no loop (the
    loop it replaces is a fusion or two a token: 20 thousand device operations
    a segment at the cell's depth), never ``[T, N, inner]``; K, V, the row's slot and the two
    float32 snapshots are updated in place. (The snapshots' three bf16 COLUMNS
    — 51 MB of the pool's 0.60 GB at the cell's depth — are relaid around
    their scatter: three rows are no tile; named in PERF.md section 7.)"""
    cfg, _, texts, memory = jamba_programs
    assert len(re.findall(r"%prefill_attention[.\d]* = ", texts["prefill"])) == 1
    assert len(re.findall(r"%selective_scan[.\d]* = ", texts["prefill"])) == 7 and " while(" not in texts["prefill"]
    large = (JAMBA_HELD[0], "f32[7,8,16,5120]", "f32[7,64,16,5120]")
    made = [m for m in _pool_shaped(texts["prefill"], large)
            if m[2] not in ("parameter", "get-tuple-element", "bitcast", "tuple", "custom-call")]
    assert {op for _n, _s, op in made} <= {"scatter", "fusion:scatter", "dynamic-update-slice",
                                           "fusion:dynamic-update-slice"}, made
    # no array of a segment's positions x the state: 512 x 16 x 5120 float32 would be 168 MB
    assert not re.findall(r"f32\[(?:1,)?5(?:12|28),(?:1,)?16,5120\]|f32\[(?:1,)?5(?:12|28),(?:1,)?5120,16\]",
                          texts["prefill"])
    assert memory["prefill"].temp_size_in_bytes < 0.25e9


# ------------------------------ a family whose window is shorter than a row
#
# ``mellum`` (models/mellum.py) at the widths of the benchmark's
# ``mellum2-12b-a2.5b-l12``, a sliding and a full layer: 32 query heads on 4 kv
# heads of 128 at hidden 2,304, a window of 1,024 keys against tables of 40
# pages (the decode walk of the sliding layer starts at the window's first
# block), rotate-half rotary with YaRN's frequencies in the full layer, 64
# experts of 2304 x 896 — seven tiles of 128 lanes — behind the megablox
# grouped matmul, an untied head of 98,304 columns; and one prior-prefill
# program: a segment behind 32 pages — of 384 tokens, a prompt's last: the
# 4,096 pairs of a 512-token one are an ACTIVATION [4096, 2304] with the shape
# of ``wq_t``, which the parse of weight copies could not tell from it (the
# expert layer alone is compiled at 512 tokens further down).
@pytest.fixture(scope="module")
def mellum_programs(v5e):
    from sentio_tpu.models.mellum import FULL, SLIDING, MellumConfig, init_mellum, mellum_forward

    cfg = MellumConfig(n_layers=LAYERS, layer_kinds=f"{SLIDING},{FULL}")
    place = _on_one_chip(v5e)
    params = jax.eval_shape(lambda: serving_layout(init_mellum(jax.random.PRNGKey(0), cfg)))
    params = jax.tree_util.tree_map(
        lambda a: place(a.shape, jnp.bfloat16 if a.ndim >= 2 else a.dtype), params)
    slots, nb, page, segment, prior = 8, 40, 128, 384, 32 * 128
    pool = place((LAYERS, 1 + slots * nb, page, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    impl = make_paged_attn_impl(interpret=False)

    def step(params, tok, lens, table, k_pages, v_pages):
        def body(carry, _):
            tok, lens, k_pages, v_pages = carry
            logits, k_pages, v_pages, routed = paged_decode_forward(
                params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=impl,
                write_mask=lens < nb * page - 1, return_routed=True)
            return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1,
                    k_pages, v_pages), routed["experts"]

        return jax.lax.scan(body, (tok, lens, k_pages, v_pages), None, length=2)

    def prefill(params, ids, positions, cache, n_prior):
        return mellum_forward(params, cfg, ids, positions=positions, cache=cache,
                              cache_index=n_prior, attn_fn=make_prefill_attn_fn(interpret=False))

    cache = place((LAYERS, 1, prior + segment, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    with _the_chips_grouped_matmul():
        texts = {
            "step": jax.jit(step, donate_argnums=(4, 5)).lower(
                params, place((slots,), jnp.int32), place((slots,), jnp.int32),
                place((slots, nb), jnp.int32), pool, pool).compile().as_text(),
            "prefill": jax.jit(prefill, donate_argnums=(3,)).lower(
                params, place((1, segment), jnp.int32), place((1, segment), jnp.int32),
                {"k": cache, "v": cache}, place((1,), jnp.int32)).compile().as_text(),
        }
    return cfg, params, texts, pool.shape


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_mellum_programs_read_their_weights_where_they_lie(mellum_programs, program):
    """The v5e compiler takes both programs, and nothing in them makes an array
    with the shape of a projection ([4096, 2304]), of the table or the head
    ([2304, 98304]), or of a stack of experts ([64, 2304, 896]: 896 is seven
    whole tiles of lanes, so ``lane_padded`` pads nothing)."""
    cfg, params, texts, _ = mellum_programs
    assert params["layers_0"]["attn"]["wq_t"]["kernel"].shape == (cfg.n_heads * cfg.head_dim, cfg.dim)
    assert params["lm_head"]["kernel"].shape == (cfg.dim, cfg.vocab_size)
    assert params["layers_0"]["moe"]["w_up"].shape == (64, 2304, 896)
    assert _weight_copies(texts[program], params) == []
    stacks = (f"bf16[{cfg.experts_held},{cfg.dim},{cfg.mlp_dim}]", f"bf16[{cfg.experts_held},{cfg.mlp_dim},{cfg.dim}]")
    assert [m for m in _pool_shaped(texts[program], stacks)
            if m[2] not in ("parameter", "get-tuple-element", "bitcast")] == []


def test_mellum_decode_step_holds_its_kernels_and_copies_no_pool(mellum_programs):
    """A layer of the decode step: one walk of the pages (the sliding layer's
    from its window's first block) and three grouped expert matmuls, each a
    Pallas call; beside them only the scatter that updates the pool in place
    makes an array of its size, and nothing one of a layer of it."""
    cfg, _, texts, pool_shape = mellum_programs
    # (84 MB a pool at these two layers, 505 at the cell's twelve: the page write stays the scatter)
    assert page_write_path(jax.ShapeDtypeStruct((12, *pool_shape[1:]), jnp.bfloat16)) == "xla"
    assert texts["step"].count('custom_call_target="tpu_custom_call"') == LAYERS * 4
    assert len(re.findall(r"%gmm[.\d]* = ", texts["step"])) == LAYERS * 3
    hlo_shape = lambda dims: "bf16[" + ",".join(map(str, dims)) + "]"  # noqa: E731
    made = _pool_shaped(texts["step"], (hlo_shape(pool_shape), hlo_shape(pool_shape[1:])))
    assert [m for m in made if m[2] not in ("parameter", "get-tuple-element", "scatter", "fusion:scatter")] == []
    assert sum(m[2] == "fusion:scatter" for m in made) == 2 * LAYERS


def test_mellum_prior_prefill_holds_the_flash_kernel_on_both_layer_kinds(mellum_programs):
    """A sliding and a full layer of a segment behind 32 pages: one flash kernel
    call each (the window a static term of the same kernel, 8 query heads a kv
    head by index), three grouped matmuls a layer over the segment's 3,072
    pairs, and no score tensor over the prior's keys in HBM."""
    _, _, texts, _ = mellum_programs
    assert len(re.findall(r"%prefill_attention[.\d]* = ", texts["prefill"])) == LAYERS
    assert len(re.findall(r"%gmm[.\d]* = ", texts["prefill"])) == LAYERS * 3
    assert _scores_in_hbm(texts["prefill"], 32 * 128 + 384) == []


# ------------------------------------------ the grouped matmul's tiles (PR 43)
#
# ``models/moe.py::expert_tile`` sizes the weight tile from the matrix and the
# VMEM a kernel is given unasked: the expert layer of each routed family at its
# published widths, under a decode step's rows, a 512-token segment and an
# admission of eight (the widest row tile), compiled for the chip — a tile
# over VMEM is refused HERE, before any chip time.

ROUTED = {"commanda": ("commanda_programs", 32), "deepseek": ("deepseek_programs", 8), "lfm2": ("lfm2_programs", 16),
          "nemotron": ("nemotron_programs", 16), "mellum": ("mellum_programs", 8)}


@pytest.mark.parametrize("load", ["decode", "segment", "admission"])
@pytest.mark.parametrize("name", sorted(ROUTED))
def test_the_expert_layer_compiles_at_the_tiles_the_rule_picks(request, v5e, name, load):
    fixture, slots = ROUTED[name]
    cfg, params, *_ = request.getfixturevalue(fixture)
    mp = next(lp["moe"] for lp in params.values() if isinstance(lp, dict) and "moe" in lp)
    b, t = {"decode": (slots, 1), "segment": (1, 512), "admission": (8, 512)}[load]
    x = _on_one_chip(v5e)((b, t, cfg.dim), jnp.bfloat16)
    with _the_chips_grouped_matmul() as moe:
        text = jax.jit(lambda mp, x: moe.expert_layer(mp, cfg, x)[0]).lower(mp, x).compile().as_text()
        tiles = moe.expert_tiles(mp, cfg, b * t)
    # a grouped matmul a matrix (three; two for ungated experts), each the Pallas call the
    # benchmark's trace reads by its name
    calls = 3 if "w_gate" in mp else 2
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    assert len(re.findall(r"%gmm[.\d]* = ", text)) == calls
    rows = {"decode": 32, "segment": 32, "admission": 256}[load]
    if (name, load) == ("mellum", "segment"):   # eight picks of 64 experts: 4,096 pairs, 64 an expert
        rows = 64
    assert all(t["tile"][0] == rows and moe.tile_vmem(*t["tile"]) <= moe._GMM_VMEM for t in tiles.values())
    if load != "admission":      # the whole expert in one step, or the contraction whole
        assert [t["steps_per_expert"] for t in tiles.values()] == {
            "commanda": [8, 8, 8], "deepseek": [3, 3, 2], "lfm2": [1, 1, 1], "nemotron": [3, 3],
            "mellum": [1, 1, 1]}[name]


# ------------------------------------------- the encoders' weights, cast once
#
# PR 52. ``L.embed`` was ``table.astype(dtype)[ids]`` and the embedder's leaves
# float32 (``init_encoder``): the v5e compiler keeps a cast where it is given,
# so the compiled forward converted the whole ``[250002, 1024]`` table — 1.0 GB
# read, 0.5 written — to look up one query's 32 rows, and streamed 302 M matrix
# parameters as float32, on every /chat request (`convert_element_type` in every
# chat cell's trace). The serving classes now hold each leaf in the dtype the
# forward uses it in (models/transformer.py::serving_dtypes) and ``L.embed``
# gathers before it casts. Held to the compiled programs at the benchmark's
# widths: the embedder ``EncoderConfig.base()`` over one query of 32 tokens, the
# reranker its configurations' 250002 x 768 in 12 layers over 8 pairs of 512.

RERANKER = dict(vocab_size=250_002, dim=768, n_layers=12, n_heads=12, mlp_dim=3072, max_len=512)


def _table_converts(text: str, cfg) -> list:
    """Instructions that make an array of the token table's shape."""
    return [line.strip()[:140] for line in text.splitlines()
            if re.search(rf"= \w+\[{cfg.vocab_size},{cfg.dim}\]\S* (convert|fusion|copy)\(", line)]


def _float32_matrices(text: str, cfg) -> set:
    """Float32 parameters of a layer's matrix shapes, ``[dim, dim]`` / ``[dim, mlp]`` / ``[mlp, dim]``."""
    shapes = "|".join(f"{a},{b}" for a, b in ((cfg.dim, cfg.dim), (cfg.dim, cfg.mlp_dim), (cfg.mlp_dim, cfg.dim)))
    found = (re.match(rf"\s+(\S+) = f32\[(?:{shapes})\]\S* parameter\(", line) for line in text.splitlines())
    return {m.group(1) for m in found if m}


@pytest.fixture(scope="module")
def encoder_programs(v5e):
    import dataclasses

    from sentio_tpu.models.cross_encoder import cross_encoder_scores, init_cross_encoder
    from sentio_tpu.models.transformer import (
        EncoderConfig, encoder_forward, init_encoder, mean_pool, serving_dtypes)

    place = _on_one_chip(v5e)

    def attn(q, k, v, n):   # what kernels.select_encoder_attn_fn picks on the chip
        return flash_attention(q, k, v, n, causal=False)

    def leaves(init, cfg, held=True):
        tree = jax.eval_shape(lambda: (serving_dtypes(init(jax.random.PRNGKey(0), cfg), cfg, owned=True)[0]
                                       if held else init(jax.random.PRNGKey(0), cfg)))
        return jax.tree_util.tree_map(lambda a: place(a.shape, a.dtype), tree)

    def embed(cfg, held=True):
        return jax.jit(lambda p, ids, mask: mean_pool(encoder_forward(p, cfg, ids, mask, attn_fn=attn), mask)).lower(
            leaves(init_encoder, cfg, held), place((1, 32), jnp.int32), place((1, 32), jnp.bool_)).compile()

    base, reranker = EncoderConfig.base(), EncoderConfig(**RERANKER)
    shallow = dataclasses.replace(base, n_layers=2)
    return {
        "embedder": (base, embed(base)),
        "reranker": (reranker, jax.jit(
            lambda p, ids, mask, types: cross_encoder_scores(p, reranker, ids, mask, types, attn_fn=attn)).lower(
            leaves(init_cross_encoder, reranker), place((8, 512), jnp.int32), place((8, 512), jnp.bool_),
            place((8, 512), jnp.int32)).compile()),
        # the controls, two layers deep: ``init_encoder``'s float32 leaves as the classes held them until PR 52
        "float32-leaves": (shallow, embed(shallow, held=False)),
        "held-shallow": (shallow, embed(shallow)),
    }


@pytest.mark.parametrize("model", ["embedder", "reranker"])
def test_encoder_forwards_convert_no_weight(encoder_programs, model):
    """Compiled from the leaves the classes hold: no instruction makes an array
    of the token table's shape, no matrix arrives as float32, and the Pallas
    flash kernel is in the program."""
    cfg, compiled = encoder_programs[model]
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _table_converts(text, cfg) == []
    assert _float32_matrices(text, cfg) == set()


def test_float32_leaves_cost_their_rows_and_are_streamed_wide(encoder_programs, v5e):
    """The controls. A table that still arrives float32 is gathered and its 32
    rows cast — ``L.embed``'s order alone spares the table — while the parent's
    order, compiled by itself, holds the conversion the traces showed; the
    float32 matrices are found by the parse the test above relies on, six a
    layer; and the program over the held leaves reads under half the bytes."""
    cfg, wide = encoder_programs["float32-leaves"]
    assert _table_converts(wide.as_text(), cfg) == []
    assert len([name for name in _float32_matrices(wide.as_text(), cfg) if "kernel" in name]) == 6 * cfg.n_layers
    place = _on_one_chip(v5e)
    parents = jax.jit(lambda table, ids: table.astype(jnp.bfloat16)[ids]).lower(
        place((cfg.vocab_size, cfg.dim), jnp.float32), place((1, 32), jnp.int32)).compile().as_text()
    assert len(_table_converts(parents, cfg)) == 1
    accessed = {name: encoder_programs[name][1].cost_analysis()["bytes accessed"]
                for name in ("float32-leaves", "held-shallow")}
    assert accessed["held-shallow"] < 0.52 * accessed["float32-leaves"], accessed


@pytest.fixture(scope="module")
def decoder_lowered(v5e):
    """The dense family's decode step and prefill segment at the mistral cell's
    widths, LOWERED under ``L.embed`` as it is and under the parent's order."""
    from sentio_tpu.models import layers

    ours = _decoder_programs(v5e, "mistral", True, compiled=False)[1]
    was, layers.embed = layers.embed, lambda params, ids, dtype=jnp.bfloat16: params["embedding"].astype(dtype)[ids]
    try:
        parents = _decoder_programs(v5e, "mistral", True, compiled=False)[1]
    finally:
        layers.embed = was
    return ours, parents


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_decoder_programs_lower_to_the_parents_text(decoder_lowered, program):
    """Every decoder's table is held in the compute dtype already: ``astype``
    of it emits nothing whichever side of the gather it stands, and the
    programs are the parent's, text for text."""
    ours, parents = decoder_lowered
    assert "gather" in ours[program] and ours[program] == parents[program]
