"""The decode step's page write as a kernel (kernels/page_write.py): K and V
rows of one token written into the pool by a DMA a row, the pool aliased to the
output — against the XLA scatter it replaces where ``page_write_path`` says so,
on the CPU in interpret mode. What the chip's compiler makes of it (no pool
moved into nearer memory and back) is tests/test_chip_compile.py's.

Rows that do not advance all write the scratch page's position 0, on each
other in no order, so that one position is compared apart: it holds ONE of
their vectors.
"""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from sentio_tpu.kernels.page_write import make_page_write_impl, page_write, page_write_path
from sentio_tpu.models.lfm2_moe import Lfm2MoeConfig, init_lfm2_moe
from sentio_tpu.models.llama import LlamaConfig, init_llama
from sentio_tpu.models.nemotron_h import NemotronHConfig, init_nemotron_h
from sentio_tpu.runtime.paged import ContinuousBatchingEngine, _page_write, init_pool, paged_decode_forward

REPO = Path(__file__).resolve().parents[1]
LAYERS, PAGES, PAGE = 3, 12, 16

# what a step's rows may name: (page ids, offsets). A slot's pages are its own;
# halted rows share (0, 0)
ROWS = {
    "distinct-pages": ([3, 7, 1, 11, 5, 9], [4, 0, 9, 2, 13, 6]),
    "halted-rows-on-page-0": ([0, 6, 0, 2, 0, 0], [0, 5, 0, 11, 0, 0]),
    "first-and-last-position": ([2, 4, 8, 10, 1, 3], [0, PAGE - 1, 0, PAGE - 1, PAGE - 1, 0]),
}
# (Hkv, D) of the model and how many heads share a row of the pool
POOLS = {"plain": (4, 128, 1), "lane-packed": (8, 64, 2)}


@pytest.mark.parametrize("layer", [0, LAYERS - 1], ids=["layer-0", "last-layer"])
@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_the_kernel_writes_what_the_scatter_writes(pool, rows, layer):
    hkv, d, pack = POOLS[pool]
    rng = np.random.default_rng(len(rows) + layer)
    shape = (LAYERS, PAGES, PAGE, hkv // pack, d * pack)
    k_pages, v_pages = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16) for _ in range(2))
    ids, offsets = (jnp.asarray(a, jnp.int32) for a in ROWS[rows])
    k, v = (jnp.asarray(rng.standard_normal((len(ROWS[rows][0]), hkv, d)), jnp.bfloat16) for _ in range(2))
    # the layer TRACED, as a decode program's scan over layers hands it over
    write = jax.jit(make_page_write_impl(interpret=True))
    got = write(k_pages, v_pages, jnp.int32(layer), ids, offsets, k, v)
    halted = np.asarray(ids) == 0
    for made, before, val in zip(got, (k_pages, v_pages), (k, v)):
        want = np.array(_page_write(before, layer, ids, offsets, val).astype(jnp.float32))
        made = np.array(made.astype(jnp.float32))
        if halted.any():   # the one position they share holds one of THEIR vectors, whole
            theirs = np.asarray(val.astype(jnp.float32)).reshape(len(halted), *shape[-2:])[halted]
            assert any(np.array_equal(made[layer, 0, 0], row) for row in theirs)
            made[layer, 0, 0] = want[layer, 0, 0] = 0
        assert np.array_equal(made, want)   # every other layer, page and position as it was


def test_rows_of_another_shape_or_dtype_are_refused():
    pool = jnp.zeros((1, 2, PAGE, 4, 128), jnp.bfloat16)
    ids = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="page write: rows of"):
        page_write((pool,), 0, ids, ids, (jnp.zeros((2, 8, 64), jnp.bfloat16),), interpret=True)
    with pytest.raises(ValueError, match="page write: rows of"):
        page_write((pool,), 0, ids, ids, (jnp.zeros((2, 4, 128), jnp.float32),), interpret=True)


# ------------------------------------------------ a decode step, both writes


def conv_step():
    """A family with convolution state (``models/lfm2_moe.py``): 8 kv heads of 64 in a lane-packed pool, three
    rows of which the second is halted."""
    cfg = dataclasses.replace(Lfm2MoeConfig.tiny(), dim=512, n_heads=8, n_kv_heads=8)
    tree = init_lfm2_moe(jax.random.PRNGKey(0), cfg)
    pool = init_pool(cfg, num_pages=8, page_size=PAGE, slots=3, pack=2)
    assert pool.k.shape == (1, 8, PAGE, 4, 128)
    return cfg, tree, pool, {"conv": pool.conv, "tail": pool.tail}


def ssm_step():
    """A family with Mamba state (``models/nemotron_h.py``): 2 kv heads of 128, the Mamba state beside them."""
    cfg = dataclasses.replace(NemotronHConfig.tiny(), head_dim=128)
    tree = init_nemotron_h(jax.random.PRNGKey(0), cfg)
    pool = init_pool(cfg, num_pages=8, page_size=PAGE, slots=3, snapshots=1)
    assert pool.k.shape == (1, 8, PAGE, 2, 128)
    return cfg, tree, pool, {"conv": pool.conv}


@pytest.mark.parametrize("family", [conv_step, ssm_step], ids=["conv-state-lane-packed", "ssm-state"])
def test_a_decode_step_with_the_kernel_is_the_step_with_the_scatter(family):
    """Two decode steps in a row (the second reads what the first wrote), one
    row halted: logits, both pools and the carried state equal bit for bit."""
    cfg, tree, pool, state = family()
    table = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    mask = jnp.asarray([True, False, True])
    fill = jax.random.normal(jax.random.PRNGKey(1), pool.k.shape).astype(pool.k.dtype)

    def two_steps(write_impl):
        step = jax.jit(functools.partial(paged_decode_forward, return_routed=True, write_impl=write_impl),
                       static_argnums=1)
        k_pages, v_pages, carried = fill, -fill, dict(state)
        out = []
        for tok, lens in (([3, 4, 5], [5, PAGE - 1, 6]), ([6, 7, 8], [6, PAGE - 1, 7])):
            logits, k_pages, v_pages, _routed, conv, tail = step(
                tree, cfg, jnp.asarray(tok), jnp.asarray(lens), table, k_pages, v_pages,
                write_mask=mask, **carried)
            carried = {name: new for name, new in (("conv", conv), ("tail", tail)) if name in carried}
            out.append(logits)
        return out, k_pages, v_pages, carried

    kernel, scatter = two_steps(make_page_write_impl(interpret=True)), two_steps(None)
    # the halted row's write went to the scratch page's first position under both
    leaves = [jax.tree_util.tree_leaves(side) for side in (kernel, scatter)]
    assert len(leaves[0]) == len(leaves[1]) > 4
    for got, want in zip(*leaves):
        assert got.dtype == want.dtype and np.array_equal(np.asarray(got.astype(jnp.float32)),
                                                          np.asarray(want.astype(jnp.float32)))
    # and the advancing rows' vectors are where the attention reads them
    assert not np.array_equal(np.asarray(kernel[1][0, 1, 5].astype(jnp.float32)),
                              np.asarray(fill[0, 1, 5].astype(jnp.float32)))


# ------------------------------------------------------------------ the rule


def _cell_pool(config: str, traffic: str | None = None):
    """One array of K (or the latent pool) as the benchmark's cell makes it:
    the configuration's widths, its ``serve_env``'s slots and pages under the
    traffic mix's."""
    model = json.loads((REPO / "benchmark" / "configs" / f"{config}.json").read_text())
    env = dict(model["serve_env"])
    if traffic:
        env.update({k: v for k, v in json.loads(
            (REPO / "benchmark" / "traffic" / f"{traffic}.json").read_text())["serve_env"].items() if k in env})
    pages = 1 + int(env["LLM_MAX_BATCH"]) * int(env["KV_MAX_PAGES_PER_SEQ"])
    page = int(env["KV_PAGE_SIZE"])
    if "kv_lora_rank" in model:
        return jax.ShapeDtypeStruct(
            (model["num_hidden_layers"], pages, model["kv_lora_rank"] + model["qk_rope_head_dim"], page), jnp.bfloat16)
    if "layer_types" in model and model["family"] == "lfm2_moe":
        layers = model["layer_types"].count("full_attention")
    else:
        layers = model.get("hybrid_override_pattern", "*" * model["num_hidden_layers"]).count("*")
    hkv, d = model["num_key_value_heads"], model.get("head_dim", model["hidden_size"] // model["num_attention_heads"])
    pack = 128 // d if d < 128 else 1
    return jax.ShapeDtypeStruct((layers, pages, page, hkv // pack, d * pack), jnp.bfloat16)


CELLS = {
    # config, traffic whose serve_env sizes the pool, the path, the array's shape
    "mistral7b-chat-closed": ("mistral-7b-v0.3-l16", None, "xla", (16, 289, 128, 8, 128)),
    "mistral7b-rag-long": ("mistral-7b-v0.3-l16", "rag-long", "xla", (16, 321, 128, 8, 128)),
    "yi6b-chat-closed": ("yi-1.5-6b-l16", None, "xla", (16, 321, 128, 4, 128)),
    "commanda-ep8-chat-closed-16": ("command-a-plus-ep8-l4", None, "xla", (4, 321, 128, 8, 128)),
    "dsv2-ep8-rag-long": ("deepseek-v2-ep8-l8", "rag-long", "xla", (8, 321, 576, 128)),
    "lfm2moe-chat-closed-16": ("lfm2-24b-a2b-l10", None, "pallas", (2, 161, 128, 4, 128)),
    "nemotron3-ep2-chat-closed-16": ("nemotron-3-nano-30b-a3b-ep2-l14", None, "pallas", (2, 161, 128, 2, 128)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_rule_on_the_benchmarks_pools(cell):
    """The six configurations' pools as their cells size them: the two whose K
    and V the compiler could place in nearer memory (42 and 21 MB an array)
    take the kernel; gigabytes of dense K and V, Command A+'s 337 MB and the
    latent pool keep the scatter."""
    config, traffic, path, shape = CELLS[cell]
    pool = _cell_pool(config, traffic)
    assert pool.shape == shape
    assert page_write_path(pool) == path


SMALL = (2, 161, 128, 4, 128)
# what keeps the scatter → (pool, mesh), built inside the test (a mesh needs a device)
SCATTER_KEPT = {
    "int8": lambda: ({"q": jax.ShapeDtypeStruct(SMALL, jnp.int8),
                      "s": jax.ShapeDtypeStruct((2, 161, 4, 128), jnp.bfloat16)}, None),
    "mesh": lambda: (jax.ShapeDtypeStruct(SMALL, jnp.bfloat16), Mesh(np.array(jax.devices()[:1]), ("tp",))),
    "float32": lambda: (jax.ShapeDtypeStruct(SMALL, jnp.float32), None),
    # half a 32-bit sublane: Mosaic refuses the slice
    "one-row": lambda: (jax.ShapeDtypeStruct((2, 161, 128, 1, 128), jnp.bfloat16), None),
    # 64-wide heads unpacked: no whole rows of lanes
    "narrow-rows": lambda: (jax.ShapeDtypeStruct((2, 161, 128, 8, 64), jnp.bfloat16), None),
    # 128 MiB exactly is not UNDER what nearer memory holds
    "at-the-limit": lambda: (jax.ShapeDtypeStruct((4, 128, 128, 8, 128), jnp.bfloat16), None),
}


@pytest.mark.parametrize("what", sorted(SCATTER_KEPT))
def test_the_rule_keeps_the_scatter_for(what):
    assert page_write_path(jax.ShapeDtypeStruct(SMALL, jnp.bfloat16)) == "pallas"
    assert page_write_path(jax.ShapeDtypeStruct((4, 127, 128, 8, 128), jnp.bfloat16)) == "pallas"   # a page under
    pool, mesh = SCATTER_KEPT[what]()
    assert page_write_path(pool, mesh) == "xla"


# ---------------------------------------------------------------- the engine


def test_the_engine_binds_the_kernel_by_the_rule_and_says_so(caplog):
    """Two kv heads of 128 in bf16, the kernels asked for: the engine writes by
    the kernel, says so in ``stats()`` and in its log, answers to the bit what
    it answers with the scatter, and a rebuilt engine chooses alike. In float32
    (no rule covers it) the same ask keeps the scatter."""
    import logging

    cfg = dataclasses.replace(LlamaConfig.tiny(), dim=256, n_heads=2, n_kv_heads=2)
    tree = init_llama(jax.random.PRNGKey(0), cfg)
    kw = dict(model_config=cfg, params=tree, max_slots=3, page_size=PAGE, max_pages_per_seq=4,
              steps_per_tick=4, ignore_eos=True)
    with caplog.at_level(logging.INFO, logger="sentio_tpu.runtime.paged"):
        kernel = ContinuousBatchingEngine(use_pallas=True, **kw)
    assert kernel.stats()["page_write"] == "pallas" and kernel.stats()["paged_attention"] == "pallas"
    assert "the page-write kernel, in place in HBM" in caplog.text
    assert ContinuousBatchingEngine(use_pallas=False, **kw).stats()["page_write"] == "xla"
    assert kernel.spawn_fresh().stats()["page_write"] == "pallas"
    # the same engine, walk and flash kernel alike, with the scatter in the write's place
    scatter = ContinuousBatchingEngine(use_pallas=True, **kw)
    scatter._write_impl = None
    scatter._build_fns()
    prompts = ["the first of three rows", "a second, longer than the first of the rows", "third"]
    got = kernel.run_all(prompts, max_new_tokens=10)
    want = scatter.run_all(prompts, max_new_tokens=10)
    assert [r.tokens for r in got] == [r.tokens for r in want] and all(len(r.tokens) == 10 for r in got)
    assert [r.logprob_sum for r in got] == [r.logprob_sum for r in want]

    wide = dataclasses.replace(cfg, dtype="float32")
    plain = ContinuousBatchingEngine(use_pallas=True, **{**kw, "model_config": wide})
    assert plain.stats()["paged_attention"] == "pallas" and plain.stats()["page_write"] == "xla"
