"""The tree the engine serves (models/llama.py ``serving_layout``).

A checkpoint, the initialisers and ``models/convert.py`` hold ``attn.wq``,
``wk``, ``wv`` as ``[in, out]``; the engine stores them, once, ``[out, in]``
(``wq_t``, ``wk_t``, ``wv_t``), the order its compiled programs read them
in. The bar: that is a LAYOUT. An engine handed a canonical tree emits exactly what greedy decoding
with no cache at all (conftest's ``CacheFreeGreedy``) emits from the
canonical tree, both forwards give equal logits for either tree, the
conversion is idempotent and leaves no second copy of a weight behind, and
what is written to a checkpoint stays canonical. What the conversion buys —
no weight transposed at the head of a call — is pinned for the chip's
compiler in tests/test_chip_compile.py.
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import CacheFreeGreedy
from sentio_tpu.models.llama import (
    LlamaConfig,
    init_llama,
    llama_forward,
    serving_layout,
)
from sentio_tpu.models.moe import MoeConfig, init_moe, moe_serving_forward
from sentio_tpu.runtime.paged import (
    ContinuousBatchingEngine,
    init_pool,
    paged_decode_forward,
)

CANONICAL = {"wq", "wk", "wv", "wo"}
SERVED = {"wq_t", "wk_t", "wv_t", "wo"}
PROMPTS = ["the weights lie where they are read", "second row, other length"]
GEOMETRY = dict(max_slots=4, page_size=16, max_pages_per_seq=8, steps_per_tick=4)


def _family(name: str):
    """→ (config, canonical tree, cache-free forward). Ample expert capacity
    makes routing independent of the tokens a call holds, so the routed
    family too is comparable token for token (tests/test_moe.py)."""
    if name == "moe":
        cfg = replace(MoeConfig.tiny(), capacity_factor=8.0)
        return cfg, init_moe(jax.random.PRNGKey(0), cfg), moe_serving_forward
    cfg = LlamaConfig.tiny()
    return cfg, init_llama(jax.random.PRNGKey(0), cfg), llama_forward


@pytest.fixture(scope="module")
def families():
    made = {}

    def get(name):
        if name not in made:
            cfg, params, forward = _family(name)
            made[name] = (cfg, params, forward, CacheFreeGreedy(cfg, params=params))
        return made[name]

    return get


def _attn_leaves(tree) -> set:
    return {key for lp in tree.values() if isinstance(lp, dict) and "attn" in lp
            for key in lp["attn"]}


@pytest.mark.parametrize("prefill_chunk", [None, 16], ids=["whole", "chunked"])
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_engine_from_canonical_tree_matches_cache_free_oracle(
        families, family, kv_quant, prefill_chunk):
    cfg, params, _, oracle = families(family)
    assert _attn_leaves(params) == CANONICAL
    engine = ContinuousBatchingEngine(
        model_config=cfg, params=params, kv_quant=kv_quant,
        prefill_chunk=prefill_chunk, **GEOMETRY)
    assert _attn_leaves(engine.params) == SERVED
    got = engine.run_all(PROMPTS, max_new_tokens=8, temperature=0.0)
    want = oracle.generate(PROMPTS, max_new_tokens=8, temperature=0.0)
    if kv_quant == "none":
        assert [r.tokens for r in got] == [r.tokens for r in want]
        return
    # int8 pages round what they hold: the least-accumulated position is
    # held to the oracle (tests/test_kv_quant.py), the whole answer to an
    # engine handed the tree already turned
    assert [r.tokens[:1] for r in got] == [r.tokens[:1] for r in want]
    handed = ContinuousBatchingEngine(
        model_config=cfg, params=engine.params, kv_quant=kv_quant,
        prefill_chunk=prefill_chunk, **GEOMETRY)
    assert handed.params is engine.params
    again = handed.run_all(PROMPTS, max_new_tokens=8, temperature=0.0)
    assert [r.tokens for r in again] == [r.tokens for r in got]


@pytest.mark.parametrize("forward", ["prefill", "cache_free", "paged_decode"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_forwards_give_equal_logits_for_either_tree(families, family, forward):
    """One seed, both trees: every output column is the same bf16 products
    summed in fp32 over the same terms, so the logits are EQUAL."""
    cfg, params, fwd, _ = families(family)
    turned = serving_layout(params)
    ids = jnp.asarray(np.random.default_rng(0).integers(3, cfg.vocab_size, (2, 24)), jnp.int32)

    def run(tree):
        if forward == "cache_free":
            return fwd(tree, cfg, ids)[0]
        if forward == "prefill":
            from sentio_tpu.models.llama import init_cache

            return fwd(tree, cfg, ids, cache=init_cache(cfg, 2, 32), cache_index=0)[0]
        pool = init_pool(cfg, 9, 16)
        table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        return paged_decode_forward(
            tree, cfg, ids[:, 0], jnp.asarray([5, 37], jnp.int32), table, pool.k, pool.v)[0]

    np.testing.assert_array_equal(np.asarray(run(params)), np.asarray(run(turned)))


@pytest.mark.parametrize("where", ["device", "host"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_conversion_is_idempotent_and_keeps_one_copy(families, family, where):
    cfg, params, _, _ = families(family)
    if where == "host":
        params = jax.device_get(params)
    turned = serving_layout(params)
    assert serving_layout(turned) is turned  # nothing to turn: the same object
    assert _attn_leaves(turned) == SERVED
    assert _attn_leaves(params) == CANONICAL  # the caller's tree is not edited
    kernel = turned["layers_0"]["attn"]["wk_t"]["kernel"]
    assert isinstance(kernel, np.ndarray if where == "host" else jax.Array)
    assert kernel.shape == (cfg.n_kv_heads * cfg.head_dim, cfg.dim)
    if where == "host":
        assert kernel.flags.c_contiguous  # turned in memory, not a view of the caller's
    np.testing.assert_array_equal(
        np.asarray(kernel), np.asarray(params["layers_0"]["attn"]["wk"]["kernel"]).T)
    # every other leaf is the caller's own array, not a copy of it
    assert turned["layers_0"]["attn"]["wo"]["kernel"] is params["layers_0"]["attn"]["wo"]["kernel"]
    assert turned["lm_head"] is params["lm_head"]


def test_no_canonical_projection_stays_alive_on_the_device():
    """An engine that builds its own weights, and one handed a canonical tree
    its caller lets go of, keep ONE copy of k and v on the device, stored
    [out, in]."""
    cfg = LlamaConfig.tiny()
    kv = cfg.n_kv_heads * cfg.head_dim

    def projections():
        return sorted(a.shape for a in jax.live_arrays() if a.shape in {(cfg.dim, kv), (kv, cfg.dim)})

    before = projections()
    engine = ContinuousBatchingEngine(model_config=cfg, **GEOMETRY)
    handed = ContinuousBatchingEngine(
        model_config=cfg, params=init_llama(jax.random.PRNGKey(1), cfg), **GEOMETRY)
    made = projections()
    for shape in before:
        made.remove(shape)
    assert made == 2 * 2 * cfg.n_layers * [(kv, cfg.dim)]  # wk_t, wv_t; two engines
    spawned = engine.spawn_fresh()
    assert spawned.params is engine.params and handed.params is not engine.params


def test_load_decoder_places_the_serving_tree_and_checkpoints_stay_canonical(tmp_path):
    """``cli convert`` / ``save_pytree`` write the canonical names; the loader
    turns them on the host before placement; serving from the checkpoint edits
    neither the file nor what a second load of it holds."""
    from sentio_tpu.config import GeneratorConfig
    from sentio_tpu.runtime.checkpoint import load_pytree, save_pytree
    from sentio_tpu.runtime.weights import load_decoder

    cfg = LlamaConfig.tiny()
    canonical = init_llama(jax.random.PRNGKey(2), cfg)
    ck = str(tmp_path / "ck")
    save_pytree(ck, canonical, meta={"family": "llama", "config": cfg.__dict__})
    decoder = load_decoder(GeneratorConfig(checkpoint_path=ck))
    assert _attn_leaves(decoder.params) == SERVED
    engine = ContinuousBatchingEngine(
        model_config=decoder.model_config, params=decoder.params, **GEOMETRY)
    assert engine.params is decoder.params  # made once, by the loader
    got = engine.run_all(PROMPTS, max_new_tokens=6, temperature=0.0)
    want = CacheFreeGreedy(cfg, params=canonical).generate(
        PROMPTS, max_new_tokens=6, temperature=0.0)
    assert [r.tokens for r in got] == [r.tokens for r in want]
    on_disk, _ = load_pytree(ck)
    assert _attn_leaves(on_disk) == CANONICAL
    np.testing.assert_array_equal(
        np.asarray(on_disk["layers_1"]["attn"]["wk"]["kernel"]),
        np.asarray(canonical["layers_1"]["attn"]["wk"]["kernel"]))


@pytest.mark.parametrize("tp", [2, 4])
def test_turned_leaves_split_their_rows_under_tp(tp):
    """``LLAMA_TP_RULES`` split q, k and v by output features: columns of a
    canonical leaf, rows of a turned one. A mesh engine handed a canonical,
    placed tree turns it on the devices, each keeping its own heads, and
    serves what the oracle does."""
    from sentio_tpu.config import MeshConfig
    from sentio_tpu.parallel.mesh import AXIS_TP, build_mesh
    from sentio_tpu.parallel.sharding import LLAMA_TP_RULES, shard_params, spec_for

    cfg = replace(LlamaConfig.tiny(), n_heads=8, n_kv_heads=4)
    canonical = init_llama(jax.random.PRNGKey(4), cfg)
    mesh = build_mesh(MeshConfig(dp_size=8 // tp, tp_size=tp))
    placed = shard_params(canonical, mesh, LLAMA_TP_RULES)
    engine = ContinuousBatchingEngine(
        model_config=cfg, params=placed, mesh=mesh, **GEOMETRY)
    rows = jax.sharding.PartitionSpec(AXIS_TP, None)
    assert spec_for("layers_0/attn/wk_t/kernel", LLAMA_TP_RULES, 2) == rows
    kernel = engine.params["layers_0"]["attn"]["wk_t"]["kernel"]
    assert kernel.sharding.is_equivalent_to(jax.sharding.NamedSharding(mesh, rows), 2)
    # device 0 holds the first kv heads' rows: the columns it held before
    share = np.asarray(kernel.addressable_shards[0].data)
    held = cfg.n_kv_heads * cfg.head_dim // tp
    np.testing.assert_array_equal(
        share, np.asarray(canonical["layers_0"]["attn"]["wk"]["kernel"])[:, :held].T)
    got = engine.run_all(PROMPTS, max_new_tokens=6, temperature=0.0)
    want = CacheFreeGreedy(cfg, params=canonical).generate(
        PROMPTS, max_new_tokens=6, temperature=0.0)
    assert [r.tokens for r in got] == [r.tokens for r in want]
