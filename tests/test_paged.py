"""Paged KV cache + continuous batching (runtime/paged.py).

The correctness bar: a paged, continuously-batched greedy decode must emit
EXACTLY the tokens that greedy decoding with no cache at all (conftest's
``CacheFreeGreedy``) emits for the same params — paging is a memory layout,
not a model change.
"""

import numpy as np
import pytest

from conftest import CacheFreeGreedy
from sentio_tpu.models.llama import LlamaConfig
from sentio_tpu.runtime.paged import (
    ContinuousBatchingEngine,
    PageAllocator,
    init_pool,
)

@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny()


@pytest.fixture(scope="module")
def oracle(cfg):
    return CacheFreeGreedy(cfg, rng_seed=0)


@pytest.fixture(scope="module")
def paged(cfg, oracle):
    # share the exact same params so greedy outputs are comparable
    return ContinuousBatchingEngine(
        model_config=cfg,
        params=oracle.params,
        tokenizer=oracle.tokenizer,
        max_slots=4,
        page_size=16,
        max_pages_per_seq=8,
    )


class TestAllocator:
    def test_alloc_free_roundtrip(self):
        a = PageAllocator(9)
        assert a.free_pages == 8
        pages = a.alloc(5)
        assert len(set(pages)) == 5 and 0 not in pages
        a.free(pages)
        assert a.free_pages == 8

    def test_exhaustion_raises(self):
        a = PageAllocator(4)
        a.alloc(3)
        with pytest.raises(MemoryError):
            a.alloc(1)

    def test_scratch_never_freed_into_pool(self):
        a = PageAllocator(4)
        a.free([0, 0])
        assert a.free_pages == 3


class TestPool:
    def test_shapes(self, cfg):
        pool = init_pool(cfg, num_pages=5, page_size=8)
        assert pool.k.shape == (cfg.n_layers, 5, 8, cfg.n_kv_heads, cfg.head_dim)
        assert pool.num_pages == 5


class TestPagedMatchesCacheFree:
    def test_single_prompt_greedy(self, oracle, paged):
        prompt = "paged equivalence check"
        ref = oracle.generate([prompt], max_new_tokens=12, temperature=0.0)[0]
        got = paged.run_all([prompt], max_new_tokens=12, temperature=0.0)[0]
        assert got.tokens == ref.tokens
        assert got.text == ref.text
        assert got.finish_reason == ref.finish_reason

    def test_mixed_length_batch_greedy(self, oracle, paged):
        prompts = ["a", "a much longer prompt that spans several pages of cache " * 2, "mid size"]
        refs = [oracle.generate([p], max_new_tokens=10, temperature=0.0)[0] for p in prompts]
        got = paged.run_all(prompts, max_new_tokens=10, temperature=0.0)
        for r, g in zip(refs, got):
            assert g.tokens == r.tokens

    def test_pages_reclaimed_after_drain(self, paged):
        before = paged.allocator.free_pages
        paged.run_all(["reclaim one", "reclaim two"], max_new_tokens=6)
        assert paged.allocator.free_pages == before
        assert all(not s.active for s in paged.slots)


class TestContinuousAdmission:
    def test_staggered_arrivals_match_isolated_runs(self, oracle, paged):
        """Requests joining mid-flight must not perturb rows already decoding."""
        early = "first request decoding"
        late = "latecomer joins the batch"
        ref_early = oracle.generate([early], max_new_tokens=12, temperature=0.0)[0]
        ref_late = oracle.generate([late], max_new_tokens=12, temperature=0.0)[0]

        rid_early = paged.submit(early, max_new_tokens=12, temperature=0.0)
        done = {}
        ticks = 0
        rid_late = None
        while paged.has_work or rid_late is None:
            if ticks == 3 and rid_late is None:
                rid_late = paged.submit(late, max_new_tokens=12, temperature=0.0)
            for r in paged.step():
                done[r.request_id] = r
            ticks += 1
            assert ticks < 200
        assert done[rid_early].tokens == ref_early.tokens
        assert done[rid_late].tokens == ref_late.tokens

    def test_more_requests_than_slots(self, paged):
        prompts = [f"queue pressure {i}" for i in range(9)]  # > max_slots=4
        results = paged.run_all(prompts, max_new_tokens=5)
        assert len(results) == 9
        assert all(len(r.tokens) <= 5 for r in results)
        assert all(not s.active for s in paged.slots)

    def test_stats_shape(self, paged):
        s = paged.stats()
        assert s["max_slots"] == 4
        assert s["active_slots"] == 0
        # idle engine: every page is either free or retained by the radix
        # prefix cache (plus the reserved scratch page)
        assert s["free_pages"] + s.get("prefix_cache_pages", 0) \
            == s["total_pages"] - 1


class TestPagedAttentionKernel:
    def test_kernel_matches_xla_gather(self, cfg):
        """Pallas page-table walk (interpret mode) ≡ XLA gather attention."""
        import jax
        import jax.numpy as jnp

        from sentio_tpu.kernels.paged_attention import paged_attention
        from sentio_tpu.runtime.paged import _paged_attn_xla

        rng = np.random.default_rng(0)
        b, h, hkv, d, page, num_pages, nb = 3, 4, 2, 16, 8, 13, 4
        layers, layer = 3, 2  # each layer its own values; the one read is not 0
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
        kp = jnp.asarray(
            rng.standard_normal((layers, num_pages, page, hkv, d)), jnp.float32)
        vp = jnp.asarray(
            rng.standard_normal((layers, num_pages, page, hkv, d)), jnp.float32)
        # each row owns a distinct shuffled set of pages; varied lengths
        table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]], jnp.int32)
        lens = jnp.asarray([5, 17, 30], jnp.int32)

        # the reference sees that layer alone, as a pool of one layer
        ref = _paged_attn_xla(
            q, kp[layer:layer + 1], vp[layer:layer + 1], 0, table, lens, h // hkv)[:, 0]
        got = paged_attention(q[:, 0], kp, vp, layer, table, lens, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)
        whole = _paged_attn_xla(q, kp, vp, layer, table, lens, h // hkv)[:, 0]
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(ref))

    @pytest.mark.parametrize("h, hkv, nb", [(32, 8, 18), (32, 4, 10), (8, 2, 18), (8, 1, 10)])
    def test_walk_of_held_pages_matches_xla_gather(self, h, hkv, nb):
        """The cells' head shapes (and a tp=4 device's share of them) with
        ``lens`` at 0, page − 1, page, page + 1 and a full table in one call,
        empty rows between full ones; cells past a row's length name a
        NaN-filled page that must never be read. (The int8 side of the same
        walk, and more edges: tests/test_paged_attn_quant.py.)"""
        import jax.numpy as jnp

        from sentio_tpu.kernels.paged_attention import paged_attention
        from sentio_tpu.runtime.paged import _paged_attn_xla

        rng = np.random.default_rng(h + hkv)
        b, d, page, layer = 7, 16, 8, 1
        nan_page = 1 + b * nb
        shape = (2, nan_page + 1, page, hkv, d)
        kp = jnp.asarray(rng.standard_normal(shape), jnp.float32).at[:, nan_page].set(jnp.nan)
        vp = jnp.asarray(rng.standard_normal(shape), jnp.float32).at[:, nan_page].set(jnp.nan)
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        lens = np.asarray([0, page - 1, page, 0, page + 1, nb * page - 1, 0], np.int32)
        own = 1 + np.arange(b * nb, dtype=np.int32).reshape(b, nb)
        held = np.arange(nb)[None, :] < (lens // page + 1)[:, None]
        got = paged_attention(q, kp, vp, layer, jnp.asarray(np.where(held, own, nan_page)),
                              jnp.asarray(lens), interpret=True)
        ref = _paged_attn_xla(q[:, None], kp, vp, layer, jnp.asarray(np.where(held, own, 0)),
                              jnp.asarray(lens), h // hkv)[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_engine_with_kernel_matches_cache_free(self, cfg, oracle):
        eng = ContinuousBatchingEngine(
            model_config=cfg, params=oracle.params, tokenizer=oracle.tokenizer,
            max_slots=2, page_size=16, max_pages_per_seq=8, use_pallas=True,
        )
        # the walk is bound; the page write keeps the scatter (16-wide heads are
        # no whole rows of lanes: kernels/page_write.py::page_write_path)
        assert eng.stats()["paged_attention"] == "pallas" and eng.stats()["page_write"] == "xla"
        prompt = "kernel path equivalence"
        ref = oracle.generate([prompt], max_new_tokens=8, temperature=0.0)[0]
        got = eng.run_all([prompt], max_new_tokens=8, temperature=0.0)[0]
        assert got.tokens == ref.tokens

    def test_int8_engine_with_kernel_churn_conserves_pages(self, cfg, oracle):
        """KV_QUANT=int8 + the quantization-native Pallas kernel (interpret
        on CPU) through an admission-churn workload, with the sanitizer
        (armed for this module) checking pool conservation on the dict-repr
        pool every tick."""
        eng = ContinuousBatchingEngine(
            model_config=cfg, params=oracle.params,
            tokenizer=oracle.tokenizer, max_slots=2, page_size=16,
            max_pages_per_seq=4, use_pallas=True, kv_quant="int8",
        )
        before = eng.allocator.free_pages + (
            eng._radix.pages_held if eng._radix is not None else 0)
        results = eng.run_all(
            [f"churn request {i} padding to cross pages" for i in range(5)],
            max_new_tokens=6, temperature=0.0,
        )
        assert len(results) == 5
        assert all(r.finish_reason in ("stop", "length") for r in results)
        after = eng.allocator.free_pages + (
            eng._radix.pages_held if eng._radix is not None else 0)
        assert after == before
        assert all(not s.active for s in eng.slots)


class TestBudgets:
    def test_length_budget_respected(self, paged):
        r = paged.run_all(["short budget"], max_new_tokens=3)[0]
        assert len(r.tokens) <= 3

    def test_per_row_temperatures(self, cfg, oracle):
        """Greedy and hot rows coexist in one batch; greedy row stays exact."""
        eng = ContinuousBatchingEngine(
            model_config=cfg, params=oracle.params, tokenizer=oracle.tokenizer,
            max_slots=2, page_size=16, max_pages_per_seq=8, rng_seed=7,
        )
        ref = oracle.generate(["cold row"], max_new_tokens=8, temperature=0.0)[0]
        rid_cold = eng.submit("cold row", max_new_tokens=8, temperature=0.0)
        eng.submit("hot row", max_new_tokens=8, temperature=1.5)
        done = {}
        while eng.has_work:
            for r in eng.step():
                done[r.request_id] = r
        assert done[rid_cold].tokens == ref.tokens


class TestMultiStepTick:
    def test_steps_per_tick_greedy_equivalence(self, cfg, oracle):
        """Fusing N decode sub-steps into one dispatch is a scheduling
        change, not a model change: greedy tokens must be bit-identical."""
        prompts = ["alpha prompt", "a", "gamma prompt with a longer tail of text"]
        outs = {}
        for steps in (1, 4, 8):
            eng = ContinuousBatchingEngine(
                model_config=cfg, params=oracle.params,
                tokenizer=oracle.tokenizer, max_slots=4, page_size=16,
                max_pages_per_seq=8, steps_per_tick=steps,
            )
            outs[steps] = [
                r.tokens for r in eng.run_all(prompts, max_new_tokens=20, temperature=0.0)
            ]
        assert outs[1] == outs[4] == outs[8]

    def test_fewer_ticks_with_fused_steps(self, cfg, oracle):
        def count_ticks(steps):
            eng = ContinuousBatchingEngine(
                model_config=cfg, params=oracle.params,
                tokenizer=oracle.tokenizer, max_slots=2, page_size=16,
                max_pages_per_seq=8, steps_per_tick=steps,
            )
            eng.submit("count the ticks", max_new_tokens=16, temperature=0.0)
            ticks = 0
            while eng.has_work:
                eng.step()
                ticks += 1
                assert ticks < 100
            return ticks

        assert count_ticks(8) <= (count_ticks(1) + 7) // 8 + 1


class TestBatchedAdmission:
    def test_admit_scatter_fault_point_armed(self, paged):
        """The ``paged.admit_scatter`` chaos seam is live: a benign delay
        rule armed at the prefill-scatter dispatch must be hit during
        admission without disturbing the decode output."""
        from sentio_tpu.infra import faults

        faults.reset()
        try:
            with faults.inject("paged.admit_scatter", delay_s=0.01) as rule:
                out = paged.run_all(["fault point probe"],
                                    max_new_tokens=4, temperature=0.0)
            assert rule.hits >= 1
            assert out[0].tokens
        finally:
            faults.reset()

    def test_burst_admission_dispatch_count(self, cfg, oracle):
        """Admitting N same-width-bucket requests must cost at most
        ceil(N / max_batch_bucket) prefill dispatches, not N."""
        eng = ContinuousBatchingEngine(
            model_config=cfg, params=oracle.params,
            tokenizer=oracle.tokenizer, max_slots=8, page_size=16,
            max_pages_per_seq=8,
        )
        calls = []
        real = eng._prefill_scatter

        def counting(*args, **kwargs):
            calls.append(args[1].shape)  # ids [rows, width]
            return real(*args, **kwargs)

        eng._prefill_scatter = counting
        n = 6  # same width bucket
        rids = [
            eng.submit(f"burst request {i}", max_new_tokens=4, temperature=0.0)
            for i in range(n)
        ]
        done = {r.request_id: r for r in eng.step()}  # one tick admits the burst
        max_bucket = max(eng.ADMIT_BUCKETS)
        assert len(calls) <= -(-n // max_bucket), calls
        # and the admitted rows decode to the same greedy tokens as isolated runs
        while eng.has_work:
            for r in eng.step():
                done[r.request_id] = r
        assert set(done) == set(rids)
        ref = oracle.generate(["burst request 0"], max_new_tokens=4, temperature=0.0)[0]
        assert done[rids[0]].tokens == ref.tokens

    def test_mixed_width_burst_groups_by_bucket(self, cfg, oracle):
        eng = ContinuousBatchingEngine(
            model_config=cfg, params=oracle.params,
            tokenizer=oracle.tokenizer, max_slots=8, page_size=16,
            max_pages_per_seq=8,
        )
        calls = []
        real = eng._prefill_scatter

        def counting(*args, **kwargs):
            calls.append(args[1].shape)
            return real(*args, **kwargs)

        eng._prefill_scatter = counting
        eng.submit("short", max_new_tokens=2, temperature=0.0)
        eng.submit("x" * 60, max_new_tokens=2, temperature=0.0)  # wider bucket
        eng.submit("tiny", max_new_tokens=2, temperature=0.0)
        eng.step()
        widths = sorted(shape[1] for shape in calls)
        assert len(calls) == 2  # two width groups, one dispatch each
        assert widths[0] < widths[1]


class TestMeshShardedEngine:
    def test_tp_sharded_pool_matches_single_device(self, cfg, oracle):
        import jax

        from sentio_tpu.config import MeshConfig
        from sentio_tpu.parallel.mesh import build_mesh
        from sentio_tpu.parallel.sharding import LLAMA_TP_RULES, shard_params

        mesh = build_mesh(MeshConfig(dp_size=4, tp_size=2))
        params = shard_params(oracle.params, mesh, LLAMA_TP_RULES)
        eng = ContinuousBatchingEngine(
            model_config=cfg, params=params, tokenizer=oracle.tokenizer,
            mesh=mesh, max_slots=4, page_size=16, max_pages_per_seq=8,
            steps_per_tick=4,
        )
        from sentio_tpu.parallel.mesh import AXIS_TP

        assert eng.pool.k.sharding.spec == jax.sharding.PartitionSpec(
            None, None, None, AXIS_TP, None
        )
        prompts = ["mesh request one", "mesh request two"]
        got = eng.run_all(prompts, max_new_tokens=8, temperature=0.0)
        ref = oracle.generate(prompts, max_new_tokens=8, temperature=0.0)
        assert [r.tokens for r in got] == [r.tokens for r in ref]

    def test_kv_heads_not_divisible_by_tp_raises(self, cfg, oracle):
        from sentio_tpu.config import MeshConfig
        from sentio_tpu.parallel.mesh import build_mesh

        mesh = build_mesh(MeshConfig(dp_size=1, sp_size=2, tp_size=4))
        with pytest.raises(ValueError, match="n_kv_heads"):
            ContinuousBatchingEngine(
                model_config=cfg, params=oracle.params,
                tokenizer=oracle.tokenizer, mesh=mesh, max_slots=2,
            )

    def test_reset_preserves_pool_sharding(self, cfg, oracle):
        from sentio_tpu.config import MeshConfig
        from sentio_tpu.parallel.mesh import AXIS_TP, build_mesh
        from sentio_tpu.parallel.sharding import LLAMA_TP_RULES, shard_params

        mesh = build_mesh(MeshConfig(dp_size=4, tp_size=2))
        params = shard_params(oracle.params, mesh, LLAMA_TP_RULES)
        eng = ContinuousBatchingEngine(
            model_config=cfg, params=params, tokenizer=oracle.tokenizer,
            mesh=mesh, max_slots=2, page_size=16, max_pages_per_seq=8,
        )
        eng.reset()
        assert AXIS_TP in str(eng.pool.k.sharding.spec)
        assert eng.run_all(["after reset"], max_new_tokens=4)[0].finish_reason


class TestPipelinedTicks:
    """pipeline_depth=2 dispatches tick N+1 before fetching tick N — a pure
    scheduling change: greedy outputs must be bit-identical to depth 1,
    including under heavy slot churn and staggered admissions."""

    def _run(self, oracle, cfg, depth, prompts, max_new, slots=4,
             steps=4, max_tick=8):
        eng = ContinuousBatchingEngine(
            model_config=cfg, params=oracle.params,
            tokenizer=oracle.tokenizer, max_slots=slots, page_size=16,
            max_pages_per_seq=8, steps_per_tick=steps, max_tick_steps=max_tick,
            pipeline_depth=depth,
        )
        return [r.tokens for r in eng.run_all(prompts, max_new_tokens=max_new,
                                              temperature=0.0)]

    def test_greedy_equivalence(self, cfg, oracle):
        prompts = ["alpha prompt", "a", "third prompt with a longer tail of text"]
        a = self._run(oracle, cfg, 1, prompts, 20)
        b = self._run(oracle, cfg, 2, prompts, 20)
        assert a == b

    def test_slot_churn_equivalence(self, cfg, oracle):
        # 10 short requests through 2 slots: constant retire + reuse while a
        # speculative tick is in flight — exercises the stale-lane guard
        prompts = [f"churn request {i}" for i in range(10)]
        a = self._run(oracle, cfg, 1, prompts, 5, slots=2)
        b = self._run(oracle, cfg, 2, prompts, 5, slots=2)
        assert a == b

    def test_staggered_equivalence(self, cfg, oracle):
        def staggered(depth):
            eng = ContinuousBatchingEngine(
                model_config=cfg, params=oracle.params,
                tokenizer=oracle.tokenizer, max_slots=4, page_size=16,
                max_pages_per_seq=8, steps_per_tick=4, pipeline_depth=depth,
            )
            rid_a = eng.submit("early request", max_new_tokens=16, temperature=0.0)
            done, ticks, rid_b = {}, 0, None
            while eng.has_work or rid_b is None:
                if ticks == 2 and rid_b is None:
                    rid_b = eng.submit("latecomer request", max_new_tokens=10,
                                       temperature=0.0)
                for r in eng.step():
                    done[r.request_id] = r
                ticks += 1
                assert ticks < 300
            return done[rid_a].tokens, done[rid_b].tokens

        assert staggered(1) == staggered(2)

    def test_varied_max_new_equivalence(self, cfg, oracle):
        def run(depth):
            eng = ContinuousBatchingEngine(
                model_config=cfg, params=oracle.params,
                tokenizer=oracle.tokenizer, max_slots=4, page_size=16,
                max_pages_per_seq=8, steps_per_tick=4, max_tick_steps=16,
                pipeline_depth=depth,
            )
            rids = [eng.submit(f"varied {i}", max_new_tokens=n, temperature=0.0)
                    for i, n in enumerate([1, 7, 23, 4, 16])]
            done = {}
            ticks = 0
            while eng.has_work:
                for r in eng.step():
                    done[r.request_id] = r
                ticks += 1
                assert ticks < 300
            return [done[r].tokens for r in rids]

        assert run(1) == run(2)


class TestSingleTokenBurst:
    def test_max_new_one_burst_no_scan(self, cfg, oracle):
        """max_new=1 bursts fold deferred first tokens with a direct fetch —
        no masked decode scan — and still match the oracle."""
        for depth in (1, 2):
            eng = ContinuousBatchingEngine(
                model_config=cfg, params=oracle.params,
                tokenizer=oracle.tokenizer, max_slots=4, page_size=16,
                max_pages_per_seq=8, steps_per_tick=4, pipeline_depth=depth,
            )
            prompts = [f"one token {i}" for i in range(6)]
            sub_steps_before = eng.total_sub_steps
            got = eng.run_all(prompts, max_new_tokens=1, temperature=0.0)
            assert eng.total_sub_steps == sub_steps_before, "no scan should run"
            refs = [
                oracle.generate([p], max_new_tokens=1, temperature=0.0)[0]
                for p in prompts
            ]
            assert [r.tokens for r in got] == [r.tokens for r in refs]
