"""int8 KV page quantization (runtime/paged.py): numeric fidelity of the
quantize/dequantize pair, attention parity against bf16 pages, engine
end-to-end behavior, and the halved-footprint claim."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentio_tpu.models.llama import LlamaConfig
from sentio_tpu.runtime.paged import (
    ContinuousBatchingEngine,
    _gather_pages,
    _page_write,
    _paged_attn_xla,
    dequantize_kv,
    init_pool,
    quantize_kv,
)

class TestQuantPair:
    def test_roundtrip_error_small(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 16, 8, 64)), jnp.float32)
        q, s = quantize_kv(x)
        back = dequantize_kv(q, s, jnp.float32)
        rel = float(jnp.abs(back - x).max() / jnp.abs(x).max())
        assert rel < 0.01  # absmax int8: <= 1/254 of the vector range

    def test_zero_vectors_stay_zero(self):
        q, s = quantize_kv(jnp.zeros((3, 8)))
        assert float(jnp.abs(dequantize_kv(q, s, jnp.float32)).max()) == 0.0

    def test_int8_pool_halves_kv_bytes(self):
        cfg = LlamaConfig.tiny()
        bf16 = init_pool(cfg, num_pages=33, page_size=16)
        i8 = init_pool(cfg, num_pages=33, page_size=16, quantized=True)
        bf16_bytes = bf16.k.nbytes
        i8_bytes = i8.k["q"].nbytes + i8.k["s"].nbytes
        assert i8_bytes < 0.6 * bf16_bytes  # int8 + bf16 scales (2/D overhead)


class TestAttentionParity:
    def test_paged_attn_matches_bf16_pages(self):
        """Decode attention over int8 pages must track the bf16-page result
        within quantization noise."""
        rng = np.random.default_rng(1)
        cfg = LlamaConfig.tiny()
        pool16 = init_pool(cfg, num_pages=17, page_size=16)
        pool8 = init_pool(cfg, num_pages=17, page_size=16, quantized=True)

        b, nb = 2, 4
        table = jnp.asarray(rng.choice(np.arange(1, 17), (b, nb), replace=False),
                            jnp.int32)
        lens = jnp.asarray([30, 55], jnp.int32)

        k16, v16, k8, v8 = pool16.k, pool16.v, pool8.k, pool8.v
        # fill the referenced pages of the LAST layer via the write helper;
        # layer 0 stays zero, so reading the wrong layer shows
        layer = cfg.n_layers - 1
        assert layer > 0
        for row in range(b):
            for pos in range(int(lens[row]) + 1):
                pid = table[row, pos // 16][None]
                off = jnp.asarray([pos % 16])
                kv = jnp.asarray(rng.standard_normal((1, cfg.n_kv_heads, cfg.head_dim)),
                                 jnp.bfloat16)
                vv = jnp.asarray(rng.standard_normal((1, cfg.n_kv_heads, cfg.head_dim)),
                                 jnp.bfloat16)
                k16 = _page_write(k16, layer, pid, off, kv)
                v16 = _page_write(v16, layer, pid, off, vv)
                k8 = _page_write(k8, layer, pid, off, kv)
                v8 = _page_write(v8, layer, pid, off, vv)

        q = jnp.asarray(rng.standard_normal((b, 1, cfg.n_heads, cfg.head_dim)),
                        jnp.bfloat16)
        n_rep = cfg.n_heads // cfg.n_kv_heads
        out16 = _paged_attn_xla(q, k16, v16, layer, table, lens, n_rep)
        out8 = _paged_attn_xla(q, k8, v8, layer, table, lens, n_rep)
        diff = float(jnp.abs(out16.astype(jnp.float32) - out8.astype(jnp.float32)).max())
        assert diff < 0.05, diff
        assert float(jnp.abs(out16).max()) > 0.1
        for k, v in ((k16, v16), (k8, v8)):
            empty = _paged_attn_xla(q, k, v, 0, table, lens, n_rep)
            assert float(jnp.abs(empty).max()) == 0.0

    def test_gather_dequantizes(self):
        cfg = LlamaConfig.tiny()
        pool8 = init_pool(cfg, num_pages=5, page_size=16, quantized=True)
        val = jnp.full((1, cfg.n_kv_heads, cfg.head_dim), 0.5, jnp.bfloat16)
        k8 = _page_write(pool8.k, 1, jnp.asarray([2]), jnp.asarray([3]), val)
        table = jnp.asarray([[2]], jnp.int32)
        dense = _gather_pages(k8, 1, table, jnp.bfloat16)
        got = float(dense[0, 3, 0, 0])
        assert abs(got - 0.5) < 0.01
        assert float(jnp.abs(_gather_pages(k8, 0, table, jnp.bfloat16)).max()) == 0.0


class TestEngineWithInt8KV:
    def test_generates_and_is_deterministic(self):
        cfg = LlamaConfig.tiny()
        eng = ContinuousBatchingEngine(
            model_config=cfg, max_slots=4, page_size=16, max_pages_per_seq=8,
            steps_per_tick=4, kv_quant="int8",
        )
        prompts = ["int8 pages", "second request"]
        a = eng.run_all(prompts, max_new_tokens=8, temperature=0.0)
        b = ContinuousBatchingEngine(
            model_config=cfg, max_slots=4, page_size=16, max_pages_per_seq=8,
            steps_per_tick=4, kv_quant="int8",
        ).run_all(prompts, max_new_tokens=8, temperature=0.0)
        assert [r.tokens for r in a] == [r.tokens for r in b]
        # a random-init model may greedy-sample EOS immediately (0 tokens);
        # determinism above is the real assertion — just require valid ends
        assert all(r.finish_reason in ("stop", "length") for r in a)

    def test_tracks_bf16_pool_closely(self):
        """Greedy tokens from int8 pages usually match bf16 pages on a tiny
        model; require agreement on the first emitted token per row (the
        least noise-accumulated position)."""
        cfg = LlamaConfig.tiny()
        prompts = ["compare the pools", "on two rows"]
        i8 = ContinuousBatchingEngine(
            model_config=cfg, max_slots=4, page_size=16, max_pages_per_seq=8,
            steps_per_tick=4, kv_quant="int8",
        ).run_all(prompts, max_new_tokens=6, temperature=0.0)
        bf = ContinuousBatchingEngine(
            model_config=cfg, max_slots=4, page_size=16, max_pages_per_seq=8,
            steps_per_tick=4,
        ).run_all(prompts, max_new_tokens=6, temperature=0.0)
        for a, b in zip(i8, bf):
            assert a.tokens[0] == b.tokens[0]

    def test_reset_preserves_quantization(self):
        cfg = LlamaConfig.tiny()
        eng = ContinuousBatchingEngine(
            model_config=cfg, max_slots=2, page_size=16, max_pages_per_seq=4,
            kv_quant="int8",
        )
        eng.reset()
        assert eng.pool.quantized
        assert isinstance(eng.pool.k, dict)

    def test_mesh_sharded_int8_pool(self):
        from sentio_tpu.config import MeshConfig
        from sentio_tpu.parallel.mesh import build_mesh

        cfg = LlamaConfig.tiny()
        mesh = build_mesh(MeshConfig(dp_size=4, tp_size=2))
        pool = init_pool(cfg, num_pages=9, page_size=16, mesh=mesh,
                         quantized=True)
        # kv-head dim sharded over tp for both payload and scales
        assert pool.k["q"].sharding.spec[3] == "tp"
        assert pool.k["s"].sharding.spec[2] == "tp"  # page-minor scales

        eng = ContinuousBatchingEngine(
            model_config=cfg, mesh=mesh, max_slots=4, page_size=16,
            max_pages_per_seq=8, steps_per_tick=4, kv_quant="int8",
        )
        out = eng.run_all(["mesh int8"], max_new_tokens=6, temperature=0.0)
        assert out[0].finish_reason in ("stop", "length")

    def test_rejects_unknown_quant(self):
        with pytest.raises(ValueError, match="kv_quant"):
            ContinuousBatchingEngine(
                model_config=LlamaConfig.tiny(), kv_quant="fp4"
            )


class TestInt8PallasPath:
    """kv_quant=int8 no longer forces the XLA gather-dequant fallback: the
    Pallas kernel has a quantization-native variant, and with use_pallas
    the engine selects it (interpret mode on CPU)."""

    def test_engine_selects_pallas_impl_with_int8(self):
        eng = ContinuousBatchingEngine(
            model_config=LlamaConfig.tiny(), max_slots=2, page_size=16,
            max_pages_per_seq=4, kv_quant="int8", use_pallas=True,
        )
        assert eng._attn_impl is not None, (
            "int8 must not force the XLA fallback anymore")

    def test_pallas_and_xla_int8_paths_token_exact(self):
        """Both paths read the SAME int8+scale page values; greedy decode
        must be token-identical between them."""
        cfg = LlamaConfig.tiny()
        prompts = ["int8 kernel path", "second row of pages"]
        kw = dict(model_config=cfg, max_slots=2, page_size=16,
                  max_pages_per_seq=4, steps_per_tick=4, kv_quant="int8")
        pallas = ContinuousBatchingEngine(use_pallas=True, **kw)
        xla = ContinuousBatchingEngine(use_pallas=False, **kw)
        a = pallas.run_all(prompts, max_new_tokens=8, temperature=0.0)
        b = xla.run_all(prompts, max_new_tokens=8, temperature=0.0)
        assert [r.tokens for r in a] == [r.tokens for r in b]

    def test_fused_top_k_sampling_deterministic(self):
        """Per-request top_k rides the fused tick as traced data: same
        seed + same k → identical streams; the emission is valid."""
        cfg = LlamaConfig.tiny()

        def run():
            eng = ContinuousBatchingEngine(
                model_config=cfg, max_slots=2, page_size=16,
                max_pages_per_seq=4, steps_per_tick=4, kv_quant="int8",
            )
            rid = eng.submit("sampled int8", max_new_tokens=6,
                             temperature=0.8, top_k=4)
            done = {}
            while eng.has_work:
                for r in eng.step():
                    done[r.request_id] = r
            return done[rid]

        a, b = run(), run()
        assert a.tokens == b.tokens
        assert a.finish_reason in ("stop", "length")
