"""Quantization-native paged attention (kernels/paged_attention.py).

Tier-1 parity matrix for the int8 Pallas kernel in interpret mode: the
kernel must agree with the XLA gather-dequant path almost exactly (both
read the SAME int8+scale values — only the fold order differs) and with
the bf16-page reference within quantization tolerance, across GQA
ratios, ragged row lengths, and partial last pages. Plus the fused-
sampling compile telemetry: a decode tick is ONE ``paged.step_n``
dispatch — changing per-request top_k/temperature after warmup must not
compile anything new, and no sampling-only jit family may exist.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from sentio_tpu.kernels.paged_attention import (
    paged_attention,
    paged_attention_quant,
)
from sentio_tpu.runtime.paged import _paged_attn_xla, quantize_kv


LAYERS, LAYER = 3, 2  # every pool has its own values in each layer; the
# tests read one that is not 0, so a kernel that ignored its layer fails


def _quant_pool(rng, num_pages, page, hkv, d):
    shape = (LAYERS, num_pages, page, hkv, d)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    # the pool stores scales page-minor: [L, P, Hkv, page]
    return k, v, kq, ks.swapaxes(-1, -2), vq, vs.swapaxes(-1, -2)


class TestInt8KernelParity:
    @pytest.mark.parametrize(
        "h,hkv",
        [(4, 1), (4, 2), (4, 4)],
        ids=["gqa4:1", "gqa4:2", "mha4:4"],
    )
    def test_matches_gather_dequant_across_gqa(self, h, hkv):
        """Same int8 values in, near-identical attention out: the in-register
        (q·K)·s fold vs the dense dequant-then-attend gather."""
        rng = np.random.default_rng(0)
        b, d, page, nb, num_pages = 3, 16, 8, 4, 13
        _k, _v, kq, ks, vq, vs = _quant_pool(rng, num_pages, page, hkv, d)
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        table = jnp.asarray(
            [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]], jnp.int32)
        # ragged: mid-first-page, mid-window (partial page 3), full window
        lens = jnp.asarray([5, 17, 30], jnp.int32)

        ref = _paged_attn_xla(
            q[:, None], {"q": kq, "s": ks}, {"q": vq, "s": vs},
            LAYER, table, lens, h // hkv,
        )[:, 0]
        got = paged_attention_quant(
            q, kq, ks, vq, vs, LAYER, table, lens, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_tracks_bf16_kernel_within_quant_tolerance(self):
        rng = np.random.default_rng(1)
        b, h, hkv, d, page, nb, num_pages = 2, 4, 2, 32, 8, 4, 9
        k, v, kq, ks, vq, vs = _quant_pool(rng, num_pages, page, hkv, d)
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        table = jnp.asarray(
            rng.choice(np.arange(1, num_pages), (b, nb), replace=False),
            jnp.int32)
        lens = jnp.asarray([13, 27], jnp.int32)

        ref = paged_attention(q, k, v, LAYER, table, lens, interpret=True)
        got = paged_attention_quant(
            q, kq, ks, vq, vs, LAYER, table, lens, interpret=True)
        diff = float(jnp.abs(got - ref).max())
        assert diff < 0.05, diff  # absmax int8: ~1e-2 worst-case here

    def test_partial_last_page_masks_garbage(self):
        """Positions past ``lens`` on the current page must not leak: poison
        the tail of the last page and require an unchanged result."""
        rng = np.random.default_rng(2)
        b, h, hkv, d, page, num_pages = 1, 2, 1, 16, 8, 5
        k, v, kq, ks, vq, vs = _quant_pool(rng, num_pages, page, hkv, d)
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        table = jnp.asarray([[2, 3]], jnp.int32)
        lens = jnp.asarray([10], jnp.int32)  # 3rd token of page 3

        clean = paged_attention_quant(
            q, kq, ks, vq, vs, LAYER, table, lens, interpret=True)
        kq2 = kq.at[LAYER, 3, 4:].set(127)
        ks2 = ks.at[LAYER, 3, :, 4:].set(100.0)
        vq2 = vq.at[LAYER, 3, 4:].set(127)
        vs2 = vs.at[LAYER, 3, :, 4:].set(100.0)
        poisoned = paged_attention_quant(
            q, kq2, ks2, vq2, vs2, LAYER, table, lens, interpret=True)
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))

    def test_single_row_single_page(self):
        """Smallest shape: one row, length inside the first page."""
        rng = np.random.default_rng(3)
        b, h, hkv, d, page, num_pages = 1, 2, 2, 16, 8, 3
        k, v, kq, ks, vq, vs = _quant_pool(rng, num_pages, page, hkv, d)
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        table = jnp.asarray([[1]], jnp.int32)
        lens = jnp.asarray([0], jnp.int32)  # only the freshly written token

        ref = _paged_attn_xla(
            q[:, None], {"q": kq, "s": ks}, {"q": vq, "s": vs},
            LAYER, table, lens, h // hkv,
        )[:, 0]
        got = paged_attention_quant(
            q, kq, ks, vq, vs, LAYER, table, lens, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


class TestFusedSamplingTelemetry:
    def test_tick_is_one_family_and_sampling_params_never_recompile(self):
        """Compile telemetry proof that sampling lives INSIDE the decode
        dispatch: after a warmup generation, submissions with different
        temperature / top_k values reuse the compiled ``paged.step_n``
        variants verbatim (traced sampling params — zero cache growth), and
        every compile event ever seen belongs to a ``paged.*`` family (no
        separate logits-then-sample dispatch exists to compile)."""
        from sentio_tpu.analysis.audit import fence
        from sentio_tpu.models.llama import LlamaConfig
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine

        fence.reset()
        try:
            eng = ContinuousBatchingEngine(
                model_config=LlamaConfig.tiny(), max_slots=2, page_size=16,
                max_pages_per_seq=4, steps_per_tick=4,
            )
            eng.run_all(["warm the tick"], max_new_tokens=6, temperature=0.0)
            # second generation: admission now merges into DEVICE-carried
            # decode state (the first merged into host-mirror seeds), which
            # is its own compiled variant — warm it like service.warmup does
            eng.run_all(["warm the tick"], max_new_tokens=6, temperature=0.0)
            events = fence.drain_events()
            assert events, "cold engine must have compiled something"
            assert all(e["family"].startswith("paged.") for e in events), (
                [e["family"] for e in events])

            # same shapes, different sampling params: the armed fence turns
            # any recompile into an error — none may happen
            fence.arm()
            try:
                out = eng.run_all(
                    ["warm the tick"], max_new_tokens=6, temperature=0.9)
                assert out[0].finish_reason in ("stop", "length")
                rid = eng.submit("warm the tick", max_new_tokens=6,
                                 temperature=0.7, top_k=5)
                done = {}
                while eng.has_work:
                    for r in eng.step():
                        done[r.request_id] = r
                assert done[rid].finish_reason in ("stop", "length")
            finally:
                fence.disarm()
            assert fence.drain_events() == []
        finally:
            fence.reset()

    def test_spec_engine_rejects_top_k(self):
        from sentio_tpu.analysis.audit.specs import _paged_engine

        eng = _paged_engine(draft=True)
        with pytest.raises(ValueError, match="speculation"):
            eng.submit("draft pool", max_new_tokens=2, top_k=3)

    def test_stream_rejects_top_k_at_call_time(self):
        """generate_stream is lazily executed; the top_k/speculation
        rejection must still fire at CALL time (before an SSE handler
        could commit its 200), not at first iteration."""
        from sentio_tpu.analysis.audit.specs import _paged_engine
        from sentio_tpu.runtime.service import PagedGenerationService

        svc = PagedGenerationService(_paged_engine(draft=True))
        try:
            with pytest.raises(ValueError, match="speculation"):
                svc.generate_stream("spec stream", max_new_tokens=2, top_k=3)
            with pytest.raises(ValueError, match="speculation"):
                svc.generate("spec call", max_new_tokens=2, top_k=3)
        finally:
            svc.close()
