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


def _walk_case(rng, kind, b, h, hkv, d, page, nb, dtype=jnp.float32):
    """A pool, a query and a kernel call for ``kind`` ("bf16" pages or
    "int8"): every row owns its own pages, and the pool's LAST page is NaN
    all through (payload and scales). Returns (call(table, lens) → kernel
    output, ref(table, lens) → XLA gather path, own [b, nb] page ids, nan
    page id)."""
    num_pages = 1 + b * nb + 1
    nan_page = num_pages - 1
    shape = (LAYERS, num_pages, page, hkv, d)
    k = jnp.asarray(rng.standard_normal(shape), dtype).at[:, nan_page].set(jnp.nan)
    v = jnp.asarray(rng.standard_normal(shape), dtype).at[:, nan_page].set(jnp.nan)
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    own = 1 + np.arange(b * nb, dtype=np.int32).reshape(b, nb)
    if kind == "bf16":
        def call(table, lens):
            return paged_attention(q, k, v, LAYER, table, lens, interpret=True)

        def ref(table, lens):
            return _paged_attn_xla(q[:, None], k, v, LAYER, table, lens, h // hkv)[:, 0]
    else:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ks = ks.swapaxes(-1, -2).at[:, nan_page].set(jnp.nan)
        vs = vs.swapaxes(-1, -2).at[:, nan_page].set(jnp.nan)

        def call(table, lens):
            return paged_attention_quant(
                q, kq, ks, vq, vs, LAYER, table, lens, interpret=True)

        def ref(table, lens):
            return _paged_attn_xla(
                q[:, None], {"q": kq, "s": ks}, {"q": vq, "s": vs},
                LAYER, table, lens, h // hkv)[:, 0]
    return call, ref, own, nan_page


def _tables(own, lens, page, beyond):
    """Row tables that name ``own`` pages up to each row's current block and
    page ``beyond`` in every cell past it."""
    held = np.asarray(lens) // page + 1
    cells = np.arange(own.shape[1])[None, :] < held[:, None]
    return jnp.asarray(np.where(cells, own, beyond), jnp.int32)


KINDS = ["bf16", "int8"]
# query / kv heads of the benchmark's cells (Mistral-7B 32 / 8, Yi-6B 32 / 4)
# and of one device's share of each under tp=4 (8 / 2, 8 / 1); pages a row as
# the cells serve them (18, 10): neither a multiple of the pages in flight
CELL_SHAPES = [(32, 8, 18), (32, 4, 10), (8, 2, 18), (8, 1, 10)]


class TestWalkFollowsWhatRowsHold:
    """The decode kernel walks the blocks a row HOLDS (``lens // page + 1``;
    one for a free slot) with its own DMA, in a ring of buffers that runs
    across rows; a table cell past a row's length is never read. bf16 and
    int8 pages go through the same walk. Against the XLA gather path, at
    the tolerances the kernels have always been held to."""

    @pytest.mark.parametrize("kind", KINDS)
    def test_block_edges_in_one_call(self, kind):
        """``lens`` at 0, page − 1, page, page + 1 and a full table in ONE
        call, empty rows between full ones: every way a row's last block can
        end, and every way the ring can cross from one row to the next."""
        rng = np.random.default_rng(10)
        b, h, hkv, d, page, nb = 9, 4, 2, 16, 8, 5
        call, ref, own, _ = _walk_case(rng, kind, b, h, hkv, d, page, nb)
        lens = jnp.asarray([0, page - 1, page, page + 1, nb * page - 1,
                            0, nb * page - 1, 0, 2 * page + 3], jnp.int32)
        table = _tables(own, lens, page, beyond=0)
        np.testing.assert_allclose(np.asarray(call(table, lens)),
                                   np.asarray(ref(table, lens)), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("kind", KINDS)
    def test_cells_past_a_rows_length_are_never_read(self, kind):
        """Cells past each row's current block name a page that is NaN all
        through: a walk of the TABLE would bring it into the softmax (a
        masked score times a NaN value is NaN — the gather path shows it);
        a walk of what the rows hold never copies it."""
        rng = np.random.default_rng(11)
        b, h, hkv, d, page, nb = 4, 4, 2, 16, 8, 4
        call, ref, own, nan_page = _walk_case(rng, kind, b, h, hkv, d, page, nb)
        lens = jnp.asarray([0, 5, 2 * page, 3 * page - 1], jnp.int32)
        poisoned = _tables(own, lens, page, beyond=nan_page)
        clean = _tables(own, lens, page, beyond=0)
        got = np.asarray(call(poisoned, lens))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, np.asarray(call(clean, lens)))
        np.testing.assert_allclose(got, np.asarray(ref(clean, lens)), atol=2e-5, rtol=2e-5)
        assert not np.isfinite(np.asarray(ref(poisoned, lens))).all()

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("h, hkv, nb", CELL_SHAPES,
                             ids=[f"{h}q{hkv}kv-{nb}pages" for h, hkv, nb in CELL_SHAPES])
    def test_cell_head_shapes_and_ragged_tables(self, kind, h, hkv, nb):
        rng = np.random.default_rng(12)
        b, d, page = 5, 16, 8
        call, ref, own, nan_page = _walk_case(rng, kind, b, h, hkv, d, page, nb)
        # free, one block, mid-table, an odd count of blocks, full
        lens = jnp.asarray([0, 3, (nb // 2) * page + 1, (nb - 3) * page - 1,
                            nb * page - 1], jnp.int32)
        got = call(_tables(own, lens, page, beyond=nan_page), lens)
        want = ref(_tables(own, lens, page, beyond=0), lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_bf16_values_through_the_walk(self):
        """As served: bf16 query and pages. The walk sums a row's pages in
        another order than the gather path; bf16 rounding is the whole gap."""
        rng = np.random.default_rng(13)
        b, h, hkv, d, page, nb = 4, 8, 2, 32, 16, 6
        call, ref, own, nan_page = _walk_case(
            rng, "bf16", b, h, hkv, d, page, nb, dtype=jnp.bfloat16)
        lens = jnp.asarray([0, page, 3 * page + 5, nb * page - 1], jnp.int32)
        got = call(_tables(own, lens, page, beyond=nan_page), lens)
        want = ref(_tables(own, lens, page, beyond=0), lens)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=2e-2)

    @pytest.mark.parametrize("page, hkv, d, quant, word", [
        # served: both cells, a tp=4 device's share of each, int8 at 8 and 4
        (128, 8, 128, False, None), (128, 4, 128, False, None),
        (128, 2, 128, False, None), (128, 1, 128, False, None),
        (128, 8, 128, True, None), (128, 4, 128, True, None),
        # int8, two kv heads a device (8 under tp=4): read head-major
        (128, 2, 128, True, None),
        (16, 8, 128, False, None), (16, 1, 128, False, None),
        # what the chip's DMA cannot bring: the engine serves these through
        # the XLA gather path, the kernel raises
        (128, 8, 64, False, "head_dim"), (8, 1, 128, False, "tiles"),
        (16, 8, 128, True, "scale page"), (128, 1, 128, True, "kv head"),
    ])
    def test_which_geometries_the_chips_dma_can_bring(self, page, hkv, d, quant, word):
        from sentio_tpu.kernels.paged_attention import untiled

        why = untiled(page, hkv, d, quant)
        assert (why is None) if word is None else (word in why), why

    @pytest.mark.parametrize("kind, hkv, want", [
        ("bf16", 1, False), ("bf16", 2, False), ("bf16", 8, False),
        ("int8", 1, False), ("int8", 2, True), ("int8", 4, False), ("int8", 8, False),
    ])
    def test_a_page_is_read_as_the_matrix_it_is_in_memory(self, kind, hkv, want):
        """Position-major where a position's kv heads fill 32-bit sublanes,
        head-major where they do not (2 heads of int8) — and the same
        attention either way, with NaN pages past the rows' lengths."""
        from sentio_tpu.kernels.paged_attention import _head_major

        dtype = jnp.int8 if kind == "int8" else jnp.bfloat16
        assert _head_major(hkv, dtype) is want
        rng = np.random.default_rng(14)
        b, h, d, page, nb = 3, 8, 16, 8, 3
        call, ref, own, nan_page = _walk_case(rng, kind, b, h, hkv, d, page, nb)
        lens = jnp.asarray([0, page + 2, nb * page - 1], jnp.int32)
        got = call(_tables(own, lens, page, beyond=nan_page), lens)
        want_out = ref(_tables(own, lens, page, beyond=0), lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_out),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("hkv, dtype", [
        (8, jnp.bfloat16),   # Mistral-7B: a page of K and V is 512 KiB
        (4, jnp.bfloat16),   # Yi-6B
        (8, jnp.int8),
    ])
    def test_pages_in_flight_follow_from_the_blocks_bytes(self, hkv, dtype):
        import jax

        from sentio_tpu.kernels.paged_attention import pages_in_flight

        pool = jax.ShapeDtypeStruct((16, 289, 128 * hkv, 128), dtype)
        scales = jax.ShapeDtypeStruct((16, 289, hkv, 128), jnp.bfloat16)
        pools = (pool, pool) if dtype == jnp.bfloat16 else (pool, scales, pool, scales)
        assert pages_in_flight(pools) == 3
        # a page too large for three: what fits, and never under two
        big = jax.ShapeDtypeStruct((2, 9, 1024 * 8, 128), jnp.bfloat16)
        assert pages_in_flight((big, big)) == 2
        huge = jax.ShapeDtypeStruct((2, 9, 4096 * 8, 128), jnp.bfloat16)
        assert pages_in_flight((huge, huge)) == 2


class TestEngineServesWhatTheDmaCannotBring:
    """On a TPU the engine picks the paged kernel — unless its geometry is
    one the chip's DMA cannot bring (``untiled``): then the XLA gather path,
    chosen when the engine is built and logged, never a padded copy of the
    pool a call; a caller who ASKS for the kernel there is refused."""

    @pytest.mark.parametrize("asked", [None, True])
    def test_a_head_dim_under_a_tile(self, monkeypatch, caplog, asked):
        import jax

        from sentio_tpu.models.llama import LlamaConfig
        from sentio_tpu.runtime.paged import ContinuousBatchingEngine

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        build = dict(model_config=LlamaConfig.tiny(), max_slots=2, page_size=16,
                     max_pages_per_seq=4, use_pallas=asked)
        if asked:
            with pytest.raises(ValueError, match="head_dim 16"):
                ContinuousBatchingEngine(**build)
            return
        with caplog.at_level("WARNING", logger="sentio_tpu.runtime.paged"):
            engine = ContinuousBatchingEngine(**build)
        assert engine._attn_impl is None
        assert "XLA gather path" in caplog.text and "head_dim 16" in caplog.text


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
