"""Kernel correctness: Pallas flash attention (interpret mode on the CPU
test mesh) and ring attention (real ppermute collectives over the virtual
8-device mesh) against the XLA reference attention — marked slow, class by
class — and the prefill's flash kernel over a prior against the three XLA
forms it replaces (tier-1: every case counts)."""

import jax.numpy as jnp
import numpy as np
import pytest

from sentio_tpu.config import MeshConfig
from sentio_tpu.kernels.flash_attention import flash_attention
from sentio_tpu.kernels.ring_attention import ring_attention_sharded
from sentio_tpu.models.layers import attention, causal_mask
from sentio_tpu.parallel.mesh import build_mesh

SLOW_MESH = [pytest.mark.slow, pytest.mark.mesh]


def make_qkv(b, t, h, d, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32) for _ in range(3)
    )


class TestFlashAttention:
    pytestmark = SLOW_MESH

    def test_causal_matches_reference(self):
        q, k, v = make_qkv(2, 96, 4, 32)
        ref = attention(q, k, v, causal_mask(96), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_non_divisible_length_padded(self):
        # 50 does not divide by the 32-blocks; padding must not leak
        q, k, v = make_qkv(1, 50, 2, 16, seed=1)
        ref = attention(q, k, v, causal_mask(50), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_varlen_rows(self):
        q, k, v = make_qkv(2, 64, 2, 16, seed=2)
        lens = jnp.array([40, 64], jnp.int32)
        pad = jnp.arange(64)[None, :] < lens[:, None]
        ref = attention(q, k, v, causal_mask(64) & pad[:, None, None, :], jnp.float32)
        out = flash_attention(q, k, v, lens, causal=True, block_q=32, block_k=32, interpret=True)
        valid = np.asarray(pad)[:, :, None, None]
        np.testing.assert_allclose(
            np.asarray(out) * valid, np.asarray(ref) * valid, atol=2e-5
        )

    def test_non_causal(self):
        q, k, v = make_qkv(1, 64, 2, 16, seed=3)
        ref = attention(q, k, v, None, jnp.float32)
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_cross_attention_shapes(self):
        # S != T (query block against a longer cache window)
        q, _, _ = make_qkv(1, 32, 2, 16, seed=4)
        _, k, v = make_qkv(1, 96, 2, 16, seed=5)
        ref = attention(q, k, v, None, jnp.float32)
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


class TestRingAttention:
    pytestmark = SLOW_MESH

    @pytest.fixture()
    def mesh(self):
        return build_mesh(MeshConfig(dp_size=2, sp_size=4, tp_size=1))

    def test_causal_matches_reference(self, mesh):
        q, k, v = make_qkv(4, 64, 4, 32, seed=6)
        ref = attention(q, k, v, causal_mask(64), jnp.float32)
        out = ring_attention_sharded(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_full_matches_reference(self, mesh):
        q, k, v = make_qkv(2, 32, 2, 16, seed=7)
        ref = attention(q, k, v, None, jnp.float32)
        out = ring_attention_sharded(q, k, v, mesh, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_rejects_indivisible_sequence(self, mesh):
        q, k, v = make_qkv(2, 30, 2, 16)
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention_sharded(q, k, v, mesh)

    def test_sp8_full_ring(self):
        mesh = build_mesh(MeshConfig(dp_size=1, sp_size=8, tp_size=1))
        q, k, v = make_qkv(1, 128, 2, 16, seed=8)
        ref = attention(q, k, v, causal_mask(128), jnp.float32)
        out = ring_attention_sharded(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


class TestLlamaKernelIntegration:
    pytestmark = SLOW_MESH

    def test_forward_with_flash_matches_xla(self):
        import jax

        from sentio_tpu.kernels import flash_attn_fn
        from sentio_tpu.models.llama import LlamaConfig, init_llama, llama_forward

        cfg = LlamaConfig.tiny()
        params = init_llama(jax.random.PRNGKey(0), cfg)
        ids = jnp.asarray(np.random.default_rng(9).integers(1, 500, (2, 48)), jnp.int32)
        mask = jnp.ones((2, 48), bool)

        ref, _ = llama_forward(params, cfg, ids, pad_mask=mask)
        out, _ = llama_forward(params, cfg, ids, pad_mask=mask, attn_fn=flash_attn_fn)
        # the model runs in bf16 — blockwise vs monolithic softmax reorders
        # accumulation, so compare at bf16 resolution + next-token agreement
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0.15, rtol=0.1)
        # random init → near-uniform logits with frequent ties, so a few
        # argmax flips from bf16 noise are expected; bound the rate
        agree = (np.argmax(np.asarray(out), -1) == np.argmax(np.asarray(ref), -1)).mean()
        assert agree > 0.95, f"next-token argmax agreement {agree}"


class TestMeshAttnFn:
    pytestmark = SLOW_MESH

    """make_mesh_attn_fn: kernels running INSIDE shard_map over the mesh —
    heads on tp, sequence-ring over sp — must match XLA attention."""

    def _masked_ref(self, q, k, v, kv_lens=None, causal=True):
        t = q.shape[1]
        mask = causal_mask(t) if causal else jnp.ones((t, t), bool)[None, None]
        if kv_lens is not None:
            key_ok = jnp.arange(t)[None, :] < kv_lens[:, None]
            mask = mask & key_ok[:, None, None, :]
        return attention(q, k, v, mask, jnp.float32)

    def test_tp_sharded_flash_matches_xla(self):
        from sentio_tpu.kernels import make_mesh_attn_fn

        mesh = build_mesh(MeshConfig(dp_size=4, sp_size=1, tp_size=2))
        fn = make_mesh_attn_fn(mesh, causal=True)
        q, k, v = make_qkv(4, 32, 4, 16, seed=11)
        out = fn(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._masked_ref(q, k, v)),
            atol=2e-2, rtol=2e-2,
        )

    def test_sp_ring_matches_xla(self):
        from sentio_tpu.kernels import make_mesh_attn_fn

        mesh = build_mesh(MeshConfig(dp_size=2, sp_size=2, tp_size=2))
        fn = make_mesh_attn_fn(mesh, causal=True)
        q, k, v = make_qkv(2, 32, 4, 16, seed=12)
        out = fn(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._masked_ref(q, k, v)), atol=2e-4
        )

    def test_ring_respects_kv_lens(self):
        from sentio_tpu.kernels import make_mesh_attn_fn

        mesh = build_mesh(MeshConfig(dp_size=2, sp_size=2, tp_size=2))
        fn = make_mesh_attn_fn(mesh, causal=True)
        q, k, v = make_qkv(2, 32, 4, 16, seed=13)
        lens = jnp.asarray([20, 9], jnp.int32)
        out = fn(q, k, v, lens)
        ref = self._masked_ref(q, k, v, kv_lens=lens)
        # compare only valid query rows (padding queries attend nothing real)
        for b in range(2):
            n = int(lens[b])
            np.testing.assert_allclose(
                np.asarray(out)[b, :n], np.asarray(ref)[b, :n], atol=2e-4
            )

    def test_indivisible_heads_raise(self):
        from sentio_tpu.kernels import make_mesh_attn_fn

        mesh = build_mesh(MeshConfig(dp_size=1, sp_size=2, tp_size=4))
        fn = make_mesh_attn_fn(mesh, causal=True)
        q, k, v = make_qkv(2, 32, 6, 16)
        with pytest.raises(ValueError, match="heads"):
            fn(q, k, v)

    def test_encoder_kernel_matches_xla(self):
        from sentio_tpu.kernels import encoder_attn_fn

        q, k, v = make_qkv(3, 24, 2, 16, seed=14)
        lens = jnp.asarray([24, 10, 1], jnp.int32)
        out = encoder_attn_fn(q, k, v, lens)
        ref = self._masked_ref(q, k, v, kv_lens=lens, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2, rtol=2e-2)

    def test_encoder_forward_kernel_path_matches(self):
        import jax

        from sentio_tpu.kernels import encoder_attn_fn
        from sentio_tpu.models.transformer import (
            EncoderConfig, encoder_forward, init_encoder,
        )

        cfg = EncoderConfig.tiny()
        params = init_encoder(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(5)
        ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 24)), jnp.int32)
        mask = jnp.asarray([[True] * 24, [True] * 10 + [False] * 14])
        ref = encoder_forward(params, cfg, ids, mask)
        out = encoder_forward(params, cfg, ids, mask, attn_fn=encoder_attn_fn)
        # compare real-token positions only
        np.testing.assert_allclose(
            np.asarray(out)[0], np.asarray(ref)[0], atol=5e-2, rtol=5e-2
        )
        np.testing.assert_allclose(
            np.asarray(out)[1, :10], np.asarray(ref)[1, :10], atol=5e-2, rtol=5e-2
        )


# ---------------------------------------------------- prefill over a prior
#
# kernels/prefill_attention.py in interpret mode against the XLA form each
# family ran before it: ``layers.attention`` behind ``repeat_kv`` (dense),
# ``cohere2_moe.windowed_attention`` (a window beside grouped queries),
# ``deepseek_v2.expanded_attention`` (keys wider than values, ONE rotated key
# a position). A page is 16 tokens here; every row's cache holds NaN past the
# row's own last query, so a block the walk should not read — or a masked key
# that reaches PV — poisons the answer.

PAGE = 16
# name → heads, kv heads, head width, segment, prior bucket (pages), each row's
# first position, and what differs from plain causal grouped attention
PREFILL_CASES = {
    "gqa-4to1-rows-at-0-midblock-40pages": dict(h=8, hkv=2, d=16, t=512, bucket=40, starts=[0, 200, 40 * PAGE]),
    "gqa-8to1": dict(h=8, hkv=1, d=16, t=512, bucket=8, starts=[8 * PAGE, 3 * PAGE]),
    "gqa-16to1": dict(h=16, hkv=1, d=16, t=128, bucket=8, starts=[100]),
    # a group that divides no power of two: query blocks of 48 (1024 // 20 = 51, rounded to the bf16 tile), the last padded
    "mqa-20to1-blocks-of-48": dict(h=20, hkv=1, d=16, t=512, bucket=8, starts=[8 * PAGE, 61]),
    "gqa-16to1-window": dict(h=16, hkv=1, d=16, t=128, bucket=40, starts=[40 * PAGE, 77], window=96),
    "latent-128-heads-second-term": dict(h=128, hkv=128, d=16, r=8, dv=32, t=512, bucket=40,
                                         starts=[40 * PAGE], scale=0.31),
    "latent-rows-differ": dict(h=4, hkv=4, d=16, r=8, dv=32, t=64, bucket=8, starts=[0, 37, 8 * PAGE],
                               scale=0.31),
    "pad-row": dict(h=8, hkv=2, d=16, t=64, bucket=4, starts=[4 * PAGE, 0], pad_rows=[1]),
    "short-last-segment": dict(h=8, hkv=2, d=16, t=40, bucket=40, starts=[40 * PAGE, 9]),
    "prior-smaller-than-its-bucket": dict(h=8, hkv=2, d=16, t=512, bucket=32, starts=[18 * PAGE]),
}


@pytest.mark.parametrize("case", sorted(PREFILL_CASES))
def test_prefill_attention_matches_the_xla_form_it_replaces(case):
    from sentio_tpu.kernels.prefill_attention import prefill_attention
    from sentio_tpu.models.cohere2_moe import windowed_attention
    from sentio_tpu.models.deepseek_v2 import expanded_attention
    from sentio_tpu.models.layers import repeat_kv

    c = PREFILL_CASES[case]
    h, hkv, d, t = c["h"], c["hkv"], c["d"], c["t"]
    dv, r, window = c.get("dv", d), c.get("r"), c.get("window")
    starts = np.asarray(c["starts"], np.int32)
    b, s = len(starts), c["bucket"] * PAGE + t
    rng = np.random.default_rng(len(case))

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    q, k = normal(b, t, h, d), normal(b, s, hkv, d)
    v = rng.uniform(-1.0, 1.0, (b, s, hkv, dv)).astype(np.float32)   # outputs under 1: a bf16 step is 0.0039
    q_pe, k_pe = (normal(b, t, h, r), normal(b, s, r)) if r else (None, None)
    for row in c.get("pad_rows", ()):       # lens 0: pad tokens over the scratch page
        q[row] = 0.0
    bf = lambda x: None if x is None else jnp.asarray(x, jnp.bfloat16)  # noqa: E731
    clean = [bf(x) for x in (q, k, v, q_pe, k_pe)]
    for row, start in enumerate(starts):    # nothing past a row's last query may be read
        for x in (k, v, k_pe):
            if x is not None:
                x[row, start + t:] = np.nan
    got = prefill_attention(bf(q), bf(k), bf(v), jnp.asarray(starts), bf(q_pe), bf(k_pe),
                            sm_scale=c.get("scale"), window=window, interpret=True)

    q, k, v, q_pe, k_pe = clean
    pos = jnp.asarray(starts)[:, None] + jnp.arange(t)[None, :]
    if r:
        ref = expanded_attention(q, q_pe, k, k_pe, v, pos, None, c["scale"], jnp.bfloat16)
    elif window:
        ref = windowed_attention(q, k, v, pos, None, window, jnp.bfloat16)
    else:
        mask = jnp.arange(s)[None, None, None, :] <= pos[:, None, :, None]
        spread = [repeat_kv(x, h // hkv) for x in (k, v)]
        ref = attention(q, *spread, mask, jnp.bfloat16)
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32).reshape(b, t, h, dv)
    assert got.shape == ref.shape and np.isfinite(got).all()
    real = [row for row in range(b) if row not in c.get("pad_rows", ())]
    step = 2.0 ** -8    # one bf16 step of a value under 1: the decode kernels' 0.0039 (PERF.md section 5)
    if r or window:
        # these forms keep their scores in float32, as the kernel does
        assert np.abs(got[real] - ref[real]).max() <= step
    else:
        # ``layers.attention`` rounds its scores to bf16 before the softmax: the kernel, which
        # does not, may differ from it by what that rounding costs — and is held to being no
        # further from the float32 answer than the form it replaces
        exact = np.asarray(attention(*(x.astype(jnp.float32) for x in (q, *spread)), mask, jnp.float32))
        assert np.abs(got[real] - ref[real]).max() <= 2 * step
        assert np.abs(got[real] - exact[real]).max() <= max(np.abs(ref[real] - exact[real]).max(), step)
