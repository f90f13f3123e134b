"""The family ``deepseek_v2`` (models/deepseek_v2.py, the group-limited expert
layer of models/moe.py, the latent pool and the third block kind of
runtime/paged.py, kernels/latent_attention.py) against its plain reference
(benchmark/deepseek_v2_reference.py), at a small size on the CPU: seeded
random weights, logits and not tokens.

Tolerances, each with its reason. In FLOAT32 (``F32``) program and reference
compute the same function from the same numbers and differ by the order of
their sums (and, through the pages, by the absorbed form: ``W_uk`` applied to
the query and not to the latent): logits of size 0.1–1 agree to 3e-5
(measured: under 4e-6), and a greedy token may differ from the reference's
only where the two best logits lie within that (``GAP``). ``ABSORBED`` 1e-5
RELATIVE is the absorbed attention against the expanded one on the same
float32 latents. In BF16, as served, at hidden 128 the pooled relative error
over forced picks reads 2.5–3.5 % over three seeds (attention peaked by the
seeded query scale, gates that are not renormalised): ``BF16_TOL`` 0.05 is
above that and under what one precision down gives (int8-rounded matrices
8–13 %, fp8-rounded 31–41 %) — the control at the end.
"""

import dataclasses
import inspect
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import deepseek_v2_reference as reference  # noqa: E402
from benchmark.check import choice_agreement, degrade, rel_rms  # noqa: E402
from benchmark.families import deepseek_v2 as family  # noqa: E402
from sentio_tpu.kernels.latent_attention import latent_attention  # noqa: E402
from sentio_tpu.models import deepseek_v2 as M  # noqa: E402
from sentio_tpu.models import moe  # noqa: E402
from sentio_tpu.models.deepseek_v2 import DeepseekV2Config, deepseek_v2_forward, init_deepseek_v2  # noqa: E402
from sentio_tpu.runtime.paged import (  # noqa: E402
    ContinuousBatchingEngine, _latent_attn_xla, init_pool, paged_decode_forward)

F32, GAP, ABSORBED, BF16_TOL = 3e-5, 3e-5, 1e-5, 0.05
KINDS = ("groups", "experts")


def tiny(**over) -> DeepseekV2Config:
    return DeepseekV2Config.tiny(**{"dtype": "float32", **over})


def seeded(cfg, seed=0, as_checkpoint=False):
    tree = init_deepseek_v2(jax.random.PRNGKey(seed), cfg)
    if as_checkpoint:  # matrices in bf16, norm scales float32
        tree = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if family.is_matrix(a) else a, tree)
    return tree


def ref_kwargs(cfg, **over) -> dict:
    """The reference's keywords are the config's own field names."""
    fields = dataclasses.asdict(cfg)
    wanted = [p.name for p in inspect.signature(reference.forward).parameters.values()
              if p.kind is p.KEYWORD_ONLY]
    return {**{k: fields[k] for k in wanted}, **over}


def ref_forward(cfg, tree, ids, forced=None, **over):
    """The plain reference on one sequence, given the program's share →
    (logits [T, V], {"groups": [Lr, T, G], "experts": [Lr, T, E]})."""
    params = jax.tree.map(jnp.asarray, family.reference_params(jax.device_get(tree), cfg.n_layers))
    logits, scores = reference.forward(params, jnp.asarray(ids), forced, **ref_kwargs(cfg, **over))
    return np.asarray(logits), {k: np.asarray(v) for k, v in scores.items()}


def agree(cfg, picks: dict, scores: dict) -> int:
    """Pairs of layer and position at which the program's groups or experts
    are not the reference's own best."""
    depth = {"groups": cfg.topk_group, "experts": cfg.experts_per_token}
    return sum(choice_agreement(picks[k], scores[k], depth[k])[1] for k in KINDS)


def ids_of(cfg, n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size))


# ------------------------------------------------------------- the forward


def test_contiguous_forward_is_the_reference():
    """Prefill against the reference: 40 tokens through the expanded form, a
    dense layer and two routed ones, and the groups and experts it hands back."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got, _, routed = deepseek_v2_forward(tree, cfg, jnp.asarray(ids)[None])
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(np.asarray(got)[0] - want).max() < F32
    picks = {k: np.asarray(routed[k])[:, 0] for k in KINDS}
    assert picks["experts"].shape == (2, 40, 4) and picks["groups"].shape == (2, 40, 2)
    assert agree(cfg, picks, scores) == 0
    assert routed["counts"].tolist() == [40 * 2 * 4, 40 * 2 * 4, 2 * 16, 2 * 16]


def test_the_references_own_controls_are_seen():
    """What the comparison catches: the reference told another scaling
    factor, told to renormalise, or given YaRN's factor 1 departs at once."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got = np.asarray(deepseek_v2_forward(tree, cfg, jnp.asarray(ids)[None])[0])[0]
    for over in ({"routed_scaling_factor": 1.0}, {"norm_topk_prob": True}, {"rope_factor": 1.0},
                 {"rope_mscale_all_dim": 0.0}):
        assert np.abs(got - ref_forward(cfg, tree, ids, **over)[0]).max() > 100 * F32, over


def test_yarn_frequencies_and_scale_against_the_closed_form():
    """The published configuration: m = 0.1 x 0.707 x ln 40 + 1 = 1.2608, the
    softmax scale 192^-1/2 m^2, cos and sin unscaled (both mscales equal), and
    the ramp between dimensions 10 and 23: as trained below it, over 40 above."""
    cfg = DeepseekV2Config()
    assert M.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=5e-5)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
    assert cfg.rope_cos_sin_scale == 1.0 and cfg.head_dim == 192 and cfg.latent_dim == 576
    assert M.yarn_correction_dims(cfg) == (10, 23)
    freq = M.yarn_inv_freq(cfg)
    plain = 1.0 / (10_000.0 ** (np.arange(0, 64, 2) / 64))
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 40, rtol=1e-6)
    mid = 16  # inside the ramp: (16 - 10) / 13 of the way to the interpolated one
    np.testing.assert_allclose(freq[mid], plain[mid] * (1 - 6 / 13) + plain[mid] / 40 * (6 / 13), rtol=1e-6)
    np.testing.assert_allclose(freq, np.asarray(reference.yarn_inv_freq(64, 10_000.0, 40.0, 4096, 32.0, 1.0)),
                               rtol=1e-6)
    np.testing.assert_allclose(M.yarn_inv_freq(DeepseekV2Config(rope_factor=1.0)), plain, rtol=1e-6)


def test_absorbed_decode_is_the_expanded_attention():
    """The same function twice, in float32: 5 queries of 4 heads over 37
    latents — expanded to per-head keys and values and attended as any
    attention, and absorbed (W_uk into the query, W_uv into the output) over
    the latents themselves. Relative to the output's size, under 1e-5."""
    cfg = tiny()
    ap = seeded(cfg)["layers_1"]["attn"]
    rng = np.random.default_rng(0)
    latents = jnp.asarray(rng.standard_normal((5, 37, 1, cfg.latent_dim)), jnp.float32)
    q_nope = jnp.asarray(rng.standard_normal((5, 1, cfg.n_heads, cfg.qk_nope_head_dim)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((5, 1, cfg.n_heads, cfg.qk_rope_head_dim)), jnp.float32)
    lens = jnp.asarray([36, 0, 17, 5, 30])
    k_nope, k_pe, v = M.expand_latents(ap, cfg, latents)
    want = M.expanded_attention(q_nope, q_pe, k_nope, k_pe, v, lens[:, None], None, cfg.softmax_scale,
                                jnp.float32).reshape(5, cfg.n_heads, cfg.v_head_dim)
    seen = jnp.arange(37)[None, :] <= lens[:, None]
    o_lat = M.latent_attention(M.absorb_query(ap, cfg, q_nope[:, 0]), q_pe[:, 0], latents[:, :, 0], seen,
                               cfg.softmax_scale)
    got = M.unabsorb(ap, cfg, o_lat)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < ABSORBED


# ---------------------------------------------- through the pages, both paths


def through_the_pages(cfg, tree, ids, prompt: int, use_pallas: bool, page: int = 8):
    """Teacher-forced: the family's contiguous prefill piece over ``prompt``
    tokens scattered into latent pages, then a decode step through the pool
    for each further token → (logits [T, V], picks {kind: [Lr, T, k]})."""
    engine = ContinuousBatchingEngine(
        model_config=cfg, params=tree, max_slots=1, page_size=page,
        max_pages_per_seq=len(ids) // page + 1, use_pallas=use_pallas)
    assert engine.stats()["paged_attention"] == ("pallas" if use_pallas else "xla")
    width = -(-prompt // page) * page
    state, prefill, decode = family.paged_pieces(engine, cfg, 1, width)
    table = 1 + np.arange(engine.max_pages_per_seq, dtype=np.int32)[None]
    padded = np.zeros((1, width), np.int32)
    padded[0, :prompt] = ids[:prompt]
    logits, state, picks = prefill(engine.params, padded, np.arange(width, dtype=np.int32)[None],
                                   np.asarray([prompt], np.int32), table[:, : width // page], state)
    out, chosen = [np.asarray(logits)[0, :prompt]], {k: [np.asarray(picks[k])[:, 0, :prompt]] for k in KINDS}
    for t in range(prompt, len(ids)):
        logits, state, picks = decode(engine.params, ids[t: t + 1].astype(np.int32),
                                      np.asarray([t], np.int32), table, state)
        out.append(np.asarray(logits))
        for k in KINDS:
            chosen[k].append(np.asarray(picks[k])[:, :1])
    return np.concatenate(out), {k: np.concatenate(v, axis=1) for k, v in chosen.items()}


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla-gather", "pallas-walk"])
def test_prefill_then_decode_through_the_latent_pool_is_the_reference(use_pallas):
    """20 tokens prefilled (expanded), 30 decoded through the latent pool
    (absorbed) — well past 8 steps, over several pages — against the
    reference's full forward, by the gather path and by the Pallas walk
    (interpreted)."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 50)
    got, picks = through_the_pages(cfg, tree, ids, 20, use_pallas)
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(got - want).max() < F32
    assert agree(cfg, picks, scores) == 0


def test_the_pool_holds_one_latent_a_token_and_layer():
    """1,152 B a token a layer at the published widths — asserted where the
    pool is made, in ``stats`` and in the benchmark's own count."""
    cfg = DeepseekV2Config(n_layers=2, vocab_size=512)
    pool = init_pool(cfg, num_pages=5, page_size=128)
    assert pool.v is None and pool.k.shape == (2, 5, 576, 128)
    assert pool.hbm_bytes == 2 * 5 * 128 * 1152 and pool.num_pages == 5
    model = {"kv_lora_rank": 512, "qk_rope_head_dim": 64, "num_hidden_layers": 2}
    env = {"LLM_MAX_BATCH": "1", "KV_MAX_PAGES_PER_SEQ": "4", "KV_PAGE_SIZE": "128"}
    assert family.pool_bytes(model, env) == pool.hbm_bytes and family.kv_bytes_per_token(model) == 2 * 1152
    small = tiny()
    engine = ContinuousBatchingEngine(model_config=small, params=seeded(small), max_slots=2, page_size=8,
                                      max_pages_per_seq=4)
    stats = engine.stats()
    assert stats["pool_token_layer_bytes"] == small.latent_dim * 4          # float32 here
    assert stats["pool_hbm_bytes"] == 9 * 8 * small.n_layers * small.latent_dim * 4


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla-gather", "pallas-walk"])
def test_served_answers_are_the_reference_and_carry_their_picks(use_pallas):
    """Through ``run_all``: a chunked prefill over a latent prior (a 50-token
    prompt in segments of 16, each expanding its prior's latents), a second
    request over the head the radix cache kept (latent pages reused), fused
    ticks. Every greedy token is the reference's own choice (or within ``GAP``
    of it), the log-probabilities agree, the picks ``run_all`` hands back —
    groups and experts — are the reference's on the same tokens, negative
    exactly where the radix cache served, and the prefill counter reads the
    dispatches' own integers."""
    cfg = tiny()
    tree = seeded(cfg)
    engine = ContinuousBatchingEngine(
        model_config=cfg, params=tree, max_slots=2, page_size=8, max_pages_per_seq=12,
        steps_per_tick=4, prefill_chunk=16, use_pallas=use_pallas)
    head = "the quick brown fox jumps over the lazy dog. "
    first = engine.run_all([head + "abc"], max_new_tokens=20, return_choices=True)
    assert first[0].prefill_segments == 4 and first[0].prefix_hit_tokens == 0
    # 49 tokens in segments of 16, 16, 16, 1 over priors of 0, 16, 32, 48
    assert engine.stats()["prefill_latent_new"] == 49 and engine.stats()["prefill_latent_expanded"] == 96
    rest = engine.run_all([head + "xyzw", "short"], max_new_tokens=20, return_choices=True)
    assert rest[0].prefix_hit_tokens >= 40
    for res, prompt in zip(first + rest, [head + "abc", head + "xyzw", "short"]):
        ids = np.asarray(engine.tokenizer.encode(prompt, add_bos=True) + list(res.tokens))
        want, scores = ref_forward(cfg, tree, ids)
        # what was SAMPLED: the answer and, where it stopped, the EOS that ended it (seeded
        # weights do sample one; the benchmark's tree cannot, its head's text columns are zero)
        sampled = list(res.tokens) + [engine.tokenizer.eos_id] * (res.finish_reason == "stop")
        assert res.logprob_count == len(sampled)
        rows = want[res.prompt_tokens - 1: res.prompt_tokens - 1 + len(sampled)].astype(np.float64)
        assert (rows.max(-1) - rows[np.arange(len(sampled)), sampled]).max() < GAP
        logprob = rows - np.log(np.exp(rows).sum(-1, keepdims=True))
        assert res.logprob_sum == pytest.approx(
            logprob[np.arange(len(sampled)), sampled].sum(), abs=1e-3)
        assert res.choices["experts"].shape == (cfg.n_routed_layers, len(ids) - 1, cfg.experts_per_token)
        assert res.choices["groups"].shape == (cfg.n_routed_layers, len(ids) - 1, cfg.topk_group)
        served = (res.choices["experts"] < 0).all(axis=(0, 2))
        assert served[: res.prefix_hit_tokens].all() and not served[res.prefix_hit_tokens:].any()
        assert ((res.choices["groups"] < 0).all(axis=(0, 2)) == served).all()
        own = ~served
        assert agree(cfg, {k: res.choices[k][:, own] for k in KINDS},
                     {k: scores[k][:, : len(ids) - 1][:, own] for k in KINDS}) == 0
    assert engine.run_all(["again"], max_new_tokens=4)[0].choices is None  # only when asked


def test_a_chunked_prefill_over_a_latent_prior_is_a_one_shot_prefill():
    """The same prompt admitted whole and in segments of 16 over its own
    latent prior: the first sampled token's log-probability and the greedy
    answers are the same to float32's last bits — and a radix hit served from
    latent pages equals the cold prefill that filled them."""
    cfg = tiny()
    tree = seeded(cfg)
    prompt = "a prompt long enough to take five segments of sixteen tokens, all told."

    def engine(chunk):
        return ContinuousBatchingEngine(model_config=cfg, params=tree, max_slots=1, page_size=8,
                                        max_pages_per_seq=16, steps_per_tick=4, prefill_chunk=chunk)

    whole = engine(None).run_all([prompt], max_new_tokens=12)[0]
    chunked_engine = engine(16)
    chunked = chunked_engine.run_all([prompt], max_new_tokens=12)[0]
    assert whole.prefill_segments == 1 and chunked.prefill_segments == 5
    assert whole.tokens == chunked.tokens
    assert chunked.logprob_sum == pytest.approx(whole.logprob_sum, abs=2e-4)
    assert chunked.logprob_min == pytest.approx(whole.logprob_min, abs=2e-4)
    again = chunked_engine.run_all([prompt], max_new_tokens=12)[0]      # the radix cache serves its pages
    assert again.prefix_hit_tokens == 64 and again.prefill_tokens == whole.prompt_tokens - 64
    assert again.tokens == whole.tokens and again.logprob_sum == pytest.approx(whole.logprob_sum, abs=2e-4)


def test_the_radix_cache_carries_over_unchanged():
    """Pages are pages: the radix cache holds ids of latent pages as it holds
    ids of K and V pages — match, lock, donate and evict by the same code,
    with no branch on the family."""
    import inspect

    from sentio_tpu.runtime import radix

    assert "latent" not in inspect.getsource(radix).lower()
    cfg = tiny()
    engine = ContinuousBatchingEngine(model_config=cfg, params=seeded(cfg), max_slots=1, page_size=8,
                                      max_pages_per_seq=8, num_pages=10)
    engine.run_all(["the first prompt, of five pages and a bit........"], max_new_tokens=4)
    held = engine.stats()["prefix_cache_pages"]
    assert held >= 5
    # nine usable pages: the second prompt needs the cached ones back, and gets them by eviction
    other = engine.run_all(["another prompt of the same size, sharing nothing!"], max_new_tokens=4)[0]
    assert other.finish_reason == "length" and other.prefix_hit_tokens == 0
    assert engine.allocator.free_pages + engine.stats()["prefix_cache_pages"] == 9


# ------------------------------------------------------- the latent kernel


@pytest.mark.parametrize("lens", [[0, 1, 11, 31], [31, 0, 7, 16]], ids=["rising", "mixed"])
def test_the_pallas_latent_kernel_is_the_gather_path(lens):
    """The Pallas walk in interpret mode against the XLA gather path over one
    pool: rows that hold nothing (the scratch page's one block), one token, a
    partial table and a full one (4 pages of 8), at a layer that is not 0."""
    rank, rope, heads, page, nb = 32, 16, 4, 8, 4
    rng = np.random.default_rng(7)
    pages = jnp.asarray(rng.standard_normal((2, 1 + 4 * nb, rank + rope, page)), jnp.float32)
    q_lat = jnp.asarray(rng.standard_normal((4, heads, rank)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((4, heads, rope)), jnp.float32)
    table = jnp.asarray(1 + np.arange(4 * nb).reshape(4, nb), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    want = _latent_attn_xla(q_lat, q_pe, pages, 1, table, lens, 0.17)
    got = latent_attention(q_lat, q_pe, pages, jnp.asarray(1), table, lens, sm_scale=0.17, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


def test_a_row_that_does_not_advance_writes_the_scratch_page_and_touches_no_expert():
    cfg = tiny()
    tree = seeded(cfg)
    pool = init_pool(cfg, num_pages=6, page_size=8)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    args = (tree, cfg, jnp.asarray([5, 9]), jnp.asarray([3, 9]), table, pool.k, None)
    _, pages, none, routed = paged_decode_forward(*args, write_mask=jnp.asarray([True, False]), return_routed=True)
    assert none is None and routed["counts"].tolist()[:2] == [2 * 4, 2 * 4]      # one row x 2 routed layers x 4
    written = np.asarray(jnp.abs(pages).sum(axis=(0, 2)))                        # [P, page]
    assert written[1, 3] > 0 and written[0, 0] > 0 and written[1:].sum() == written[1, 3]
    assert paged_decode_forward(*args)[2] is None


# ------------------------------------------------------- the expert layer


def layer_of(cfg, seed=3, tokens=48):
    tree = seeded(cfg, seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, tokens, cfg.dim), jnp.float32)
    return tree["layers_1"]["moe"], x


def reference_layer(cfg, mp, x, held, offset):
    lp = {"router": mp["router"]["kernel"], **{k: mp[k][offset: offset + held] for k in ("w_gate", "w_up", "w_down")},
          **{f"shared_{k[2:]}": mp["shared"][k] for k in ("w_gate", "w_up", "w_down")}}
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.experts(
            x[0], lp, None, n_group=cfg.n_group, topk_group=cfg.topk_group,
            experts_per_token=cfg.experts_per_token, routed_scaling_factor=cfg.routed_scaling_factor,
            norm_topk_prob=cfg.norm_topk_prob, experts_held=held, expert_offset=offset)[0])


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """THE SHARE TEST: 16 experts in 8 groups of 2, the best 3 groups, 4 a
    token, 2 shared. What each of the eight chips of the deployment computes —
    the routed part of ITS group — summed, plus the shared experts counted
    ONCE, is the uncut reference's layer output; each share is the reference
    given that share; and no token is sent to more than 3 chips."""
    over = dict(n_experts=16, n_group=8, topk_group=3, experts_per_token=4, n_shared_experts=2)
    whole = tiny(**over, experts_held=16)
    mp, x = layer_of(whole)
    routed_only = {k: v for k, v in mp.items() if k != "shared"}
    shared = moe.expert_layer(mp, whole, x)[0] - moe.expert_layer(routed_only, whole, x)[0]
    total, pairs, sent = np.asarray(shared)[0], 0, np.zeros((48, 8), bool)
    for i in range(8):
        cfg = tiny(**over, experts_held=2, expert_offset=2 * i)
        mine = {**mp, **{k: mp[k][2 * i: 2 * i + 2] for k in ("w_gate", "w_up", "w_down")}}
        out, picks, counts, groups = moe.expert_layer(mine, cfg, x)
        assert np.abs(np.asarray(out)[0] - reference_layer(cfg, mp, x, 2, 2 * i)).max() < F32
        assert picks.shape == (1, 48, 4) and int(picks.max()) < 16      # routed over ALL experts
        sent[:, i] = (np.asarray(picks)[0] // 2 == i).any(-1)
        total = total + np.asarray(out)[0] - np.asarray(shared)[0]
        pairs += int(counts[1])
    assert np.abs(total - reference_layer(whole, mp, x, 16, 0)).max() < F32
    assert pairs == 48 * 4                 # every pair is held by exactly one share
    assert sent.sum(-1).max() <= 3         # a token reaches at most topk_group devices


def test_group_limited_picks_stay_in_the_best_groups_and_one_group_is_plain_top_k():
    rng = np.random.default_rng(11)
    scores = jax.nn.softmax(jnp.asarray(rng.standard_normal((64, 24)) * 2, jnp.float32), axis=-1)
    kept, groups = moe.group_limited(scores, n_group=6, topk_group=2)
    best = np.argsort(-np.asarray(scores).reshape(64, 6, 4).max(-1), axis=-1)[:, :2]
    assert (np.sort(np.asarray(groups), -1) == np.sort(best, -1)).all()
    picks = np.asarray(jax.lax.top_k(kept, 5)[1])
    assert all(set(p // 4) <= set(g) for p, g in zip(picks, np.asarray(groups)))
    # one group: nothing is limited, the picks are plain top-k of the scores
    same, only = moe.group_limited(scores, n_group=1, topk_group=1)
    assert (np.asarray(same) == np.asarray(scores)).all() and (np.asarray(only) == 0).all()
    cfg = tiny(n_experts=16, experts_held=16, n_group=1, topk_group=1, experts_per_token=4)
    mp, x = layer_of(cfg)
    _, picks, _ = moe.expert_layer(mp, cfg, x)        # three results: no groups to hand back
    plain = jax.lax.top_k(jax.nn.softmax(x[0] @ mp["router"]["kernel"], -1), 4)[1]
    assert (np.asarray(picks)[0] == np.asarray(plain)).all()


def test_gates_are_unnormalised_and_scaled_and_shared_experts_are_summed():
    """The three config fields the Cohere family sets the other way."""
    from sentio_tpu.models.cohere2_moe import Cohere2MoeConfig

    c = Cohere2MoeConfig.tiny()
    assert (c.n_group, c.topk_group, c.norm_topk_prob, c.routed_scaling_factor, c.shared_combine) == \
        (1, 1, True, 1.0, "mean")
    d = tiny(routed_scaling_factor=16.0)
    assert (d.norm_topk_prob, d.shared_combine, DeepseekV2Config().routed_scaling_factor) == (False, "sum", 16.0)
    mp, x = layer_of(d)
    routed_only = {k: v for k, v in mp.items() if k != "shared"}
    out16 = np.asarray(moe.expert_layer(routed_only, d, x)[0])
    out1 = np.asarray(moe.expert_layer(routed_only, tiny(routed_scaling_factor=1.0), x)[0])
    np.testing.assert_allclose(out16, 16 * out1, rtol=1e-5, atol=1e-6)
    flat = x[0]
    sp = mp["shared"]
    want = sum((jax.nn.silu(flat @ sp["w_gate"][j]) * (flat @ sp["w_up"][j])) @ sp["w_down"][j] for j in range(2))
    got = moe.expert_layer(mp, d, x)[0] - moe.expert_layer(routed_only, d, x)[0]
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want), rtol=1e-4, atol=2e-5)


# ------------------------------------------------------- what is refused


def test_int8_pages_speculation_and_a_mesh_refuse_this_family_by_name():
    cfg = tiny()
    tree = seeded(cfg)
    with pytest.raises(ValueError, match="latent pool .DeepseekV2Config. is bf16"):
        ContinuousBatchingEngine(model_config=cfg, params=tree, kv_quant="int8")
    with pytest.raises(ValueError, match="speculation does not serve a routed family .DeepseekV2Config."):
        ContinuousBatchingEngine(model_config=cfg, params=tree, draft_params=tree, draft_config=cfg)
    with pytest.raises(ValueError, match="int8 latents have no kernel"):
        init_pool(cfg, 4, 8, quantized=True)
    from sentio_tpu.config import MeshConfig
    from sentio_tpu.parallel.mesh import build_mesh
    from sentio_tpu.runtime.weights import WeightsError, load_decoder

    mesh = build_mesh(MeshConfig(tp_size=2), devices=jax.devices()[:2])
    with pytest.raises(WeightsError, match="a deepseek_v2 model is served on one device"):
        load_decoder(mesh=mesh, model_config=cfg)
    with pytest.raises(ValueError, match="no heads to split"):
        ContinuousBatchingEngine(model_config=cfg, params=tree, mesh=mesh)


# ------------------------------------------------------- bf16, as served


def test_bf16_as_served_passes_and_one_precision_down_fails():
    """bf16 weights and a bf16 latent pool, forced picks: prefill and decode
    through the pool within ``BF16_TOL`` of the float32 reference on the true
    weights; the same program on fp8-rounded matrices is far outside it."""
    cfg = DeepseekV2Config.tiny(dim=128, q_lora_rank=64, kv_lora_rank=64, mlp_dim=256, moe_mlp_dim=64)
    tree, ids = seeded(cfg, as_checkpoint=True), ids_of(cfg, 44)

    def run(served_tree):
        got, picks = through_the_pages(cfg, served_tree, ids, 28, use_pallas=False)
        want, _ = ref_forward(cfg, tree, ids, forced={k: jnp.asarray(v) for k, v in picks.items()})
        return rel_rms(got[:28], want[:28]), rel_rms(got[28:], want[28:])

    prefill, decode = run(tree)
    assert prefill < BF16_TOL and decode < BF16_TOL, (prefill, decode)
    worse = run(degrade(tree, "weights_fp8", family.is_matrix))
    assert min(worse) > 2 * BF16_TOL, worse
