"""The family ``cohere2_moe`` (models/cohere2_moe.py, the expert layer of
models/moe.py, the parallel-block walk and the windows of runtime/paged.py and
kernels/paged_attention.py) against its plain reference
(benchmark/command_a_reference.py), at a small size on the CPU: seeded random
weights, logits and not tokens.

Tolerances, each with its reason. In FLOAT32 (``F32``) program and reference
compute the same function from the same numbers and differ by the order of
their sums: logits of size 0.1–1 agree to 2e-5 (measured: under 2e-6), and a
greedy token may differ from the reference's only where the two best logits
lie within that (``GAP``). In BF16, as served, at hidden 64 the pooled
relative error over forced picks reads 0.5–0.8 %: ``BF16_TOL`` 0.015 is twice
that and under what one precision down gives (fp8-rounded expert matrices:
4 % and more) — the control at the end.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import command_a_reference as reference  # noqa: E402
from benchmark.check import choice_agreement, degrade, rel_rms  # noqa: E402
from benchmark.families import cohere2_moe as family  # noqa: E402
from sentio_tpu.models import moe  # noqa: E402
from sentio_tpu.models.cohere2_moe import (  # noqa: E402
    FULL, SLIDING, Cohere2MoeConfig, cohere2_forward, init_cohere2_moe)
from sentio_tpu.runtime.paged import ContinuousBatchingEngine  # noqa: E402

F32, GAP, BF16_TOL = 2e-5, 2e-5, 0.015
KINDS = {"mixed": f"{SLIDING},{FULL}", "sliding": f"{SLIDING},{SLIDING}", "full": f"{FULL},{FULL}"}


def tiny(**over) -> Cohere2MoeConfig:
    return Cohere2MoeConfig.tiny(**{"dtype": "float32", **over})


def seeded(cfg, seed=0, as_checkpoint=False):
    tree = init_cohere2_moe(jax.random.PRNGKey(seed), cfg)
    if as_checkpoint:  # matrices in bf16, norm scales float32
        tree = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if family.is_matrix(a) else a, tree)
    return tree


def ref_forward(cfg, tree, ids, forced=None, **over):
    """The plain reference on one sequence, given the program's share."""
    kwargs = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                  rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, layer_types=cfg.kinds,
                  sliding_window=cfg.sliding_window, experts_per_token=cfg.experts_per_token,
                  experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
                  logit_scale=cfg.logit_scale)
    params = jax.tree.map(jnp.asarray, family.reference_params(jax.device_get(tree), cfg.n_layers))
    logits, scores = reference.forward(params, jnp.asarray(ids), forced, **{**kwargs, **over})
    return np.asarray(logits), np.asarray(scores["experts"])


def ids_of(cfg, n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size))


# ------------------------------------------------ (a), (f), (g): the forward


@pytest.mark.parametrize("kinds", sorted(KINDS))
def test_contiguous_forward_is_the_reference(kinds):
    """(a) and (f): the whole forward, 40 tokens past a window of 24 — a
    sliding and a full layer together, then the interleaved rotary and the
    rotation-free layer each alone — and the picks it hands back."""
    cfg = tiny(layer_kinds=KINDS[kinds])
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got, _, routed = cohere2_forward(tree, cfg, jnp.asarray(ids)[None])
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(np.asarray(got)[0] - want).max() < F32
    picks = np.asarray(routed["experts"])[:, 0]                       # [L, T, k]
    assert choice_agreement(picks, scores, cfg.experts_per_token)[1] == 0
    assert routed["counts"].tolist() == [40 * 2 * 4, 40 * 2 * 4, 2 * 16, 2 * 16]


def test_a_window_that_is_ignored_is_seen():
    """The control of the window: the reference told the window is wider than
    the sequence departs from the program at once."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got = np.asarray(cohere2_forward(tree, cfg, jnp.asarray(ids)[None])[0])[0]
    wide = ref_forward(cfg, tree, ids, sliding_window=10_000)[0]
    assert np.abs(got[:24] - wide[:24]).max() < F32       # until the window bites ...
    assert np.abs(got[30:] - wide[30:]).max() > 100 * F32  # ... and not after


def test_head_is_the_embedding_times_the_logit_scale():
    """(g): no ``lm_head`` leaf; ``logit_scale`` scales the logits and nothing else."""
    cfg, half = tiny(), tiny(logit_scale=0.5)
    tree, ids = seeded(cfg), ids_of(cfg, 12)
    assert "lm_head" not in tree
    one = np.asarray(cohere2_forward(tree, cfg, jnp.asarray(ids)[None])[0])[0]
    scaled = np.asarray(cohere2_forward(tree, half, jnp.asarray(ids)[None])[0])[0]
    np.testing.assert_allclose(scaled, 0.5 * one, rtol=1e-6, atol=1e-7)
    assert np.abs(scaled - ref_forward(half, tree, ids)[0]).max() < F32


# ------------------------------------------ (b): through the pages, both paths


def through_the_pages(cfg, tree, ids, prompt: int, use_pallas: bool, page: int = 8):
    """Teacher-forced: the family's contiguous prefill piece over ``prompt``
    tokens scattered into pages, then a decode step through the pool for each
    further token of ``ids`` → (logits [T, V], picks [L, T, k])."""
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
    out, chosen = [np.asarray(logits)[0, :prompt]], [np.asarray(picks["experts"])[:, 0, :prompt]]
    for t in range(prompt, len(ids)):
        logits, state, picks = decode(engine.params, ids[t: t + 1].astype(np.int32),
                                      np.asarray([t], np.int32), table, state)
        out.append(np.asarray(logits))
        chosen.append(np.asarray(picks["experts"])[:, :1])
    return np.concatenate(out), np.concatenate(chosen, axis=1)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla-gather", "pallas-walk"])
def test_prefill_then_decode_through_the_pages_is_the_reference(use_pallas):
    """(b): 20 tokens prefilled, 30 decoded through the pool — past the window
    of 24 and over a window's first block that is not block 0 — against the
    reference's full forward, by the gather path and by the Pallas walk
    (interpreted)."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 50)
    got, picks = through_the_pages(cfg, tree, ids, 20, use_pallas)
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(got - want).max() < F32
    assert choice_agreement(picks, scores, cfg.experts_per_token)[1] == 0


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla-gather", "pallas-walk"])
def test_served_answers_are_the_reference_and_carry_their_picks(use_pallas):
    """(b) and (e) through ``run_all``: chunked prefill over priors (a
    50-token prompt in segments of 16), a second request over the head the
    radix cache kept, both decoding past the window. Every greedy token is the
    reference's own choice (or within ``GAP`` of it), the log-probabilities
    agree, and the picks ``run_all`` hands back are those of the reference on
    the same tokens — negative exactly where the radix cache served."""
    cfg = tiny(vocab_size=512)
    engine = ContinuousBatchingEngine(
        model_config=cfg, params=seeded(cfg), max_slots=2, page_size=8, max_pages_per_seq=12,
        steps_per_tick=4, prefill_chunk=16, use_pallas=use_pallas)
    head = "the quick brown fox jumps over the lazy dog. "
    first = engine.run_all([head + "abc"], max_new_tokens=20, return_choices=True)
    rest = engine.run_all([head + "xyzw", "short"], max_new_tokens=20, return_choices=True)
    assert rest[0].prefix_hit_tokens >= 40 and first[0].prefix_hit_tokens == 0
    for res, prompt in zip(first + rest, [head + "abc", head + "xyzw", "short"]):
        ids = np.asarray(engine.tokenizer.encode(prompt, add_bos=True) + list(res.tokens))
        want, scores = ref_forward(cfg, engine_tree(engine), ids)
        rows = want[res.prompt_tokens - 1: res.prompt_tokens - 1 + len(res.tokens)].astype(np.float64)
        assert (rows.max(-1) - rows[np.arange(len(res.tokens)), res.tokens]).max() < GAP
        logprob = rows - np.log(np.exp(rows).sum(-1, keepdims=True))
        assert res.logprob_sum == pytest.approx(
            logprob[np.arange(len(res.tokens)), res.tokens].sum(), abs=1e-3)
        picks = res.choices["experts"]
        assert picks.shape == (cfg.n_layers, len(ids) - 1, cfg.experts_per_token)
        served = (picks < 0).all(axis=(0, 2))
        assert served[: res.prefix_hit_tokens].all() and not served[res.prefix_hit_tokens:].any()
        own = ~served
        assert choice_agreement(picks[:, own], scores[:, : len(ids) - 1][:, own],
                                cfg.experts_per_token)[1] == 0
    assert engine.run_all(["again"], max_new_tokens=4)[0].choices is None  # only when asked


def engine_tree(engine):
    """The canonical tree back out of an engine's serving tree."""
    out = dict(engine.params)
    for name, lp in engine.params.items():
        if name.startswith("layers_"):
            out[name] = {**lp, "attn": {k[:2] if k.endswith("_t") else k:
                                        ({"kernel": w["kernel"].T} if k.endswith("_t") else w)
                                        for k, w in lp["attn"].items()}}
    return out


# --------------------------------------------- (c), (d): the expert layer


def layer_of(cfg, seed=3, tokens=24):
    tree = seeded(cfg, seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, tokens, cfg.dim), jnp.float32)
    return tree["layers_0"]["moe"], x


def reference_layer(cfg, mp, x, held, offset):
    lp = {"router": mp["router"]["kernel"], **{k: mp[k][offset: offset + held] for k in ("w_gate", "w_up", "w_down")},
          **{f"shared_{k[2:]}": mp["shared"][k] for k in ("w_gate", "w_up", "w_down")}}
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.experts(x[0], lp, cfg.experts_per_token, held, offset, None)[0])


@pytest.mark.parametrize("shares", [8, 4])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(shares):
    """(c): 16 experts, 4 a token, 2 shared. What each of the eight (or four)
    chips of a deployment computes — the routed part of ITS experts — summed,
    plus the shared part counted once, is the uncut reference's layer output;
    and each share is the reference given that share."""
    whole = tiny(n_experts=16, experts_per_token=4, n_shared_experts=2, experts_held=16)
    mp, x = layer_of(whole)
    held = 16 // shares
    routed_only = {k: v for k, v in mp.items() if k != "shared"}
    shared = moe.expert_layer(mp, whole, x)[0] - moe.expert_layer(routed_only, whole, x)[0]
    total, pairs = np.asarray(shared)[0], 0
    for i in range(shares):
        cfg = tiny(n_experts=16, experts_per_token=4, n_shared_experts=2,
                   experts_held=held, expert_offset=i * held)
        mine = {**mp, **{k: mp[k][i * held: (i + 1) * held] for k in ("w_gate", "w_up", "w_down")}}
        out, picks, counts = moe.expert_layer(mine, cfg, x)
        assert np.abs(np.asarray(out)[0] - reference_layer(cfg, mp, x, held, i * held)).max() < F32
        assert picks.shape == (1, 24, 4) and int(picks.max()) < 16  # routed over ALL experts
        total = total + np.asarray(out)[0] - np.asarray(shared)[0]
        pairs += int(counts[1])
    assert np.abs(total - reference_layer(whole, mp, x, 16, 0)).max() < F32
    assert pairs == 24 * 4  # every pair is held by exactly one share


def test_nothing_is_dropped_when_every_token_takes_one_expert():
    """(d): a router that sends EVERY token's first pick to expert 5 — 64
    tokens on one expert, where a capacity of 1.25 would keep 20 — gives the
    reference's result, and no tensor of tokens x experts x capacity exists."""
    cfg = tiny()
    mp, x = layer_of(cfg, tokens=64)
    rigged = np.zeros_like(np.asarray(mp["router"]["kernel"]))
    rigged[:, 5] = 1.0
    x = jnp.abs(x)  # every token scores expert 5 highest: sigmoid(sum |x|) > sigmoid(0)
    mp = {**mp, "router": {"kernel": jnp.asarray(rigged)}}
    out, picks, counts = moe.expert_layer(mp, cfg, x)
    assert (np.asarray(picks)[0, :, 0] == 5).all() and counts.tolist() == [256, 256, 16, 4]
    assert np.abs(np.asarray(out)[0] - reference_layer(cfg, mp, x, 16, 0)).max() < F32
    shapes = {tuple(v.aval.shape) for eqn in jax.make_jaxpr(
        lambda x: moe.expert_layer(mp, cfg, x))(x).jaxpr.eqns for v in eqn.outvars}
    assert not [s for s in shapes if len(s) == 3 and s[0] == 64 and s[1] == 16]  # no [G, E, C]


def test_a_row_that_does_not_advance_touches_no_expert():
    cfg = tiny()
    mp, x = layer_of(cfg, tokens=8)
    valid = jnp.asarray([[True] * 3 + [False] * 5])
    out, _, counts = moe.expert_layer({k: v for k, v in mp.items() if k != "shared"}, cfg, x, valid)
    assert counts[0] == 12 and counts[1] == 12 and counts[3] <= 12
    assert not np.asarray(out)[0, 3:].any() and np.asarray(out)[0, :3].any()


@pytest.mark.parametrize("k, n, rows", [
    (128, 256, 64),      # five groups' rows, one empty, and a tail that belongs to none
    (5120, 256, 64),     # a contraction the old constant cut at 4096, the rest of 1024 masked
    (256, 1536, 64),     # a matrix the old constant cut in three 512-lane tiles: one tile now
    (256, 1536, 256),    # the same under the widest row tile
])
def test_the_pallas_grouped_matmul_is_the_ragged_dot(k, n, rows):
    """The chip's grouped matmul (megablox, interpreted here) against XLA's
    ``ragged_dot``, which runs on the CPU: rows of five groups, one empty,
    and a tail that belongs to none, at the tile the rule picks: here the
    whole matrix."""
    lhs = jax.random.normal(jax.random.PRNGKey(0), (256, k), jnp.float32)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (5, k, n), jnp.float32)
    sizes = jnp.asarray([40, 0, 100, 7, 60], jnp.int32)
    assert moe.expert_tile(k, n, rows, 4, 4) == (k, n)
    got = moe.expert_matmul(lhs, rhs, sizes, rows=rows, interpret=True)[:207]
    want = jax.lax.ragged_dot(lhs, rhs, sizes, precision="highest")[:207]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4 * (k / 128) ** 0.5)


# the expert matrices ``[K, N]`` of the three routed configurations as published (``w_gate`` /
# ``w_up``, then ``w_down``), a contraction too long to stay whole and a matrix under 128 lanes;
# beside each what the rule answers at a decode step's 32 rows
MATRICES = {
    "commanda": (4096, 4096, (4096, 512)),       # the tile PR 33 swept: its programs do not change
    "lfm2-up": (2048, 1536, (2048, 1536)),       # 6.3 MB: the whole expert in one DMA
    "lfm2-down": (1536, 2048, (1536, 2048)),
    "dsv2-up": (5120, 1536, (5120, 512)),        # the contraction whole: no masked rest of 1024
    "dsv2-down": (1536, 5120, (1536, 2560)),
    "long-k": (32768, 1024, (16384, 128)),
    "narrow": (128, 64, (128, 64)),
}


@pytest.mark.parametrize("rows", [32, 64, 128, 256])        # a decode step's tile ... a large admission's
@pytest.mark.parametrize("name", sorted(MATRICES))
def test_the_expert_tile_follows_the_matrix(name, rows):
    """``expert_tile`` from shapes alone: ``tk`` is K or halves of it (no
    rest to mask), ``tn`` whole lanes that divide N, the tile inside the VMEM
    a kernel has, and no wider ``tn`` or longer ``tk`` would be."""
    k, n, at_32 = MATRICES[name]
    tk, tn = moe.expert_tile(k, n, rows)
    assert k % tk == 0 and (k // tk) & (k // tk - 1) == 0
    assert n % tn == 0 and (tn % 128 == 0 or tn == n)
    assert moe.tile_vmem(rows, tk, tn) <= moe._GMM_VMEM
    wider = [d for d in range(tn + 128, n + 1, 128) if n % d == 0]
    assert not wider or moe.tile_vmem(rows, tk, wider[0]) > moe._GMM_VMEM
    assert tk == k or moe.tile_vmem(rows, 2 * tk, 128) > moe._GMM_VMEM
    if rows == 32:
        assert (tk, tn) == at_32
    if name == "commanda":
        assert (tk, tn) == (4096, 512)       # at every row tile: the parent's constant


# --------------------------------- (h): as served, and one precision down


def pooled_error(cfg, tree, served_tree, ids):
    got, picks = through_the_pages(cfg, served_tree, ids, 24, use_pallas=False)
    want, scores = ref_forward(cfg, tree, ids, forced={"experts": jnp.asarray(picks)})
    return rel_rms(got, want), choice_agreement(picks, scores, cfg.experts_per_token)


def test_bf16_as_served_passes_and_one_precision_down_fails():
    """(h): bf16 through the pages against the reference on the checkpoint's
    own values, the program's picks forced: inside ``BF16_TOL``. With the
    matrices rounded to float8's mantissa (the experts among them) the same
    comparison fails; and with the router's scores rounded from float32 to
    bf16 the picks of a float32 program stop being the reference's."""
    cfg = Cohere2MoeConfig.tiny(dim=128, mlp_dim=64, n_experts=64, experts_held=64, experts_per_token=8)
    tree, ids = seeded(cfg, as_checkpoint=True), ids_of(cfg, 48)
    error, (pairs, differ, _) = pooled_error(cfg, tree, tree, ids)
    assert error < BF16_TOL
    low = degrade(tree, "weights_fp8", family.is_matrix)
    assert pooled_error(cfg, tree, low, ids)[0] > 2 * BF16_TOL

    # the scores, which the program computes in float32 from its bf16 input:
    # in a float32 program no pick differs from the reference's ((a), (b));
    # with the scores rounded to bf16 a rank turns at some positions in a
    # hundred (as served the bf16 INPUT of the router turns as many: the
    # check's choice limits are read on the chip for that reason)
    exact = tiny(dim=128, mlp_dim=64, n_experts=64, experts_held=64, experts_per_token=8)
    exact_tree, long_ids = seeded(exact), ids_of(exact, 384, seed=7)

    def disagreements():
        picks = np.asarray(cohere2_forward(exact_tree, exact, jnp.asarray(long_ids)[None])[2]["experts"])[:, 0]
        scores = ref_forward(exact, exact_tree, long_ids, forced={"experts": jnp.asarray(picks)})[1]
        return choice_agreement(picks, scores, exact.experts_per_token)[1]

    assert disagreements() == 0
    scores_of = moe.routed_scores
    try:
        # ``reduce_precision``: a convert to bf16 and back is folded away by XLA
        moe.routed_scores = lambda logits, fn: scores_of(
            jax.lax.reduce_precision(logits, exponent_bits=8, mantissa_bits=7), fn)
        assert disagreements() >= 5  # of 768 pairs of layer and position
    finally:
        moe.routed_scores = scores_of


# ------------------------------------------------- the window's first block


def test_the_walk_and_its_counter_start_at_the_windows_first_block():
    """``blocks_walked`` with a window against the blocks that hold a key the
    query sees, counted one by one; and the engine's ``kv_pages`` counter by
    the same rule, the mean over a sliding and a full layer."""
    from sentio_tpu.kernels.paged_attention import blocks_walked, first_block

    page, nb, window = 8, 12, 24
    lens = np.arange(0, page * nb - 1)
    seen = [len({j // page for j in range(max(0, n - window + 1), n + 1)}) for n in lens]
    assert blocks_walked(lens, page, nb, window).tolist() == seen
    assert first_block(np.asarray([0, 23, 24, 31, 32]), page, window).tolist() == [0, 0, 0, 1, 1]
    assert blocks_walked(lens, page, nb).tolist() == (lens // page + 1).tolist()  # no window: as it was

    cfg = tiny()  # window 24: a sliding and a full layer
    engine = ContinuousBatchingEngine(model_config=cfg, params=seeded(cfg), max_slots=2, page_size=page,
                                      max_pages_per_seq=nb)
    engine.slots[0].active, engine.slots[0].length = True, 60
    got = engine._kv_pages([2, 0], 2)
    # row 0 at 60 and 61: 8 blocks in the full layer, 4 in the sliding one (the other 4 it HOLDS lie behind the
    # window: a mean of 2 over the two layers, each sub-step); row 1 one block a sub-step, and nothing behind
    assert got == {"held": (8 + 4) // 2 * 2 + 2, "tabled": 2 * 2 * nb, "behind_window": (0 + 4) // 2 * 2}
