"""The family ``lfm2_moe`` (models/lfm2_moe.py: gated short convolutions beside
attention, routed experts picked under a selection-only bias; the convolution
state per slot and per page in runtime/paged.py; the lane-packed pool for
64-wide heads) against its plain reference (benchmark/lfm2_moe_reference.py),
at a small size on the CPU: seeded random weights, logits and not tokens.

Tolerances, each with its reason. In FLOAT32 (``F32``) program and reference
compute the same function from the same numbers and differ by the order of
their sums: logits of size 0.01-0.1 agree to 3e-5 (measured: under 2e-6), and a
greedy token may differ from the reference's only where the two best logits
lie within that (``GAP``). Two paths of the PROGRAM (whole and chunked, cold
and behind a radix hit) round ``z`` alike and sum the taps in one order, so
their answers are the same tokens and their log-probabilities agree to 2e-4.
"""

import dataclasses
import functools
import inspect
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import lfm2_moe_reference as reference  # noqa: E402
from benchmark.check import choice_agreement  # noqa: E402
from benchmark.families import lfm2_moe as family  # noqa: E402
from sentio_tpu.models import lfm2_moe as M  # noqa: E402
from sentio_tpu.models import moe  # noqa: E402
from sentio_tpu.models.lfm2_moe import CONV, FULL, Lfm2MoeConfig, init_lfm2_moe, lfm2_forward  # noqa: E402
from sentio_tpu.runtime.paged import ContinuousBatchingEngine, init_pool, paged_decode_forward  # noqa: E402

F32, GAP = 3e-5, 3e-5
PAGE = 8
# jitted: eager, a forward of a few hundred small operations takes ten times as long here
forward = jax.jit(lfm2_forward, static_argnums=1, static_argnames=("cache_index",))


def tiny(**over) -> Lfm2MoeConfig:
    return dataclasses.replace(Lfm2MoeConfig.tiny(), dtype="float32", **over)


def seeded(cfg, seed=0):
    return init_lfm2_moe(jax.random.PRNGKey(seed), cfg)


def ref_kwargs(cfg, **over) -> dict:
    """The reference's keywords are the config's own field names."""
    fields = dataclasses.asdict(cfg)
    wanted = [p.name for p in inspect.signature(reference.forward).parameters.values()
              if p.kind is p.KEYWORD_ONLY]
    return {**{k: fields[k] for k in wanted}, **over}


def ref_forward(cfg, tree, ids, forced=None, **over):
    """The plain reference on one sequence → (logits [T, V], {"experts": [Lr, T, E]})."""
    params = jax.tree.map(jnp.asarray, family.reference_params(jax.device_get(tree), cfg.n_layers))
    logits, scores = jax.jit(functools.partial(reference.forward, **ref_kwargs(cfg, **over)))(
        params, jnp.asarray(ids), forced)
    return np.asarray(logits), {k: np.asarray(v) for k, v in scores.items()}


def ids_of(cfg, n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size))


def engine_of(cfg, tree, **over):
    return ContinuousBatchingEngine(**{**dict(
        model_config=cfg, params=tree, max_slots=2, page_size=PAGE, max_pages_per_seq=16,
        steps_per_tick=4), **over})


def through_the_pages(cfg, tree, ids, n_prompt, use_pallas=False, rows=1):
    """``n_prompt`` tokens prefilled into the pool (right-padded to whole
    pages), the rest decoded through it one at a time → (logits [T, V], picks
    [Lr, T, k], the pool's state at the end)."""
    engine = engine_of(cfg, tree, use_pallas=use_pallas, max_slots=rows)
    width = -(-n_prompt // PAGE) * PAGE
    state, prefill, decode = family.paged_pieces(engine, cfg, rows, width)
    table = np.zeros((rows, 16), np.int32)
    table[0] = 1 + np.arange(16)
    row_ids = np.zeros((rows, width), np.int32)
    row_ids[0, :n_prompt] = ids[:n_prompt]
    lens = np.asarray([n_prompt] + [1] * (rows - 1), np.int32)
    logits, state, picks = prefill(engine.params, row_ids, np.broadcast_to(np.arange(width), (rows, width)),
                                   lens, table[:, : width // PAGE], state)
    got, chosen = [np.asarray(logits)[0, :n_prompt]], [np.asarray(picks["experts"])[:, 0, :n_prompt]]
    for t in range(n_prompt, len(ids)):
        tok = np.asarray([ids[t]] + [0] * (rows - 1), np.int32)
        logits, state, picks = decode(engine.params, tok, np.asarray([t] + [0] * (rows - 1), np.int32), table, state)
        got.append(np.asarray(logits)[:1])
        chosen.append(np.asarray(picks["experts"])[:, :1])
    return np.concatenate(got), np.concatenate(chosen, axis=1), state


# --------------------------------------------------- (1) (2) the two forwards


def test_contiguous_forward_is_the_reference():
    """(1) 40 tokens through every kind of block — conv and dense, conv and
    routed, attention and routed — and the picks it hands back."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got, _, routed = forward(tree, cfg, jnp.asarray(ids)[None])
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(np.asarray(got)[0] - want).max() < F32
    picks = np.asarray(routed["experts"])[:, 0]
    assert picks.shape == (cfg.n_routed_layers, 40, cfg.experts_per_token)
    assert choice_agreement(picks, scores["experts"], cfg.experts_per_token)[1] == 0
    held = cfg.n_routed_layers * cfg.n_experts
    assert routed["counts"].tolist() == [40 * 2 * 2, 40 * 2 * 2, held, held]


def test_the_references_own_controls_are_seen():
    """What the comparison catches: the reference told another theta, told
    not to renormalise, or given another normaliser departs at once."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got = np.asarray(forward(tree, cfg, jnp.asarray(ids)[None])[0])[0]
    for over in ({"rope_theta": 500.0}, {"norm_topk_prob": False}, {"norm_topk_eps": 0.5}):
        assert np.abs(got - ref_forward(cfg, tree, ids, **over)[0]).max() > 10 * F32, over


@pytest.mark.parametrize("use_pallas,heads", [(False, (4, 2)), (True, (8, 8))],
                         ids=["xla-gather", "pallas-walk-lane-packed"])
def test_prefill_then_decode_through_the_pages_is_the_reference(use_pallas, heads):
    """(2) 13 tokens prefilled (one page and five of the next), 30 decoded
    through the pool — over three page boundaries, every one leaving its tail
    — against the reference's full forward: by the gather path, and by the
    Pallas walk (interpreted) over a LANE-PACKED pool (8 kv heads of 16, eight
    to a 128-lane row)."""
    cfg = tiny(dim=16 * heads[0], n_heads=heads[0], n_kv_heads=heads[1])
    tree, ids = seeded(cfg), ids_of(cfg, 43)
    got, picks, state = through_the_pages(cfg, tree, ids, 13, use_pallas)
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(got - want).max() < F32
    assert choice_agreement(picks, scores["experts"], cfg.experts_per_token)[1] == 0
    assert state[0].shape[-2:] == ((1, 128) if use_pallas else (2, 16))


def test_a_page_that_decode_filled_holds_the_tail_prefill_would_have_written():
    """(4, second half) 13 tokens prefilled and 27 decoded fill pages 2..5;
    40 tokens prefilled whole fill the same pages: every tail is the same."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    *_, decoded = through_the_pages(cfg, tree, ids, 13)
    *_, prefilled = through_the_pages(cfg, tree, ids, 40)
    tails = [np.asarray(state[3])[:, 1:6] for state in (decoded, prefilled)]   # pages 1..5: 40 tokens
    assert np.abs(tails[0]).max() > 0 and np.abs(tails[0] - tails[1]).max() < 1e-5
    # and the slot's own state is z at the last two positions, whoever computed it
    assert np.abs(np.asarray(decoded[2]) - np.asarray(prefilled[2])).max() < 1e-5
    assert np.abs(np.asarray(prefilled[2])[:, 0] - tails[1][:, -1]).max() == 0  # 40 tokens end a page


# ------------------------------------ (3) (4) chunks and radix hits, served


PROMPT = "a prompt long enough to take five segments of sixteen tokens, all told."


def test_a_prompt_prefilled_in_chunks_is_one_prefilled_whole():
    """(3) The same prompt admitted whole and in segments of 16, each later
    one starting from the tail its predecessor left: the same greedy answer,
    the same log-probabilities. With the carried state taken out (every
    segment from zeros) the answers part."""
    cfg = tiny()
    tree = seeded(cfg)
    whole = engine_of(cfg, tree).run_all([PROMPT], max_new_tokens=12)[0]
    chunked_engine = engine_of(cfg, tree, prefill_chunk=16)
    chunked = chunked_engine.run_all([PROMPT], max_new_tokens=12)[0]
    assert whole.prefill_segments == 1 and chunked.prefill_segments == 5
    assert whole.tokens == chunked.tokens
    assert chunked.logprob_sum == pytest.approx(whole.logprob_sum, abs=2e-4)
    stats = chunked_engine.stats()
    assert (stats["conv_state_zero"], stats["conv_state_tail"], stats["conv_state_carried"]) == (1, 0, 4)
    # 72 prompt tokens fill 9 pages, the 12-token answer fills one more
    assert stats["conv_state_pages"] == 9 + 1



def test_a_later_segment_needs_the_state_its_predecessor_left():
    """(3) What "carried" means, at the forward: 32 tokens whole, and as two
    segments of 16 over one cache. From the state the first segment handed
    back, the second's logits are the whole prompt's; from zeros they are not."""
    cfg = tiny()
    tree, ids = seeded(cfg), jnp.asarray(ids_of(cfg, 32))[None]
    whole = np.asarray(forward(tree, cfg, ids, cache=M.init_lfm2_cache(cfg, 1, 32, 4))[0])[0]
    positions = jnp.arange(32)[None]
    _, cache, _ = forward(tree, cfg, ids[:, :16], positions=positions[:, :16],
                          cache=M.init_lfm2_cache(cfg, 1, 32, 2))
    carried = np.asarray(forward(tree, cfg, ids[:, 16:], positions=positions[:, 16:], cache=cache,
                                 cache_index=16)[0])[0]
    assert np.abs(carried - whole[16:]).max() < F32
    # the first segment's second page tail IS the state it hands on
    assert np.abs(np.asarray(cache["tail"])[:, 0, 1] - np.asarray(cache["conv"])[:, 0]).max() == 0
    from_zero = {**cache, "conv": jnp.zeros_like(cache["conv"])}
    lost = np.asarray(forward(tree, cfg, ids[:, 16:], positions=positions[:, 16:], cache=from_zero,
                              cache_index=16)[0])[0]
    assert np.abs(lost - whole[16:]).max() > 100 * F32


@pytest.mark.parametrize("pages", [1, 2])
def test_a_radix_hit_of_whole_pages_is_no_hit(pages):
    """(4) A second prompt that shares its first ``pages`` pages with a cached
    one starts from that page's stored tail: its answer and log-probabilities
    are those of an engine that never saw the first prompt. A zeroed tail
    (what prefilling a hit from zero state would be) parts them."""
    cfg = tiny()
    tree = seeded(cfg)
    head = "the quick brown fox jumps over the lazy dog"[: pages * PAGE - 1]      # + BOS: whole pages
    first, second = head + " and then some more text.", head + "! another tail, other words"
    engine = engine_of(cfg, tree)          # one engine, three lives: ``reset`` keeps its compiled programs
    cold = engine.run_all([second], max_new_tokens=12)[0]
    engine.reset()
    engine.run_all([first], max_new_tokens=4)
    warm = engine.run_all([second], max_new_tokens=12)[0]
    assert warm.prefix_hit_tokens == pages * PAGE and cold.prefix_hit_tokens == 0
    assert warm.tokens == cold.tokens
    assert warm.logprob_sum == pytest.approx(cold.logprob_sum, abs=2e-4)
    assert engine.stats()["conv_state_tail"] == 1

    engine.reset()
    engine.run_all([first], max_new_tokens=4)
    engine.pool.tail = jnp.zeros_like(engine.pool.tail)
    lost = engine.run_all([second], max_new_tokens=12)[0]
    assert lost.prefix_hit_tokens == pages * PAGE
    assert abs(lost.logprob_sum - cold.logprob_sum) > 1e-3


def test_served_answers_are_the_reference():
    """Through ``run_all``: a chunked prompt, a second over the head the cache
    kept, a third beside it in one fused tick. Every greedy token is the
    reference's own choice (or within ``GAP`` of it), the log-probabilities
    agree, and the picks handed back are the reference's."""
    cfg = tiny()
    tree = seeded(cfg)
    engine = engine_of(cfg, tree, prefill_chunk=16)
    head = "the quick brown fox jumps over the lazy dog. "
    prompts = [head + "abc", head + "xyzw", "short"]
    results = engine.run_all(prompts[:1], max_new_tokens=20, return_choices=True) \
        + engine.run_all(prompts[1:], max_new_tokens=20, return_choices=True)
    assert results[1].prefix_hit_tokens >= 40
    for res, prompt in zip(results, prompts):
        ids = np.asarray(engine.tokenizer.encode(prompt, add_bos=True) + list(res.tokens))
        want, scores = ref_forward(cfg, tree, ids)
        sampled = list(res.tokens) + [engine.tokenizer.eos_id] * (res.finish_reason == "stop")
        rows = want[res.prompt_tokens - 1: res.prompt_tokens - 1 + len(sampled)].astype(np.float64)
        assert (rows.max(-1) - rows[np.arange(len(sampled)), sampled]).max() < GAP
        logprob = rows - np.log(np.exp(rows).sum(-1, keepdims=True))
        assert res.logprob_sum == pytest.approx(logprob[np.arange(len(sampled)), sampled].sum(), abs=1e-3)
        own = ~(res.choices["experts"] < 0).all(axis=(0, 2))
        assert not own[: res.prefix_hit_tokens].any() and own[res.prefix_hit_tokens:].all()
        assert choice_agreement(res.choices["experts"][:, own], scores["experts"][:, : len(ids) - 1][:, own],
                                cfg.experts_per_token)[1] == 0


# ------------------------------------------------- (5) the fused tick's rows


def decode_rows(cfg, tree, conv, write_mask):
    """One decode step of three rows at lens (5, 7, 6) over a fresh pool."""
    pool = init_pool(cfg, num_pages=8, page_size=PAGE, slots=3)
    table = np.asarray([[1, 2], [3, 4], [5, 6]], np.int32)
    step = jax.jit(functools.partial(paged_decode_forward, return_routed=True), static_argnums=1)
    out = step(tree, cfg, jnp.asarray([3, 4, 5]), jnp.asarray([5, 7, 6]), jnp.asarray(table),
               pool.k, pool.v, write_mask=write_mask, conv=conv, tail=pool.tail)
    return out[4], out[5]


def test_a_row_that_does_not_advance_keeps_its_state_and_writes_no_tail():
    """(5) In a sub-step of a fused tick a halted row's state does not move,
    and only a row at one of its page's last two positions leaves a tail."""
    cfg = tiny()
    tree = seeded(cfg)
    lc = len(cfg.conv_layers)
    conv = jax.random.normal(jax.random.PRNGKey(3), (lc, 3, 2, cfg.dim))
    new, tail = decode_rows(cfg, tree, conv, jnp.asarray([True, False, True]))
    new, tail = np.asarray(new), np.array(tail)
    assert np.abs(new[:, 1] - np.asarray(conv)[:, 1]).max() == 0              # the halted row
    assert np.abs(new[:, 0, 0] - np.asarray(conv)[:, 0, 1]).max() == 0        # an advancing row shifts
    assert np.abs(new[:, 0, 1]).max() > 0 and np.abs(new[:, 2, 1]).max() > 0
    # row 0 at position 5 of 8 leaves nothing; row 2 at position 6 leaves column 0 of ITS page (5);
    # row 1 stands at position 7, its page's last, and is halted: nothing
    assert np.abs(tail[:, 5, 0] - new[:, 2, 1]).max() == 0
    tail[:, 5, 0] = 0
    assert np.abs(tail).max() == 0


def test_a_right_padded_rows_state_is_taken_at_its_own_length():
    """(5) Two rows in one prefill bucket of 32, 21 and 9 tokens long: each
    row's state is z at ITS last two positions — what the row alone gives —
    and the short row's pad tail changes nothing."""
    cfg = tiny()
    tree = seeded(cfg)
    ids = np.zeros((2, 32), np.int32)
    ids[0, :21], ids[1, :9] = ids_of(cfg, 21), ids_of(cfg, 9, seed=2)
    lens = np.asarray([21, 9])

    def states(rows, width):
        cache = M.init_lfm2_cache(cfg, len(rows), width, width // PAGE)
        pad = jnp.arange(width)[None, :] < jnp.asarray(lens[rows])[:, None]
        return np.asarray(forward(tree, cfg, jnp.asarray(ids[rows, :width]), cache=cache,
                                  pad_mask=pad)[1]["conv"])

    both = states([0, 1], 32)
    assert np.abs(both[:, 0] - states([0], 24)[:, 0]).max() < 1e-5
    assert np.abs(both[:, 1] - states([1], 16)[:, 0]).max() < 1e-5
    assert np.abs(both[:, 0] - both[:, 1]).max() > 1e-3


def test_a_reused_slot_starts_clean():
    """(5) One slot serves two requests in turn: the second's answer is what
    a fresh engine gives it (its state arrives with its own admission, never
    from its predecessor) — and ``reset`` and ``spawn_fresh`` start from zeros."""
    cfg = tiny()
    tree = seeded(cfg)
    one = engine_of(cfg, tree, max_slots=1, prefix_cache=False)
    one.run_all(["the first request leaves its state behind in the slot"], max_new_tokens=9)
    assert float(jnp.abs(one.pool.conv).max()) > 0
    again = one.run_all(["a second request"], max_new_tokens=9)[0]
    one.reset()
    assert float(jnp.abs(one.pool.conv).max()) == 0 and float(jnp.abs(one.pool.tail).max()) == 0
    fresh = one.run_all(["a second request"], max_new_tokens=9)[0]       # from zeros everywhere
    assert again.tokens == fresh.tokens and again.logprob_sum == pytest.approx(fresh.logprob_sum, abs=2e-4)
    spawned = one.spawn_fresh()
    assert spawned.pool.conv.shape == one.pool.conv.shape and float(jnp.abs(spawned.pool.conv).max()) == 0


def test_the_pool_holds_attention_layers_and_the_state_beside_them():
    """The pool's layer axis counts ATTENTION layers; the state's arrays are
    in ``hbm_bytes``, in ``stats`` and in the benchmark's own count, to the
    byte; at the published widths 4,096 B a token and 64 KB a page."""
    cfg = Lfm2MoeConfig()
    pool = init_pool(cfg, num_pages=5, page_size=128, slots=3)
    assert pool.k.shape == (2, 5, 128, 8, 64) and pool.conv.shape == (8, 3, 2, 2048)
    assert pool.tail.shape == (8, 5, 2, 2048)
    assert pool.hbm_bytes == 5 * 128 * 4096 + (5 + 3) * 65536 and pool.conv_state_bytes == 8 * 65536
    packed = init_pool(cfg, num_pages=5, page_size=128, slots=3, pack=2)
    assert packed.k.shape == (2, 5, 128, 4, 128) and packed.hbm_bytes == pool.hbm_bytes
    small = tiny()
    engine = engine_of(small, seeded(small))
    stats = engine.stats()
    assert stats["conv_state_bytes"] == (33 + 2) * 3 * 2 * small.dim * 4           # float32 here
    assert stats["pool_hbm_bytes"] == 33 * PAGE * 1 * 2 * 2 * 16 * 4 + stats["conv_state_bytes"]
    assert small.attn_layers == (2,) and small.attn_index(2) == 0 and small.conv_index(3) == 2


# ------------------------------------------------------- (6) (7) the experts


def layer_of(cfg, seed=5, tokens=48):
    mp = seeded(cfg, seed)[f"layers_{cfg.num_dense_layers}"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, tokens, cfg.dim))
    return mp, x


def test_the_bias_decides_the_picks_and_is_no_part_of_a_gate():
    """(6) With a bias as large as the scores' spread the picks are
    ``top_k(s + b)``, not ``top_k(s)``; the gates are the UNBIASED scores of
    those picks over their sum plus 1e-6 — they sum to ``Σg / (Σg + 1e-6)``."""
    cfg = tiny(n_experts=16, experts_held=16, experts_per_token=4)
    mp, x = layer_of(cfg)
    mp = {**mp, "bias": jax.random.normal(jax.random.PRNGKey(9), (16,)) * 0.3}
    out, picks, _ = moe.expert_layer(mp, cfg, x)
    s = np.asarray(jax.nn.sigmoid(x[0] @ mp["router"]["kernel"]), np.float64)
    biased = np.argsort(-(s + np.asarray(mp["bias"], np.float64)), axis=-1)[:, :4]
    plain = np.argsort(-s, axis=-1)[:, :4]
    assert (np.sort(np.asarray(picks)[0]) == np.sort(biased)).all()
    assert (np.sort(biased) != np.sort(plain)).any(axis=-1).mean() > 0.5
    # the layer's output is the reference's with those picks: gates hold no bias
    params = {"router": mp["router"]["kernel"], "bias": mp["bias"],
              **{k: mp[k] for k in ("w_gate", "w_up", "w_down")}}
    keys = ("experts_per_token", "norm_topk_prob", "norm_topk_eps", "routed_scaling_factor",
            "experts_held", "expert_offset")
    with jax.default_matmul_precision("highest"):
        want, ranked = reference.experts(x[0], params, None, **{k: getattr(cfg, k) for k in keys})
    assert np.abs(np.asarray(out)[0] - np.asarray(want)).max() < F32
    assert np.abs(np.asarray(ranked) - (s + np.asarray(mp["bias"]))).max() < 1e-6
    # identity experts (gate-free): the output's size is the gates' sum — under 1 by the 1e-6
    g = np.take_along_axis(s, biased, axis=-1)
    assert ((g / (g.sum(-1, keepdims=True) + 1e-6)).sum(-1) < 1).all()
    no_eps = dataclasses.replace(cfg, norm_topk_eps=0.0)
    assert np.abs(np.asarray(moe.expert_layer(mp, no_eps, x)[0]) - np.asarray(out)).max() < 1e-5
    big_eps = dataclasses.replace(cfg, norm_topk_eps=1.0)
    assert np.abs(np.asarray(moe.expert_layer(mp, big_eps, x)[0]) - np.asarray(out)).max() > 1e-3


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """(7) THE SHARE TEST for this family's routing: two shares of half the
    experts, each routed over ALL of them under the bias, add up to the whole
    layer; every pair is held by exactly one share."""
    cfg = tiny(n_experts=8, experts_held=8)
    mp, x = layer_of(cfg)
    whole, picks, counts = moe.expert_layer(mp, cfg, x)
    total, pairs = 0.0, 0
    for i in range(2):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=4 * i)
        mine = {**mp, **{k: mp[k][4 * i: 4 * i + 4] for k in ("w_gate", "w_up", "w_down")}}
        out, share_picks, n = moe.expert_layer(mine, share, x)
        assert (np.asarray(share_picks) == np.asarray(picks)).all() and int(share_picks.max()) >= 4
        total, pairs = total + np.asarray(out), pairs + int(n[1])
    assert np.abs(total - np.asarray(whole)).max() < F32
    assert pairs == int(counts[0]) == 48 * cfg.experts_per_token


# ------------------------------------------------------------- (8) refusals


def test_speculation_and_a_mesh_are_refused_with_their_reasons():
    cfg = tiny()
    tree = seeded(cfg)
    with pytest.raises(ValueError, match="recurrent state .Lfm2MoeConfig.*roll the convolution state back"):
        ContinuousBatchingEngine(model_config=cfg, params=tree, draft_params=tree, draft_config=cfg)
    from sentio_tpu.runtime.paged_spec import build_spec_tick

    with pytest.raises(ValueError, match="recurrent state"):
        build_spec_tick(lfm2_forward, cfg, lfm2_forward, cfg, eos_id=2, ignore_eos=False, page_size=PAGE)
    from sentio_tpu.config import MeshConfig
    from sentio_tpu.parallel.mesh import build_mesh
    from sentio_tpu.runtime.weights import WeightsError, load_decoder

    mesh = build_mesh(MeshConfig(tp_size=2), devices=jax.devices()[:2])
    with pytest.raises(WeightsError, match="a lfm2_moe model is served on one device"):
        load_decoder(mesh=mesh, model_config=cfg)
    with pytest.raises(ValueError, match="convolution state .Lfm2MoeConfig. is served on one device"):
        ContinuousBatchingEngine(model_config=cfg, params=tree, mesh=mesh)
    with pytest.raises(ValueError, match="no rule under a mesh"):
        init_pool(cfg, 4, PAGE, mesh=mesh, slots=2)


def test_the_config_takes_its_layer_types_as_a_list_and_hard_codes_no_period():
    cfg = tiny(n_layers=5, layer_types=[FULL, CONV, CONV, CONV, FULL], num_dense_layers=1)
    assert cfg.kinds == [FULL, CONV, CONV, CONV, FULL] and cfg.layer_types == ",".join(cfg.kinds)
    assert cfg.attn_layers == (0, 4) and cfg.conv_layers == (1, 2, 3) and cfg.n_routed_layers == 4
    tree = seeded(cfg)
    assert "attn" in tree["layers_0"] and "mlp" in tree["layers_0"] and "moe" in tree["layers_1"]
    ids = ids_of(cfg, 24)
    got = np.asarray(forward(tree, cfg, jnp.asarray(ids)[None])[0])[0]
    assert np.abs(got - ref_forward(cfg, tree, ids)[0]).max() < F32
    with pytest.raises(ValueError, match="layer types"):
        tiny(layer_types=[CONV] * 3)


def test_the_state_counters_reach_metrics_and_no_other_family_has_the_series():
    """``sentio_tpu_conv_state_starts_total{kind}`` and
    ``sentio_tpu_conv_tail_pages_total`` as ``/metrics`` exports them, from a
    harvested tick's ``conv_state``; a tick of a family without the state
    (zeros) makes no series."""
    from sentio_tpu.infra.metrics import MetricsCollector

    m = MetricsCollector()
    rows = {"useful": 3, "halted": 4, "empty": 9}
    m.record_row_steps(rows, conv_state={"zero": 0, "tail": 0, "carried": 0, "pages": 0})
    assert b"sentio_tpu_conv_state_starts_total{kind=" not in m.export_prometheus()
    m.record_row_steps(rows, conv_state={"zero": 1, "tail": 5, "carried": 2, "pages": 7})
    text = m.export_prometheus()
    assert b'sentio_tpu_conv_state_starts_total{kind="tail"} 5.0' in text
    assert b'sentio_tpu_conv_state_starts_total{kind="carried"} 2.0' in text
    assert b"sentio_tpu_conv_tail_pages_total 7.0" in text
    snap = m.export_json()["counters"]
    assert snap["conv_starts('zero',)"] == 1.0 and snap["conv_tail_pages()"] == 7.0
