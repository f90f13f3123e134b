"""The family ``nemotron_h`` (models/nemotron_h.py: blocks of one operator
each — Mamba-2 mixers, ungated relu² experts under a selection-only bias with
a shared one, rotation-free GQA; the Mamba state per slot and in a bounded
pool of snapshots the radix cache hands to page boundaries, runtime/paged.py
and runtime/radix.py) against its plain reference
(benchmark/nemotron_h_reference.py), at a small size on the CPU: seeded random
weights, logits and not tokens.

Tolerances, each with its reason. In FLOAT32 (``F32``) program and reference
compute the same function from the same numbers and differ by the order of
their sums — the program's chunked scan sums a chunk's positions as a matrix
product where the reference walks them one by one: logits of size 0.1 agree
to 5e-5 (measured: under 4e-6). Two paths of the PROGRAM (whole and chunked,
cold and behind a snapshot) differ the same way (a snapshot is the scan's own
state at a chunk boundary), so their answers are the same tokens and their
log-probabilities agree to 2e-4.
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

from benchmark import nemotron_h_reference as reference  # noqa: E402
from benchmark.check import choice_agreement  # noqa: E402
from benchmark.families import nemotron_h as family  # noqa: E402
from sentio_tpu.models import moe  # noqa: E402
from sentio_tpu.models import nemotron_h as M  # noqa: E402
from sentio_tpu.models.llama import serving_layout  # noqa: E402
from sentio_tpu.models.nemotron_h import NemotronHConfig, init_nemotron_h, nemotron_h_forward  # noqa: E402
from sentio_tpu.runtime.paged import ContinuousBatchingEngine  # noqa: E402
from sentio_tpu.runtime.radix import RadixPrefixCache  # noqa: E402

F32 = 5e-5
PAGE = 16
# jitted: eager, a forward of a few hundred small operations takes ten times as long here
forward = jax.jit(nemotron_h_forward, static_argnums=1, static_argnames=("cache_index",))


def tiny(**over) -> NemotronHConfig:
    return dataclasses.replace(NemotronHConfig.tiny(), dtype="float32", **over)


def seeded(cfg, seed=0):
    return init_nemotron_h(jax.random.PRNGKey(seed), cfg)


def ref_forward(cfg, tree, ids, forced=None, **over):
    """The plain reference on one sequence → (logits [T, V], {"experts": [Le, T, E]})."""
    fields = dataclasses.asdict(cfg)
    wanted = [p.name for p in inspect.signature(reference.forward).parameters.values() if p.kind is p.KEYWORD_ONLY]
    params = jax.tree.map(jnp.asarray, family.reference_params(jax.device_get(tree), cfg.n_layers))
    logits, scores = jax.jit(functools.partial(reference.forward, **{**{k: fields[k] for k in wanted}, **over}))(
        params, jnp.asarray(ids), forced)
    return np.asarray(logits), {k: np.asarray(v) for k, v in scores.items()}


def ids_of(cfg, n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size))


def engine_of(cfg, tree, **over):
    return ContinuousBatchingEngine(**{**dict(
        model_config=cfg, params=tree, max_slots=2, page_size=PAGE, max_pages_per_seq=12,
        steps_per_tick=4, ssm_snapshots=4), **over})


def ssm(stats: dict) -> dict:
    """What prefill counted (the decode ticks' row updates have a test of their own)."""
    return {k.removeprefix("ssm_state_"): v for k, v in stats.items() if k.startswith("ssm_state_") and v
            and k not in ("ssm_state_bytes", "ssm_state_row_updates", "ssm_state_row_skips")}


# ----------------------------------------------- the two forms of the recurrence


@pytest.mark.parametrize("n", [40, 16, 7], ids=["two-chunks-and-a-half", "one-chunk", "under-a-chunk"])
def test_contiguous_forward_is_the_reference(n):
    """Tokens through every kind of block — the chunked scan in its matmul
    form against the recurrence walked a token at a time — and the picks it
    hands back."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, n)
    got, _, routed = forward(tree, cfg, jnp.asarray(ids)[None])
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(np.asarray(got)[0] - want).max() < F32
    picks = np.asarray(routed["experts"])[:, 0]
    assert picks.shape == (cfg.n_routed_layers, n, cfg.experts_per_token)
    assert choice_agreement(picks, scores["experts"], cfg.experts_per_token)[1] == 0


@pytest.mark.parametrize("over", [{"norm_topk_prob": False}, {"routed_scaling_factor": 1.0}, {"n_groups": 1},
                                  {"norm_eps": 1e-2}], ids=lambda o: next(iter(o)))
def test_the_references_own_controls_are_seen(over):
    """What the comparison catches: the reference told not to renormalise,
    another gate scale, one group of B and C for all heads, another epsilon."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got = np.asarray(forward(tree, cfg, jnp.asarray(ids)[None])[0])[0]
    if "n_groups" in over:   # the reference reads B and C of group 0 for every head: its widths must still split
        with pytest.raises(Exception):
            ref_forward(cfg, tree, ids, **over)
        return
    assert np.abs(got - ref_forward(cfg, tree, ids, **over)[0]).max() > 10 * F32, over


def test_the_chunked_scan_is_the_recurrence_and_hands_out_its_boundaries():
    """``ssm_scan`` over 48 positions in chunks of 16, from a non-zero state,
    with pad positions (step 0) behind 37: y and every boundary state against
    the recurrence one token at a time; the last boundary is the state after
    the 37 real tokens."""
    rng = np.random.default_rng(0)
    b, t, h, p, g, n = 2, 48, 4, 8, 2, 16
    x, bm, cm = (rng.standard_normal(s).astype(np.float32) for s in ((b, t, h, p), (b, t, g, n), (b, t, g, n)))
    step = rng.uniform(0.01, 0.5, (b, t, h)).astype(np.float32)
    step[1, 37:] = 0.0
    a = -rng.uniform(1, 4, h).astype(np.float32)
    start = rng.standard_normal((b, h, p, n)).astype(np.float32)
    y, states = M.ssm_scan(*(jnp.asarray(v) for v in (x, step, a, bm, cm, start)), 16)
    want_y, want_states = np.zeros_like(x), [start.copy()]
    state = start.copy()
    for i in range(t):
        bh, ch = (np.repeat(m[:, i], h // g, axis=1) for m in (bm, cm))                    # [b, h, n]
        state = np.exp(step[:, i] * a)[..., None, None] * state + (step[:, i, :, None] * x[:, i])[..., None] * bh[:, :, None]
        want_y[:, i] = np.einsum("bhpn,bhn->bhp", state, ch)
        if i % 16 == 15:
            want_states.append(state.copy())
        if i == 36:
            after_37 = state.copy()
    assert np.abs(np.asarray(y) - want_y).max() < 1e-4
    assert np.abs(np.asarray(states) - np.stack(want_states, axis=1)).max() < 1e-4
    # behind the pads the state stands still: the boundary at 48 is the state after 37
    assert np.abs(np.asarray(states)[1, 3] - after_37[1]).max() < 1e-4


def test_prefill_then_decode_through_the_pages_is_the_reference():
    """21 tokens prefilled (one page and five of the next), 27 decoded through
    the pool and the slot's state — the one-token update — against the
    reference's full forward, a second row of junk beside it."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 48)
    engine = engine_of(cfg, tree)
    state, prefill, decode = family.paged_pieces(engine, cfg, 2, 32)
    table = np.zeros((2, 12), np.int32)
    table[0] = 1 + np.arange(12)
    row_ids = np.zeros((2, 32), np.int32)
    row_ids[0, :21] = ids[:21]
    logits, state, picks = prefill(engine.params, row_ids, np.broadcast_to(np.arange(32), (2, 32)),
                                   np.asarray([21, 1], np.int32), table[:, :2], state)
    got, chosen = [np.asarray(logits)[0, :21]], [np.asarray(picks["experts"])[:, 0, :21]]
    idle = jax.tree.map(lambda s: np.asarray(s)[:, 1].copy(), state[2])
    for t in range(21, 48):
        logits, state, picks = decode(engine.params, np.asarray([ids[t], 0], np.int32),
                                      np.asarray([t, 0], np.int32), table, state)
        got.append(np.asarray(logits)[:1])
        chosen.append(np.asarray(picks["experts"])[:, :1])
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(np.concatenate(got) - want).max() < F32
    assert choice_agreement(np.concatenate(chosen, axis=1), scores["experts"], cfg.experts_per_token)[1] == 0
    # float32 state [Lm, rows, H, P, N] beside the convolution's three columns
    assert state[2]["ssm"].shape == (3, 2, 8, 8, 16) and state[2]["ssm"].dtype == jnp.float32
    assert state[2]["conv"].shape == (3, 2, 3, 64 + 2 * 2 * 16)
    # the pieces advance every row (the junk row's state moved with its tokens); through ``step_n`` a row
    # that does not advance keeps its state (``write_mask``): the served tests below
    assert any(np.abs(np.asarray(state[2][name])[:, 1] - idle[name]).max() > 0 for name in idle)


def test_the_picks_the_program_hands_back_are_followed():
    """The reference forced to the program's picks computes the program's
    function even where its own would differ (a bias that reverses the order)."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 24)
    for i in (1, 3, 6):   # the routed blocks: a bias large enough to decide every pick
        tree[f"layers_{i}"]["moe"]["bias"] = jnp.linspace(-4.0, 4.0, cfg.n_experts)
    got, _, routed = forward(tree, cfg, jnp.asarray(ids)[None])
    picks = np.asarray(routed["experts"])[:, 0]
    assert set(picks.ravel().tolist()) == {cfg.n_experts - 2, cfg.n_experts - 1}
    want, scores = ref_forward(cfg, tree, ids, forced={"experts": jnp.asarray(picks)})
    assert np.abs(np.asarray(got)[0] - want).max() < F32
    assert choice_agreement(picks, scores["experts"], cfg.experts_per_token)[1] == 0    # ranked by s + b


def test_two_shares_of_the_experts_add_up_to_the_uncut_block():
    """The cut of the configuration: experts 0..3 on one chip, 4..7 on the
    other, the router as wide as published on both, the shared expert on both
    — the two routed parts and the shared expert ONCE add up to the uncut
    reference's block."""
    cfg = tiny(n_layers=1, pattern="E")
    tree, ids = seeded(cfg), ids_of(cfg, 24)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 24, cfg.dim)), jnp.float32)
    mp = tree["layers_0"]["moe"]
    whole = np.asarray(moe.expert_layer(mp, cfg, x)[0])[0]
    no_shared = {k: v for k, v in mp.items() if k != "shared"}
    parts = []
    for offset in (0, 4):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=offset)
        held = {**no_shared, "w_up": mp["w_up"][offset: offset + 4], "w_down": mp["w_down"][offset: offset + 4]}
        out, picks, counts = moe.expert_layer(held, share, x)
        parts.append(np.asarray(out)[0])
        assert counts.tolist()[0] == 24 * 2 and 0 < counts.tolist()[1] < 24 * 2      # some pairs lie elsewhere
    shared = np.asarray(moe.expert_layer({**no_shared, "w_up": mp["w_up"][:0], "w_down": mp["w_down"][:0],
                                          "shared": mp["shared"]},
                                         dataclasses.replace(cfg, experts_held=0), x)[0])[0]
    assert np.abs(parts[0] + parts[1] + shared - whole).max() < 1e-5
    # and the uncut block IS the reference's (norm weight one: u = rmsnorm(x))
    lp = family.reference_params(jax.device_get(tree), 1)["layers"][0]
    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts(jnp.asarray(x[0]), jax.tree.map(jnp.asarray, lp), None, experts_per_token=2,
                                    norm_topk_prob=True, norm_topk_eps=1e-20, routed_scaling_factor=2.5,
                                    experts_held=8, expert_offset=0)
    assert np.abs(whole - np.asarray(want)).max() < 1e-5


@pytest.mark.parametrize("width,padded", [(160, 256), (128, 128), (48, 48)])
def test_the_serving_tree_pads_an_odd_expert_width_and_computes_the_same(width, padded):
    """``serving_layout``: a width over one tile of lanes and no multiple of
    them is zero-padded to the next (relu²(0) = 0 meets zero rows of
    ``w_down``), a Mamba block's ``w_in`` is stored [out, in]; the forward
    reads either tree and gives the same logits."""
    cfg = tiny(mlp_dim=width)
    tree, ids = seeded(cfg), jnp.asarray(ids_of(cfg, 24))[None]
    served = serving_layout(tree)
    mp = served["layers_1"]["moe"]
    assert mp["w_up"].shape == (8, 64, padded) and mp["w_down"].shape == (8, padded, 64)
    assert (mp is tree["layers_1"]["moe"]) == (width == padded) and serving_layout(served) is served
    assert "w_in_t" in served["layers_0"]["mamba"] and served["layers_0"]["mamba"]["w_in_t"]["kernel"].shape[1] == 64
    assert np.abs(np.asarray(forward(served, cfg, ids)[0]) - np.asarray(forward(tree, cfg, ids)[0])).max() < 1e-5


def test_expert_tiles_fit_at_the_published_widths_and_the_three_families_keep_theirs():
    """(2688, 1856) and (1856, 2688) — a K with no half on a lane multiple,
    an N that is no lane multiple — fit the VMEM a kernel is given unasked, as
    published and as served (padded to 1,920); the tiles of the three families
    before are what PR 43 left."""
    for rows in (32, 256):
        for k, n in ((2688, 1856), (1856, 2688), (2688, 1920), (1920, 2688)):
            tk, tn = moe.expert_tile(k, n, rows)
            assert moe.tile_vmem(rows, tk, tn) <= moe._GMM_VMEM and k % tk == 0 and n % tn == 0, (k, n, rows)
    assert moe.expert_tile(2688, 1856, 32) == (896, 1856) and moe.expert_tile(1856, 2688, 32) == (1856, 896)
    assert moe.expert_tile(2688, 1920, 32) == (2688, 640) and moe.expert_tile(1920, 2688, 32) == (1920, 896)
    unchanged = {(2048, 1536, 32): (2048, 1536), (1536, 2048, 32): (1536, 2048), (2048, 1536, 256): (2048, 768),
                 (5120, 1536, 32): (5120, 512), (1536, 5120, 32): (1536, 2560), (12288, 768, 32): (12288, 256),
                 (768, 12288, 32): (768, 4096), (12288, 768, 256): (6144, 256), (4096, 4096, 32): (4096, 512)}
    assert {key: moe.expert_tile(*key) for key in unchanged} == unchanged


# ------------------------------------------ chunks, snapshots and cut-backs, served


HEAD = "a head of forty-eight characters, shared by all."         # 48 chars + BOS: three pages and one token
PROMPT = HEAD + " then a tail long enough to take three more segments of sixteen."


def greedy(cfg, tree, engine, prompt, n):
    """The answer with no cache at all: the contiguous forward over the whole
    sequence, a token at a time (at ONE padded length: every block is causal,
    so what follows a position changes nothing at it)."""
    toks = engine.tokenizer.encode(prompt, add_bos=True)
    for _ in range(n):
        padded = jnp.zeros((1, 192), jnp.int32).at[0, : len(toks)].set(jnp.asarray(toks))
        toks.append(int(jnp.argmax(forward(tree, cfg, padded)[0][0, len(toks) - 1])))
    return toks[-n:]


def test_a_prompt_prefilled_in_chunks_is_one_prefilled_whole():
    """The same prompt admitted whole and in segments of 16, each later one
    starting from the state ITS SLOT carries: the same greedy answer (the
    cache-free one), the same log-probabilities; every segment's end left a
    snapshot while the pool had a slot to give."""
    cfg = tiny()
    tree = seeded(cfg)
    whole_engine = engine_of(cfg, tree)
    whole = whole_engine.run_all([PROMPT], max_new_tokens=10)[0]
    chunked_engine = engine_of(cfg, tree, prefill_chunk=16, ssm_snapshots=16)
    chunked = chunked_engine.run_all([PROMPT], max_new_tokens=10)[0]
    assert whole.prefill_segments == 1 and chunked.prefill_segments == 8
    assert whole.tokens == chunked.tokens == greedy(cfg, tree, whole_engine, PROMPT, 10)
    assert chunked.logprob_sum == pytest.approx(whole.logprob_sum, abs=2e-4)
    assert ssm(whole_engine.stats()) == {"zero": 1, "written": 1}
    # the decode ticks' one-token state updates, a Mamba block each: these widths are no float32
    # tile, so the XLA form serves them, which updates every slot's state and skips none
    stats = whole_engine.stats()
    assert stats["ssm_update"] == "xla" and stats["ssm_state_row_skips"] == 0
    assert stats["ssm_state_row_updates"] == sum(whole_engine.row_steps_total.values()) * len(cfg.ssm_layers) > 0
    # 113 prompt tokens: seven whole segments and a last of one token, which reaches no new page
    assert ssm(chunked_engine.stats()) == {"zero": 1, "carried": 7, "written": 7}
    assert chunked_engine.stats()["ssm_snapshots_held"] == 7


def test_a_hit_starts_from_a_snapshot_and_a_match_without_one_is_cut_back():
    """A first prompt leaves ONE snapshot, at its last whole page (112). A
    second shares its 49-token head: three pages match, none has a snapshot,
    the match is CUT BACK to nothing, the prompt is computed whole — and
    leaves a snapshot at the boundary the match reached (48). The third hits:
    it starts behind three pages from that snapshot. Every answer is the
    cache-free one."""
    cfg = tiny()
    tree = seeded(cfg)
    engine = engine_of(cfg, tree)
    others = [HEAD + " a second tail, not the first's.", HEAD + " and a third one, again its own."]
    first = engine.run_all([PROMPT], max_new_tokens=8)[0]
    assert first.prefix_hit_tokens == 0 and ssm(engine.stats()) == {"zero": 1, "written": 1}
    second = engine.run_all([others[0]], max_new_tokens=8)[0]
    assert second.prefix_hit_tokens == 0
    assert ssm(engine.stats()) == {"zero": 2, "written": 3, "cut_back_tokens": 48}
    third = engine.run_all([others[1]], max_new_tokens=8)[0]
    assert third.prefix_hit_tokens == 48
    assert ssm(engine.stats()) == {"zero": 2, "snapshot": 1, "written": 4, "cut_back_tokens": 48}
    for res, prompt in zip((first, second, third), [PROMPT] + others):
        assert res.tokens == greedy(cfg, tree, engine, prompt, 8), prompt
    stats = engine.stats()
    assert stats["ssm_snapshots"] == 4 and stats["ssm_snapshots_held"] == 4
    assert stats["ssm_snapshot_bytes"] == 4 * (3 * 8 * 8 * 16 * 4 + 3 * 3 * 128 * 4)     # float32 config: 4 B columns
    assert stats["pool_hbm_bytes"] == engine.pool.hbm_bytes and stats["ssm_state_bytes"] == engine.pool.conv_state_bytes


def test_an_evicted_snapshot_is_prefilled_again_and_the_pool_stays_bounded():
    """A pool of TWO snapshots under five prompts over one head: the head's
    snapshot is the most recently used and stays; the prompts' own last pages
    push each other out (``evicted``), their pages stay cached, and a prompt
    that comes back finds its pages without a state: cut back to the head's
    snapshot, computed again from there, the same answer."""
    cfg = tiny()
    tree = seeded(cfg)
    engine = engine_of(cfg, tree, ssm_snapshots=2)
    prompts = [HEAD + f" tail number {i}, long enough to fill two more pages of sixteen." for i in range(4)]
    answers = [engine.run_all([p], max_new_tokens=6)[0] for p in prompts]
    assert [a.prefix_hit_tokens for a in answers] == [0, 0, 48, 48]
    stats = engine.stats()
    assert stats["ssm_snapshots_held"] == 2 and stats["ssm_state_evicted"] >= 2
    again = engine.run_all([prompts[0]], max_new_tokens=6)[0]
    # its own pages matched to 96 tokens; the state was kept at 48 alone
    assert again.prefix_hit_tokens == 48 and again.tokens == answers[0].tokens
    assert engine.stats()["ssm_state_cut_back_tokens"] == 48 + 48
    assert again.tokens == greedy(cfg, tree, engine, prompts[0], 6)


def test_two_rows_decode_together_and_a_halted_row_keeps_its_state():
    """Two requests of different lengths through the fused ticks: the short
    one halts first and the long one's answer is still the cache-free one (a
    row that does not advance keeps its state and is routed nowhere)."""
    cfg = tiny()
    tree = seeded(cfg)
    engine = engine_of(cfg, tree)
    prompts = ["short", PROMPT]
    got = engine.run_all(prompts, max_new_tokens=14)
    engine2 = engine_of(cfg, tree)
    alone = [engine2.run_all([p], max_new_tokens=14)[0] for p in prompts]
    assert [g.tokens for g in got] == [a.tokens for a in alone]
    assert got[1].tokens == greedy(cfg, tree, engine, PROMPT, 14)


# ------------------------------------------------------------ the radix cache's part


def test_snapshots_follow_their_pages_through_splits_and_evictions():
    class Pages:
        def __init__(self):
            self.freed = []

        def free(self, ids):
            self.freed.extend(ids)

    cache = RadixPrefixCache(4, Pages(), snapshots=3)
    a = list(range(16))
    cache.insert(a, 0, [1, 2, 3, 4])
    taken = [cache.snap_alloc() for _ in range(3)]
    assert sorted(taken) == [0, 1, 2] and cache.snap_alloc() is None         # all being written: none to give
    assert cache.snap_attach(a, 8, taken[0]) and cache.snap_attach(a, 16, taken[1])
    assert not cache.snap_attach(a, 8, taken[2]) and cache.snapshots_held == 2   # the boundary has one: slot freed
    assert cache.match_state(a + [99], 16)[:1] == (16,) and cache.match_state(a + [99], 12)[0] == 8
    # a prompt that shares three pages splits the edge: the snapshot at 8 stays with the upper half
    b = a[:12] + [50, 51, 52, 53]
    assert cache.match_state(b, 12) == (8, [1, 2], cache.root.children[tuple(a[:4])], taken[0], 12)
    cache.insert(b, 12, [9])
    upper = cache.root.children[tuple(a[:4])]
    assert upper.snaps == {2: taken[0]} and len(upper.pages) == 3
    assert upper.children[tuple(a[12:16])].snaps == {1: taken[1]}
    assert cache.match_state(a + [99], 16)[3] == taken[1]
    # the LRU snapshot leaves its boundary for another, its pages stay; a pinned one never does
    cache.snap_pin(taken[0])
    third = cache.snap_alloc()
    fourth = cache.snap_alloc()
    assert third == taken[2] and fourth == taken[1] and cache.snapshots_evicted == 1
    assert cache.snap_alloc() is None and cache.match_state(a + [99], 16)[:1] == (8,)
    cache.snap_pin(taken[0], -1)
    # pages evicted take their snapshots with them
    cache.snap_free(third), cache.snap_free(fourth)
    assert cache.evict(100) == 5 and cache.snapshots_held == 0 and cache.take_snapshots_evicted() == 1


# ------------------------------------------------------------------- refusals


def test_what_this_family_is_not_served_with_says_why():
    cfg = tiny()
    tree = seeded(cfg)
    with pytest.raises(ValueError, match="recurrent state .NemotronHConfig.*roll the Mamba state back"):
        engine_of(cfg, tree, draft_params=tree, draft_config=cfg)
    with pytest.raises(ValueError, match="float32, as the model card advises"):
        engine_of(cfg, tree, kv_quant="int8")
    with pytest.raises(ValueError, match="whole chunks of 16"):
        engine_of(cfg, tree, page_size=8)
    with pytest.raises(ValueError, match="Mamba state .NemotronHConfig. is served on one device"):
        engine_of(cfg, tree, mesh=object())
    with pytest.raises(ValueError, match="letters"):
        tiny(pattern="MEMEM*X")


def test_the_engine_updates_the_state_by_the_kernel_where_the_rule_says_so(caplog):
    """A state whose head is whole float32 tiles (``ssm_state`` 128), the
    kernels asked for: the engine binds ``kernels/ssm_update.py`` (interpret
    mode here), says so in ``stats()`` and in its log, answers what the same
    engine answers with the XLA form in its place, and counts the row-updates
    the kernel did — the tokens the ticks folded, a Mamba block each — and
    those it skipped: the rest of slots x sub-steps x blocks. The rehearsal
    widths under the same ask keep the XLA form."""
    import logging

    cfg = tiny(ssm_state=128, head_dim=128)
    tree = seeded(cfg)
    with caplog.at_level(logging.INFO, logger="sentio_tpu.runtime.paged"):
        kernel = engine_of(cfg, tree, use_pallas=True)
    assert kernel.stats()["ssm_update"] == "pallas"
    assert "the ssm-update kernel" in caplog.text
    xla = engine_of(cfg, tree, use_pallas=True)
    xla._ssm_impl = None
    xla._build_fns()
    prompts = [PROMPT[:40], HEAD]
    got, want = (engine.run_all(prompts, max_new_tokens=6) for engine in (kernel, xla))
    assert [r.tokens for r in got] == [r.tokens for r in want] and all(len(r.tokens) == 6 for r in got)
    assert [r.logprob_sum for r in got] == pytest.approx([r.logprob_sum for r in want], abs=2e-4)
    stats, blocks = kernel.stats(), len(cfg.ssm_layers)
    assert stats["ssm_state_row_updates"] == kernel.row_steps_total["useful"] * blocks > 0
    assert stats["ssm_state_row_updates"] + stats["ssm_state_row_skips"] == sum(kernel.row_steps_total.values()) * blocks
    assert xla.stats()["ssm_update"] == "xla" and xla.stats()["ssm_state_row_skips"] == 0
    assert engine_of(tiny(head_dim=128), seeded(tiny(head_dim=128)), use_pallas=True).stats()["ssm_update"] == "xla"


def test_the_row_update_counters_reach_metrics_with_the_familys_others():
    """``sentio_tpu_ssm_state_rows_total{kind}`` as ``/metrics`` exports it,
    from a harvested tick's ``ssm_state`` beside the starts and snapshots it
    already carried; a tick of a family without Mamba layers (zeros) makes no
    series."""
    from sentio_tpu.infra.metrics import MetricsCollector
    from sentio_tpu.infra.phases import SSM_ROW_UPDATE_KINDS, SSM_STATE_KINDS

    assert SSM_ROW_UPDATE_KINDS == ("row_updates", "row_skips") == SSM_STATE_KINDS[-2:]
    m = MetricsCollector()
    rows = {"useful": 21, "halted": 11, "empty": 32}
    m.record_row_steps(rows, ssm_state=dict.fromkeys(SSM_STATE_KINDS, 0))
    assert b"sentio_tpu_ssm_state_rows_total{kind=" not in m.export_prometheus()
    m.record_row_steps(rows, ssm_state={**dict.fromkeys(SSM_STATE_KINDS, 0), "zero": 1,
                                        "row_updates": 21 * 6, "row_skips": 43 * 6})
    text = m.export_prometheus()
    assert b'sentio_tpu_ssm_state_rows_total{kind="row_updates"} 126.0' in text
    assert b'sentio_tpu_ssm_state_rows_total{kind="row_skips"} 258.0' in text
    assert b'sentio_tpu_ssm_state_starts_total{kind="zero"} 1.0' in text
    assert m.export_json()["counters"]["ssm_row_updates('row_skips',)"] == 258.0
