"""The family ``jamba`` (models/jamba.py: Mamba-1 mixers whose decay is a
number per channel and state column — a selective scan — 3 to 1 here beside
multi-query attention, a dense SwiGLU in every layer, a tied head; the Mamba
state per slot and in the bounded pool of snapshots the radix cache hands to
page boundaries, runtime/paged.py and runtime/radix.py as they serve
``nemotron_h``) against its plain reference (benchmark/jamba_reference.py), at
a small size on the CPU: seeded random weights, logits and not tokens.

Tolerances, each with its reason. In FLOAT32 (``F32``) program and reference
compute the same function from the same numbers and differ by the order of
their sums and by the state's orientation (the program holds ``S`` transposed
and walks blocks of thirty-two tokens, the reference walks them one by one over
``[inner, N]``): logits of size 0.1 agree to 5e-5 (measured: under 1e-6). Two
paths of the PROGRAM (whole and chunked, cold and behind a snapshot) run the
same steps in the same order from the same state, so their answers are the same
tokens and their log-probabilities agree to 2e-4.
"""

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import jamba_reference as reference  # noqa: E402
from benchmark.families import jamba as family  # noqa: E402
from sentio_tpu.models import jamba as M  # noqa: E402
from sentio_tpu.models.jamba import JambaConfig, init_jamba, jamba_forward  # noqa: E402
from sentio_tpu.models.llama import serving_layout  # noqa: E402
from sentio_tpu.runtime.paged import ContinuousBatchingEngine  # noqa: E402

F32 = 5e-5
PAGE = 16
# jitted: eager, a forward of a few hundred small operations takes ten times as long here
forward = jax.jit(jamba_forward, static_argnums=1, static_argnames=("cache_index",))


def tiny(**over) -> JambaConfig:
    return dataclasses.replace(JambaConfig.tiny(), dtype="float32", **over)


def seeded(cfg, seed=0):
    return init_jamba(jax.random.PRNGKey(seed), cfg)


def ref_forward(cfg, tree, ids, **over):
    """The plain reference on one sequence → logits [T, V]."""
    params = jax.tree.map(jnp.asarray, family.reference_params(jax.device_get(tree), cfg.n_layers))
    kwargs = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, norm_eps=cfg.norm_eps,
                  d_state=cfg.mamba_d_state, dt_rank=cfg.mamba_dt_rank)
    return np.asarray(jax.jit(functools.partial(reference.forward, **{**kwargs, **over}))(params, jnp.asarray(ids)))


def ids_of(cfg, n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size))


def engine_of(cfg, tree, **over):
    return ContinuousBatchingEngine(**{**dict(
        model_config=cfg, params=tree, max_slots=2, page_size=PAGE, max_pages_per_seq=12,
        steps_per_tick=4, ssm_snapshots=4), **over})


def ssm(stats: dict) -> dict:
    """What prefill counted (the decode ticks' row updates are read beside it)."""
    return {k.removeprefix("ssm_state_"): v for k, v in stats.items() if k.startswith("ssm_state_") and v
            and k not in ("ssm_state_bytes", "ssm_state_row_updates", "ssm_state_row_skips")}


# ----------------------------------------------- the model and the recurrence


@pytest.mark.parametrize("n,cfg", [(40, tiny()), (23, tiny(n_layers=6, attn_layer_period=3, attn_layer_offset=1,
                                                           mamba_d_state=16, mamba_dt_rank=12, n_heads=8))],
                         ids=["a-block-and-a-quarter", "another-size-under-a-block"])
def test_contiguous_forward_is_the_reference(n, cfg):
    """Tokens through both kinds of layer — the blocked scan over a transposed
    state against the recurrence walked a token at a time, multi-query
    attention, the tied head — at two sizes."""
    tree, ids = seeded(cfg), ids_of(cfg, n)
    got, _ = forward(tree, cfg, jnp.asarray(ids)[None])
    assert got.shape == (1, n, cfg.vocab_size) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got)[0] - ref_forward(cfg, tree, ids)).max() < F32


@pytest.mark.parametrize("over", [{"inner_norms": False}, {"dt_bias": False}, {"one_decay_column": True}],
                         ids=lambda o: next(iter(o)))
def test_the_references_own_controls_are_seen(over):
    """What the comparison catches: the reference told to drop the three inner
    RMSNorms, to drop ``b_dt``, to decay every state column as column 0."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got = np.asarray(forward(tree, cfg, jnp.asarray(ids)[None])[0])[0]
    assert np.abs(got - ref_forward(cfg, tree, ids, **over)).max() > 100 * F32, over


def test_the_selective_scan_is_the_literal_recurrence_and_hands_out_its_boundaries():
    """``selective_scan`` over 64 positions in blocks of 32 (what the model
    runs), from a non-zero state, with pad positions (step 0) behind 37, a
    decay that differs by channel AND column: y, the last state and the states
    asked for (the start itself, boundaries in the MIDDLE of a block and at
    its end) against the recurrence one token at a time; behind the pads the
    state stands still. The block is how the loop is cut, not what it
    computes: 16 and one step an iteration give the same."""
    rng = np.random.default_rng(0)
    b, t, d, n = 2, 64, 24, 8
    x, bm, cm = (rng.standard_normal(s).astype(np.float32) for s in ((b, t, d), (b, t, n), (b, t, n)))
    step = rng.uniform(0.01, 0.5, (b, t, d)).astype(np.float32)
    step[1, 37:] = 0.0
    a = -rng.uniform(0.5, 4, (n, d)).astype(np.float32)
    start = rng.standard_normal((b, n, d)).astype(np.float32)
    at = np.asarray([[0, 1, 3], [4, 0, 2]], np.int32)          # in SNAP_TOKENS = 16 tokens from the start
    want_y, state, states = np.zeros_like(x), start.copy(), [start.copy()]
    for i in range(t):
        state = np.exp(step[:, i, None, :] * a) * state + (step[:, i] * x[:, i])[:, None, :] * bm[:, i, :, None]
        want_y[:, i] = np.einsum("bnd,bn->bd", state, cm[:, i])
        if i % 16 == 15:
            states.append(state.copy())
        if i == 36:
            after_37 = state.copy()
    assert M.SCAN_BLOCK == 32 and M.SNAP_TOKENS == 16
    for block in (M.SCAN_BLOCK, 16, 1):
        y, last, snaps = M.selective_scan(*(jnp.asarray(v) for v in (x, step, a, bm, cm, start, at)), block=block)
        assert np.abs(np.asarray(y) - want_y).max() < 1e-4 and np.abs(np.asarray(last) - state).max() < 1e-4
        for row in range(b):
            for k in range(3):
                assert np.abs(np.asarray(snaps)[row, k] - states[at[row, k]][row]).max() < 1e-4, (block, row, k)
        assert np.abs(np.asarray(last)[1] - after_37[1]).max() < 1e-4       # the state after the 37 real tokens


def test_the_kernel_is_the_loop_and_serves_the_same_answer():
    """``kernels/selective_scan.py`` (interpret mode here; a serving TPU takes
    it) against the loop it replaces: y, the last state and the boundaries —
    the start itself among them — with pads behind 37 of a row; then one
    prompt served in chunks through it, the tokens and log-probabilities the
    loop's."""
    rng = np.random.default_rng(1)
    b, t, d, n = 2, 64, 256, 8
    x, bm, cm = (rng.standard_normal(s).astype(np.float32) for s in ((b, t, d), (b, t, n), (b, t, n)))
    step = rng.uniform(0.01, 0.5, (b, t, d)).astype(np.float32)
    step[1, 37:] = 0.0
    a = -rng.uniform(0.5, 4, (n, d)).astype(np.float32)
    start = rng.standard_normal((b, n, d)).astype(np.float32)
    args = [jnp.asarray(v) for v in (x, step, a, bm, cm, start, np.asarray([[0, 1, 3], [4, 0, 2]], np.int32))]
    assert M.SCAN_FORM is None                     # by backend: the loop here
    want = M.scan_segment(*args)
    cfg = tiny()
    tree = seeded(cfg)
    loop = engine_of(cfg, tree, prefill_chunk=16, ssm_snapshots=16).run_all([PROMPT], max_new_tokens=8)[0]
    try:
        M.SCAN_FORM = "interpret"
        got = M.scan_segment(*args)
        kernel = engine_of(cfg, tree, prefill_chunk=16, ssm_snapshots=16).run_all([PROMPT], max_new_tokens=8)[0]
    finally:
        M.SCAN_FORM = None
    assert all(np.abs(np.asarray(g) - np.asarray(w)).max() < 1e-5 for g, w in zip(got, want))
    assert M.scan_segment(*args[:6])[2] is None
    assert kernel.tokens == loop.tokens and kernel.prefill_segments == loop.prefill_segments == 8
    assert kernel.logprob_sum == pytest.approx(loop.logprob_sum, abs=2e-4)


def test_a_segment_from_a_carried_state_is_the_whole_and_the_step_is_a_segment_of_one():
    """``mamba1_segment`` over 40 tokens at once, and over 16 + 24 with the
    second from the first's state (rows right-padded to 32: the state is
    taken at the row's own length), and ``mamba1_step`` a token at a time:
    one mixer, three ways, the same output and the same last state."""
    cfg = tiny()
    mp = seeded(cfg)["layers_0"]["mamba"]
    u = jnp.asarray(np.random.default_rng(3).standard_normal((1, 40, cfg.dim)), jnp.float32)
    zero = {name: s[0] for name, s in M.zero_state(cfg, 1).items()}
    whole, after, _ = M.mamba1_segment(mp, cfg, u, zero, None)
    first, mid, snaps = M.mamba1_segment(mp, cfg, jnp.pad(u[:, :16], ((0, 0), (0, 16), (0, 0))), zero,
                                         jnp.asarray([16]), jnp.asarray([[1, 0]]))
    assert np.abs(np.asarray(snaps["ssm"])[0, 0] - np.asarray(mid["ssm"])[0]).max() == 0     # the boundary at 16
    assert not np.asarray(snaps["ssm"])[0, 1].any() and not np.asarray(snaps["conv"])[0, 1].any()   # the start: zeros
    assert np.abs(np.asarray(snaps["conv"])[0, 0] - np.asarray(mid["conv"])[0]).max() == 0
    second, end, _ = M.mamba1_segment(mp, cfg, jnp.pad(u[:, 16:], ((0, 0), (0, 8), (0, 0))), mid, jnp.asarray([24]))
    got = np.concatenate([np.asarray(first)[0, :16], np.asarray(second)[0, :24]])
    assert np.abs(got - np.asarray(whole)[0]).max() < 1e-5
    assert all(np.abs(np.asarray(end[name]) - np.asarray(after[name])).max() < 1e-5 for name in after)
    state, outs = zero, []
    for i in range(40):
        out, state = M.mamba1_step(mp, cfg, u[:, i: i + 1], state)
        outs.append(np.asarray(out)[0, 0])
    assert np.abs(np.stack(outs) - np.asarray(whole)[0]).max() < 1e-5
    assert all(np.abs(np.asarray(state[name]) - np.asarray(after[name])).max() < 1e-5 for name in after)


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
    logits, state = prefill(engine.params, row_ids, np.broadcast_to(np.arange(32), (2, 32)),
                            np.asarray([21, 1], np.int32), table[:, :2], state)
    got = [np.asarray(logits)[0, :21]]
    idle = jax.tree.map(lambda s: np.asarray(s)[:, 1].copy(), state[2])
    for t in range(21, 48):
        logits, state = decode(engine.params, np.asarray([ids[t], 0], np.int32), np.asarray([t, 0], np.int32),
                               table, state)
        got.append(np.asarray(logits)[:1])
    assert np.abs(np.concatenate(got) - ref_forward(cfg, tree, ids)).max() < F32
    # float32 state [Lm, rows, N, inner] — S transposed — beside the convolution's three columns; K and V
    # in the ONE attention layer's pool, one kv head
    assert state[2]["ssm"].shape == (3, 2, 8, 128) and state[2]["ssm"].dtype == jnp.float32
    assert state[2]["conv"].shape == (3, 2, 3, 128) and engine.pool.k.shape[0] == 1 and cfg.n_kv_heads == 1
    # the pieces advance every row (the junk row's state moved with its tokens); through ``step_n`` a row
    # that does not advance keeps its state (``write_mask``): the served tests below
    assert any(np.abs(np.asarray(state[2][name])[:, 1] - idle[name]).max() > 0 for name in idle)


def test_the_serving_tree_turns_the_input_projection_and_computes_the_same():
    """``serving_layout``: q, k, v and a Mamba layer's ``w_in`` stored [out,
    in]; the forward reads either tree and gives the same logits."""
    cfg = tiny()
    tree, ids = seeded(cfg), jnp.asarray(ids_of(cfg, 24))[None]
    served = serving_layout(tree)
    mamba, attn = served["layers_0"]["mamba"], served["layers_2"]["attn"]
    assert "w_in" not in mamba and mamba["w_in_t"]["kernel"].shape == (256, 64) and serving_layout(served) is served
    assert set(attn) == {"wq_t", "wk_t", "wv_t", "wo"} and attn["wk_t"]["kernel"].shape == (16, 64)
    assert np.abs(np.asarray(forward(served, cfg, ids)[0]) - np.asarray(forward(tree, cfg, ids)[0])).max() < 1e-5


# ------------------------------------------ chunks, snapshots and cut-backs, served


HEAD = "a head of forty-eight characters, shared by all."         # 48 chars + BOS: three pages and one token
PROMPT = HEAD + " then a tail long enough to take three more segments of sixteen."


def greedy(cfg, tree, engine, prompt, n):
    """The answer with no cache at all: the contiguous forward over the whole
    sequence, a token at a time (at ONE padded length: every layer is causal,
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
    # 113 prompt tokens: seven whole segments and a last of one token, which reaches no new page
    assert ssm(chunked_engine.stats()) == {"zero": 1, "carried": 7, "written": 7}
    assert chunked_engine.stats()["ssm_snapshots_held"] == 7


def test_a_hit_starts_from_a_snapshot_and_a_match_without_one_is_cut_back():
    """A first prompt leaves ONE snapshot, at its last whole page (112). A
    second shares its 49-token head: three pages match, none has a snapshot,
    the match is CUT BACK to nothing, the prompt is computed whole — and
    leaves a snapshot at the boundary the match reached (48). The third hits:
    it starts behind three pages from that snapshot. Every answer is the
    cache-free one; the pool's bytes are this family's shapes."""
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
    assert stats["ssm_snapshot_bytes"] == 4 * 3 * (8 * 128 * 4 + 3 * 128 * 4)     # float32 config: 4 B columns
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
    row that does not advance keeps its state)."""
    cfg = tiny()
    tree = seeded(cfg)
    engine = engine_of(cfg, tree)
    prompts = ["short", PROMPT]
    got = engine.run_all(prompts, max_new_tokens=14)
    engine2 = engine_of(cfg, tree)
    alone = [engine2.run_all([p], max_new_tokens=14)[0] for p in prompts]
    assert [g.tokens for g in got] == [a.tokens for a in alone]
    assert got[1].tokens == greedy(cfg, tree, engine, PROMPT, 14)


# ------------------------------------------------------------------- refusals


def test_what_this_family_is_not_served_with_says_why():
    cfg = tiny()
    tree = seeded(cfg)
    with pytest.raises(ValueError, match="recurrent state .JambaConfig.*roll the Mamba state back"):
        engine_of(cfg, tree, draft_params=tree, draft_config=cfg)
    with pytest.raises(ValueError, match="a hundredth of what a sequence of JambaConfig keeps"):
        engine_of(cfg, tree, kv_quant="int8")
    with pytest.raises(ValueError, match="whole chunks of 16"):
        engine_of(cfg, tree, page_size=8)
    with pytest.raises(ValueError, match="Mamba state .JambaConfig. is served on one device"):
        engine_of(cfg, tree, mesh=object())
    with pytest.raises(ValueError, match="num_experts=16.*routed feed-forwards beside Mamba-1 mixers"):
        tiny(num_experts=16)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        tiny(tie_word_embeddings=False)
    with pytest.raises(ValueError, match="mamba_proj_bias=True"):
        tiny(mamba_proj_bias=True)


def test_the_update_kernel_is_bound_where_asked_and_counts_the_rows_it_skips(caplog):
    """``kernels/ssm_update.py`` takes this family's ``[Lm, B, N, inner]`` too
    since PR 49 (``selective_update``: a decay a channel and column), by the
    rule that reads the operand: the published widths take it, and so does
    this file's tiny state (``[8, 128]`` a row is one whole float32 tile) once
    the kernels are ASKED for — interpret mode here. The engine says so in
    ``stats()`` and its log, answers what the same engine answers with the
    XLA form in its place, and counts the row-updates the kernel did and those
    it skipped; left unasked (every other engine of this file, a CPU server)
    the XLA form stays and every row of a tick is an update. The rule's
    answers for the Mamba-2 state have not moved."""
    import logging

    from sentio_tpu.kernels.ssm_update import ssm_update_path

    published = JambaConfig().state_shapes(8)["ssm"]
    assert published == ((26, 8, 16, 5120), jnp.float32)
    assert ssm_update_path(jax.ShapeDtypeStruct(*published)) == "pallas"
    assert ssm_update_path(jax.ShapeDtypeStruct((6, 16, 64, 64, 128), jnp.float32)) == "pallas"
    assert ssm_update_path(jax.ShapeDtypeStruct((3, 2, 8, 8, 16), jnp.float32)) == "xla"
    cfg = tiny()
    tree = seeded(cfg)
    with caplog.at_level(logging.INFO, logger="sentio_tpu.runtime.paged"):
        kernel = engine_of(cfg, tree, use_pallas=True)
    assert kernel.stats()["ssm_update"] == "pallas" and "the ssm-update kernel" in caplog.text
    xla = engine_of(cfg, tree, use_pallas=True)
    xla._ssm_impl = None
    xla._build_fns()
    prompts = [PROMPT[:40], HEAD]
    got, want = (engine.run_all(prompts, max_new_tokens=6) for engine in (kernel, xla))
    assert [r.tokens for r in got] == [r.tokens for r in want] and all(len(r.tokens) == 6 for r in got)
    assert [r.logprob_sum for r in got] == pytest.approx([r.logprob_sum for r in want], abs=2e-4)
    stats, layers = kernel.stats(), len(cfg.ssm_layers)
    assert stats["ssm_state_row_updates"] == kernel.row_steps_total["useful"] * layers > 0
    assert stats["ssm_state_row_updates"] + stats["ssm_state_row_skips"] == sum(kernel.row_steps_total.values()) * layers
    assert stats["ssm_state_row_skips"] > 0
    assert xla.stats()["ssm_update"] == "xla" and xla.stats()["ssm_state_row_skips"] == 0
    unasked = engine_of(cfg, tree)
    assert unasked._ssm_impl is None and unasked.stats()["ssm_update"] == "xla"


def test_the_snapshots_held_reach_metrics_as_a_gauge():
    """``ssm_snapshots_held`` beside ``/info``: a serving gauge at scrape time
    (PERF.md Q18), for a family with such a pool alone."""
    import types

    from sentio_tpu.infra.metrics import get_metrics
    from sentio_tpu.serve.app import _publish_serving_gauges

    def container(stats):
        service = types.SimpleNamespace(stats=lambda: stats)
        return types.SimpleNamespace(peek=lambda name: service if name == "generation_service" else None)

    _publish_serving_gauges(container({"active_slots": 1, "ssm_snapshots_held": 37}))
    assert b'sentio_tpu_serving_stat{stat="ssm_snapshots_held"} 37.0' in get_metrics().export_prometheus()
