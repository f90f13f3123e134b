"""The family ``mellum`` (models/mellum.py, the expert layer of models/moe.py,
the windows of runtime/paged.py and kernels/paged_attention.py) against its
plain reference (benchmark/mellum_reference.py), at a small size on the CPU:
seeded random weights, logits and not tokens.

Tolerances, each with its reason. In FLOAT32 (``F32``) program and reference
compute the same function from the same numbers and differ by the order of
their sums: logits of size 1 agree to 5e-5 (measured: under 1e-5), and a greedy
token may differ from the reference's only where the two best logits lie within
that (``GAP``). A control must depart by a hundred times that.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark import mellum_reference as reference  # noqa: E402
from benchmark.check import choice_agreement  # noqa: E402
from benchmark.families import mellum as family  # noqa: E402
from sentio_tpu.models import families, moe  # noqa: E402
from sentio_tpu.models.mellum import (  # noqa: E402
    FAMILY, FULL, SLIDING, MellumConfig, init_mellum, mellum_forward, rope_by_kind, yarn_inv_freq)
from sentio_tpu.runtime.paged import ContinuousBatchingEngine  # noqa: E402

F32, GAP = 5e-5, 5e-5
KINDS = {"mixed": f"{SLIDING},{FULL}", "two-periods": ",".join((SLIDING, SLIDING, SLIDING, FULL) * 2)}


def tiny(**over) -> MellumConfig:
    return MellumConfig.tiny(**{"dtype": "float32", **over})


def seeded(cfg, seed=0):
    return init_mellum(jax.random.PRNGKey(seed), cfg)


def ref_forward(cfg, tree, ids, forced=None, **over):
    """The plain reference on one sequence, told what the configuration says."""
    kwargs = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                  rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, layer_types=cfg.kinds,
                  sliding_window=cfg.sliding_window, rope_factor=cfg.rope_factor,
                  rope_original_max=cfg.rope_original_max, rope_beta_fast=cfg.rope_beta_fast,
                  rope_beta_slow=cfg.rope_beta_slow, rope_attention_factor=cfg.rope_attention_factor,
                  experts_per_token=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
                  experts_held=cfg.experts_held, expert_offset=cfg.expert_offset)
    params = jax.tree.map(jnp.asarray, family.reference_params(jax.device_get(tree), cfg.n_layers))
    logits, scores = reference.forward(params, jnp.asarray(ids), forced, **{**kwargs, **over})
    return np.asarray(logits), np.asarray(scores["experts"])


def ids_of(cfg, n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size))


def engine_tree(engine):
    """The canonical tree back out of an engine's serving tree."""
    out = dict(engine.params)
    for name, lp in engine.params.items():
        if name.startswith("layers_"):
            out[name] = {**lp, "attn": {k[:2] if k.endswith("_t") else k:
                                        ({"kernel": w["kernel"].T} if k.endswith("_t") else w)
                                        for k, w in lp["attn"].items()}}
    return out


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("kinds", sorted(KINDS))
def test_contiguous_forward_is_the_reference(kinds):
    """The whole forward, 40 tokens past a window of 24 and an original length
    of 32 — a sliding and a full layer, then two whole periods — and the picks
    it hands back."""
    cfg = tiny(layer_kinds=KINDS[kinds], n_layers=KINDS[kinds].count(",") + 1)
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got, _, routed = mellum_forward(tree, cfg, jnp.asarray(ids)[None])
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(np.asarray(got)[0] - want).max() < F32
    picks = np.asarray(routed["experts"])[:, 0]                       # [L, T, k]
    assert picks.shape == (cfg.n_layers, 40, 4) and choice_agreement(picks, scores, 4)[1] == 0
    n = 40 * cfg.n_layers
    assert routed["counts"].tolist() == [n * 4, n * 4, cfg.n_layers * 16, cfg.n_layers * 16]


CONTROLS = {
    "default rotary on the full layers": dict(rope_factor=1.0),
    "the attention factor dropped": dict(rope_attention_factor=1.0),
    "the window dropped": dict(sliding_window=10_000),
    "gates left unnormalised": dict(norm_topk_prob=False),
    "top-4 made top-2": dict(experts_per_token=2),
}


@pytest.mark.parametrize("what", sorted(CONTROLS))
def test_the_references_own_controls_are_seen(what):
    """Each term the reference is told wrongly breaks agreement with the
    program, at a length past the tiny window (24) and original length (32)."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 40)
    got = np.asarray(mellum_forward(tree, cfg, jnp.asarray(ids)[None])[0])[0]
    assert np.abs(got - ref_forward(cfg, tree, ids)[0]).max() < F32
    assert np.abs(got[30:] - ref_forward(cfg, tree, ids, **CONTROLS[what])[0][30:]).max() > 100 * F32
    if what == "the window dropped":   # until the window bites the two agree
        assert np.abs(got[:24] - ref_forward(cfg, tree, ids, **CONTROLS[what])[0][:24]).max() < F32


def test_yarn_frequencies_and_factor_by_hand():
    """The published settings: the ramp runs over dimensions 18 to 35 of 64,
    below it the plain frequencies, above it a sixteenth; the factor is 0.1
    ln 16 + 1 and scales cos and sin of a full layer alone."""
    cfg = MellumConfig()
    plain = 500_000.0 ** (-np.arange(64) / 64)
    got = yarn_inv_freq(cfg)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-6)
    np.testing.assert_allclose(got[27], plain[27] * (1 - 9 / 17 * 15 / 16), rtol=1e-6)
    assert cfg.rope_attention_factor == 1.2772588722239782
    at = jnp.asarray([[0, 5000]])
    (cos_f, sin_f), (cos_s, sin_s) = rope_by_kind(cfg, FULL, at), rope_by_kind(cfg, SLIDING, at)
    assert cos_f.shape == (1, 2, 64) and float(cos_f[0, 0, 0]) == pytest.approx(1.2772588722239782)
    assert float(cos_s[0, 0, 0]) == 1.0 and float(sin_s[0, 0, 0]) == 0.0
    np.testing.assert_allclose(np.hypot(cos_f, sin_f), 1.2772588722239782, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sin_s)[0, 1], np.sin(5000 * plain), atol=2e-3)


# ------------------------------------------------------- through the pages


def test_a_prompt_prefilled_in_chunks_is_one_prefilled_whole():
    """A cold prompt admitted in segments of 16 over growing priors and the
    same prompt admitted whole: the same first tokens, log-probabilities within
    float32's sums."""
    cfg = tiny()
    tree = seeded(cfg)
    prompt = "the quick brown fox jumps over the lazy dog, twice over. "
    results = []
    for chunk in (16, None):
        engine = ContinuousBatchingEngine(model_config=cfg, params=tree, max_slots=1, page_size=8,
                                          max_pages_per_seq=12, steps_per_tick=4, prefill_chunk=chunk)
        results.append(engine.run_all([prompt], max_new_tokens=8)[0])
    chunked, whole = results
    assert chunked.prompt_tokens == whole.prompt_tokens == len(prompt) + 1 > 2 * cfg.sliding_window
    assert chunked.tokens == whole.tokens
    assert chunked.logprob_sum == pytest.approx(whole.logprob_sum, abs=1e-3)


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
    """20 tokens prefilled, 30 decoded through the pool: a row LONGER than the
    window of 24, so the sliding layer's walk starts past page 0, and past the
    original length, so the full layer's rotary is YaRN's — against the
    reference's full forward, by the gather path and by the Pallas walk
    (interpreted)."""
    cfg = tiny()
    tree, ids = seeded(cfg), ids_of(cfg, 50)
    got, picks = through_the_pages(cfg, tree, ids, 20, use_pallas)
    want, scores = ref_forward(cfg, tree, ids)
    assert np.abs(got - want).max() < F32
    assert choice_agreement(picks, scores, cfg.experts_per_token)[1] == 0


def test_a_radix_hit_then_a_chunk_over_the_cached_head_and_two_rows_together():
    """Through ``run_all``: a 50-token prompt in segments of 16, then two
    requests TOGETHER, one over the head the radix cache kept: every greedy
    token is the reference's own choice (or within ``GAP`` of it), the
    log-probabilities agree, and the picks handed back are the reference's —
    negative exactly where the radix cache served."""
    cfg = tiny(vocab_size=512)
    tree = seeded(cfg)
    # no answer ends early on EOS: the head's columns for the text ids are zero, as the benchmark's trees have them
    tree["lm_head"] = {"kernel": tree["lm_head"]["kernel"].at[:, :261].set(0.0)}
    engine = ContinuousBatchingEngine(
        model_config=cfg, params=tree, max_slots=2, page_size=8, max_pages_per_seq=12,
        steps_per_tick=4, prefill_chunk=16)
    head = "the quick brown fox jumps over the lazy dog. "
    first = engine.run_all([head + "abc"], max_new_tokens=20, return_choices=True)
    rest = engine.run_all([head + "xyzw", "short"], max_new_tokens=20, return_choices=True)
    assert rest[0].prefix_hit_tokens >= 40 and first[0].prefix_hit_tokens == 0
    for res, prompt in zip(first + rest, [head + "abc", head + "xyzw", "short"]):
        ids = np.asarray(engine.tokenizer.encode(prompt, add_bos=True) + list(res.tokens))
        assert len(res.tokens) == res.logprob_count == 20
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
        assert choice_agreement(picks[:, ~served], scores[:, : len(ids) - 1][:, ~served],
                                cfg.experts_per_token)[1] == 0
    # what a token keeps in the pages whatever a layer's window: K and V of 2 kv heads of 16 in 2 layers, float32 here
    assert engine.stats()["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    # the window bit while they decoded: blocks were held behind it, and counted
    assert engine.kv_pages_total["behind_window"] > 0
    assert engine.kv_pages_total["held"] + engine.kv_pages_total["behind_window"] <= engine.kv_pages_total["tabled"]


# ------------------------------------------------------- the expert layer


def test_every_expert_held_is_the_whole_layer_and_a_halted_row_is_routed_nowhere():
    """With every expert held the pairs held equal the pairs routed and the
    layer is the reference's whole layer; a row that does not advance touches
    no expert and adds nothing."""
    cfg = tiny()
    mp = seeded(cfg, 3)["layers_0"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, cfg.dim), jnp.float32)
    out, picks, counts = moe.expert_layer(mp, cfg, x)
    assert counts.tolist()[:3] == [32, 32, 16] and picks.shape == (1, 8, 4)
    lp = {"router": mp["router"]["kernel"], **{k: mp[k] for k in ("w_gate", "w_up", "w_down")}}
    with jax.default_matmul_precision("highest"):
        want, scores = reference.experts(x[0], lp, None, experts_per_token=4, norm_topk_prob=True,
                                         experts_held=16, expert_offset=0)
    assert np.abs(np.asarray(out)[0] - np.asarray(want)).max() < F32
    np.testing.assert_allclose(np.asarray(scores).sum(-1), 1.0, rtol=1e-5)      # a softmax over ALL experts
    valid = jnp.asarray([[True] * 3 + [False] * 5])
    out, _, counts = moe.expert_layer(mp, cfg, x, valid)
    assert counts[0] == 12 and counts[1] == 12 and counts[3] <= 12
    assert not np.asarray(out)[0, 3:].any() and np.asarray(out)[0, :3].any()


# ------------------------------------------------------------- the record


def test_what_the_family_is_and_what_it_refuses():
    assert "mellum" in families.names() and families.family("mellum") is FAMILY
    cfg = MellumConfig()
    assert families.family_of(cfg) is FAMILY and FAMILY.picks(cfg) == {"experts": 8}
    assert (cfg.n_heads * cfg.head_dim, cfg.dim, cfg.mlp_dim, cfg.n_experts, cfg.experts_held) \
        == (4096, 2304, 896, 64, 64)
    assert cfg.kinds.count(SLIDING) == 21 and cfg.kinds[3::4] == (FULL,) * 7
    assert [cfg.window(i) for i in range(4)] == [1024, 1024, 1024, None]
    assert cfg.layer_types == list(cfg.kinds) and cfg.mlp_layer_types == ["sparse"] * 28
    for what in ("mesh", "draft"):
        assert "MellumConfig" in FAMILY.refusal(what, cfg)
    assert FAMILY.refusal("int8", cfg) is None
    for wrong in (dict(tie_embeddings=True), dict(parallel_block=True), dict(rope_kind="interleaved"),
                  dict(norm_kind="layernorm"), dict(layer_kinds="sliding_attention"),
                  dict(experts_held=60, expert_offset=8)):
        with pytest.raises(ValueError):
            MellumConfig(**wrong)
    # the tiles the shapes choose for [2304, 896] and [896, 2304] at a decode step's rows: both whole
    shapes = {"w_gate": jnp.zeros((64, 2304, 896)), "w_up": jnp.zeros((64, 2304, 896)),
              "w_down": jnp.zeros((64, 896, 2304))}
    tiles = FAMILY.expert_tiles(shapes, cfg, 8)
    assert tiles["w_up"] == tiles["w_gate"] == {"tile": [32, 2304, 896], "steps_per_expert": 1}
    assert tiles["w_down"] == {"tile": [32, 896, 2304], "steps_per_expert": 1}
