"""What the reference check does with a model that CHOOSES (``check.py``'s
docstring): the arithmetic of the agreement, the controls that show each of
the two comparisons failing alone, the coarser types failing in bf16, and
the golden readings that hold the dense path where it was."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

from benchmark.check import choice_agreement, run_check  # noqa: E402
from test_benchmark_family_seam import MODEL_BF16, scratch_family  # noqa: E402,F401

SCORES = np.asarray([[[5.0, 4.0, 3.0, 2.9, 1.0, 0.0]]])       # one layer, one position
SPREAD = float(np.std(SCORES))


@pytest.mark.parametrize("picks, depth, differ, margin", [
    ([0, 1, 2], 3, 0, 0.0),                                    # the reference's own three
    ([2, 0, 1], 3, 0, 0.0),                                    # in any order
    ([0, 1, 3], 3, 1, 0.1 / SPREAD),                           # a near-tie taken the other way
    ([0, 3, 4], 3, 1, 3.0 / SPREAD),                           # best left out 4.0, worst taken 1.0
    ([0, 1, 2], 2, 1, 2.0 / SPREAD),                           # a reference that takes one fewer: nothing left out → its best 5.0, taken 3.0
    ([0, 1, -1], 3, 1, 3.0 / SPREAD),                          # a pick the program did not make: left out 3.0, nothing taken → the worst 0.0
    ([0, 1, 6], 3, 1, 3.0 / SPREAD),                           # a pick that is no candidate counts as none
])
def test_the_margin_of_a_differing_pick(picks, depth, differ, margin):
    pairs, differing, worst = choice_agreement(np.asarray([[picks]]), SCORES, depth)
    assert (pairs, differing) == (1, differ) and worst == pytest.approx(margin)


def test_agreement_over_layers_positions_and_masked_candidates():
    """Two layers of three positions; a candidate scored ``-inf`` (a position
    a query may not see) is never the reference's own and a pick of it is as
    wrong as a pick can be."""
    scores = np.asarray([[[3.0, 2.0, 1.0, -np.inf]] * 3, [[1.0, 2.0, 3.0, 0.0]] * 3])
    chosen = np.asarray([[[0, 1], [0, 1], [0, 3]], [[2, 1], [1, 2], [2, 3]]])
    pairs, differing, worst = choice_agreement(chosen, scores, 2)
    assert (pairs, differing) == (6, 2)
    # layer 0, position 2: left out 2.0, taken -inf → the position's worst, 1.0
    # layer 1, position 2: left out 2.0, taken 0.0
    assert worst == pytest.approx(max(1.0 / np.std([3.0, 2.0, 1.0]), 2.0 / np.std([1.0, 2.0, 3.0, 0.0])))


# ------------------------------------------------------------------ controls


@pytest.mark.parametrize("variant", ["weights_int8", "weights_fp8", "kv_int8"])
def test_a_coarser_type_fails_the_routed_check(scratch_family, variant):
    """With the choices followed the tolerances are the dense ones, so they
    catch what the dense ones catch."""
    out = run_check(MODEL_BF16, MODEL_BF16["check"], seed=1, variant=variant)
    assert not out["ok"], out
    if variant == "kv_int8":   # a coarser pool: decode through it, not prefill
        assert out["decode_rel_rms"] > out["decode_over_prefill_max"] * out["prefill_rel_rms"]
        assert out["prefill_rel_rms"] <= out["tolerance"]
    else:
        assert min(out["prefill_rel_rms"], out["decode_rel_rms"]) > out["tolerance"]


def patch_routing(monkeypatch, change):
    """``change(route_topk, logits, k, capacity, valid)`` in the place of
    the program's ``route_topk``, for every program traced from here on."""
    from sentio_tpu.models import moe

    route_topk = moe.route_topk

    def altered(logits, k, capacity, valid=None):
        return change(route_topk, logits, k, capacity, valid)

    monkeypatch.setattr(moe, "route_topk", altered)


def test_wrong_routing_fails_on_the_agreement_alone(scratch_family, monkeypatch):
    """A program that sends every token to experts 0 to 7 (their router
    logits raised by 50: the gates, renormalised over the chosen, stay the
    true ones). The reference follows it there and the logits agree; what it
    chose is nowhere near what the reference ranks first."""
    patch_routing(monkeypatch, lambda route, logits, k, capacity, valid:
                  route(logits.at[:, :k].add(50.0), k, capacity, valid))
    out = run_check(MODEL_BF16, MODEL_BF16["check"], seed=1)
    assert not out["ok"], out
    assert max(out["prefill_rel_rms"], out["decode_rel_rms"]) <= out["tolerance"]
    assert out["decode_rel_rms"] <= out["decode_over_prefill_max"] * out["prefill_rel_rms"]
    assert out["served_token_gap"] <= out["served_token_gap_tol"]
    assert out["served_logprob_err"] <= out["served_logprob_tol"] and out["served_problems"] == []
    assert out["choice_disagree_share"] > 0.9 and out["choice_worst_margin"] > 1.0


def test_wrong_expert_arithmetic_fails_on_the_forced_logits_alone(scratch_family, monkeypatch):
    """The right experts, and the last layer's down-projections a tenth too
    large in the program alone (after every router has run, so no later
    choice can feel it): every pick is the sound program's pick, the
    agreement reads what it reads for the sound program, and the logits do not."""
    from benchmark import check

    def wrong_product(tree, how, is_matrix):
        last = tree["layers_1"]
        return {**tree, "layers_1": {**last, "moe": {**last["moe"], "w_down": last["moe"]["w_down"] * 1.1}}}

    monkeypatch.setattr(check, "degrade", wrong_product)
    out = run_check(MODEL_BF16, MODEL_BF16["check"], seed=1, variant="weights_int8")
    assert not out["ok"], out
    assert min(out["prefill_rel_rms"], out["decode_rel_rms"]) > out["tolerance"]
    assert out["choice_disagree_share"] <= out["choice_disagree_max"]
    assert out["choice_worst_margin"] <= out["choice_margin_max"]
    assert out["served_choice_disagree_share"] <= out["served_choice_disagree_max"]
    assert out["served_choice_worst_margin"] <= out["served_choice_margin_max"]


@pytest.mark.parametrize("handed", ["every position", "none"])
def test_picks_the_engine_hands_back_take_the_place_of_the_replay(scratch_family, monkeypatch, handed):
    """The door for a program that can say what a REQUEST was routed by
    (``family.served``). The program routes to experts 0 to 7, so what the
    engine would hand back is known; the replay is made to claim experts 8
    to 15. Handed back, the engine's picks explain the served answers;
    handed nothing (-1), the check is left with the replay and they do not."""
    from benchmark import check

    patch_routing(monkeypatch, lambda route, logits, k, capacity, valid:
                  route(logits.at[:, :k].add(50.0), k, capacity, valid))
    replayed = check.replayed_picks
    monkeypatch.setattr(check, "replayed_picks", lambda *args: [
        {"experts": picks["experts"] + 8} for picks in replayed(*args)])

    def served(engine, prompts, new):
        results = engine.run_all(prompts, max_new_tokens=new)
        first = 0 if handed == "every position" else -1
        return results, [{"experts": np.broadcast_to(
            np.arange(first, first + 8) if first == 0 else np.full(8, -1),
            (2, res.prompt_tokens + len(res.tokens) - 1, 8))} for res in results]

    monkeypatch.setattr(scratch_family, "served", served, raising=False)
    out = run_check(MODEL_BF16, MODEL_BF16["check"], seed=1)
    explained = out["served_token_gap"] <= out["served_token_gap_tol"] \
        and out["served_logprob_err"] <= out["served_logprob_tol"]
    if handed == "none":
        assert out["served_choices_from_engine_share"] == 0 and not explained
    else:   # all but each answer's last token, which the engine never runs
        assert 0.98 < out["served_choices_from_engine_share"] < 1 and explained
    assert not out["ok"]   # experts 0 to 7 are not what the reference ranks first


def test_picks_reported_but_not_used_fail_on_the_forced_logits(scratch_family, monkeypatch):
    """A family that reports, for every position, the picks of the position
    before it: the reference follows what the program did not do."""
    pieces = scratch_family.paged_pieces

    def shifted(engine, cfg, rows, width):
        state, prefill, decode = pieces(engine, cfg, rows, width)

        def lying_prefill(*args):
            logits, pool, picks = prefill(*args)
            return logits, pool, {"experts": np.roll(np.asarray(picks["experts"]), 1, axis=2)}

        return state, lying_prefill, decode

    monkeypatch.setattr(scratch_family, "paged_pieces", shifted)
    out = run_check(MODEL_BF16, MODEL_BF16["check"], seed=1)
    assert not out["ok"], out
    assert out["prefill_rel_rms"] > 2 * out["tolerance"]


def test_routing_one_precision_down_exceeds_a_choice_limit(scratch_family, monkeypatch):
    """The router's input through float8's three bits of mantissa (the step
    below bf16), the experts' arithmetic untouched: the picks move further
    from the reference's than a near-tie explains."""
    from benchmark.check import degrade
    from sentio_tpu.models import layers

    dense = layers.dense

    def coarse_router(params, x, dtype=None, **kwargs):
        if params["kernel"].shape[-1] == MODEL_BF16["num_local_experts"]:
            x = degrade({"x": x}, "weights_fp8", lambda a: True)["x"]
        return dense(params, x, *([dtype] if dtype is not None else []), **kwargs)

    monkeypatch.setattr(layers, "dense", coarse_router)
    out = run_check(MODEL_BF16, MODEL_BF16["check"], seed=1)
    assert not out["ok"], out
    assert out["choice_disagree_share"] > out["choice_disagree_max"] \
        or out["choice_worst_margin"] > out["choice_margin_max"]


# ------------------------------------------------------------ the dense path


@pytest.mark.parametrize("config, prefill, decode, gap, logprob", [
    ("mistral-7b-v0.3-l16", "0.8630", "0.8436", "0", "0.005392"),
    ("yi-1.5-6b-l16", "0.8558", "0.7455", "0", "0.003952"),
])
def test_the_dense_check_reads_what_it_read_before_choices(config, prefill, decode, gap, logprob):
    """A family without ``CHOICES`` takes the path it took: the readings of
    both configurations at rehearsal size, seed 2147483777, to the digits
    PERF.md printed before this file existed (Findings of PR 27)."""
    model = json.loads((HERE.parents[1] / "benchmark" / "configs" / f"{config}.json").read_text())
    model = {**model, **model["rehearsal"]}
    out = run_check(model, model["check"], seed=2147483777)
    assert out["ok"], out
    assert f"{100 * out['prefill_rel_rms']:.4f}" == prefill
    assert f"{100 * out['decode_rel_rms']:.4f}" == decode
    assert f"{out['served_token_gap']:.6g}" == gap
    assert f"{out['served_logprob_err']:.6f}" == logprob
    assert not any(key.startswith(("choice", "unforced", "served_choice")) for key in out)
