"""A second architecture goes through the check as FILES: a family module and
a reference beside this test, registered for the test alone, a configuration
as a dict — and not one line of ``benchmark/*.py`` knows it.

The family is the program's own ``moe`` family with room for every token in
every expert; the reference is dropless top-k. It CHOOSES (which experts a
token goes to is decided by rank), so its pieces say what the program chose,
the reference follows those picks, and the picks are compared apart
(``check.py``'s docstring). Twice: at ``MoeConfig.tiny`` widths in float32,
where program and reference choose alike and agree to 1e-6, and at hidden
512 with 64 experts, 8 a token, in bf16 as a model is served, where 2 to 7
pairs of layer and position in a hundred choose otherwise, each within a
near-tie, and the forced logits read what a dense block reads.
"""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from benchmark.check import degrade, run_check  # noqa: E402

# MoeConfig.tiny's widths under the published key names of a routed decoder.
# Tolerances from 8 CPU seeds at float32 (prefill 3.9e-7 to 5.1e-7, decode
# 3.8e-7 to 6.2e-7, 0.97 to 1.29 times prefill, log-probabilities 1.1e-7 to
# 2.9e-7 of the mean top logit, token gap 0) against the control, the same
# program in bf16 (0.7 to 12 % over 6 seeds): 1e-4 is 160 times the one and a
# seventieth of the other.
MODEL = {
    "name": "scratch-moe", "family": "scratch_moe", "torch_dtype": "float32",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512, "max_position_embeddings": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "num_local_experts": 4, "num_experts_per_tok": 2,
    "check": {"layers": 2, "page_size": 16, "prompt_tokens": [40, 21], "decode_steps": 8,
              "rel_rms_tol": 1e-4, "decode_over_prefill_max": 2.0,
              # float32 on both sides: 0 of 280 pairs differed in any of 8 seeds
              "choice_disagree_max": 0.01, "choice_margin_max": 1e-4,
              "served": {"prompt_chars": [44, 52, 40], "shared_head_chars": 24, "new_tokens": 8,
                         "prefill_chunk": 16, "steps_per_tick": 4, "token_gap_tol": 1e-4,
                         "logprob_tol": 1e-4}},
}


# As a routed model is served: bf16, hidden 512, 64 experts, 8 a token, at the
# tolerances the dense configurations hold ON THE CHIP (not the looser
# rehearsal blocks: at this width bf16 reads what it reads there). The two
# choice limits are a third above the largest of seeds 1 to 6 on the CPU
# (readings in PERF.md, Findings of PR 28).
MODEL_BF16 = {
    **MODEL, "name": "scratch-moe-bf16", "torch_dtype": "bfloat16",
    "hidden_size": 512, "intermediate_size": 256, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 64, "vocab_size": 2048, "num_local_experts": 64, "num_experts_per_tok": 8,
    "check": {"layers": 2, "page_size": 16, "prompt_tokens": [120, 90], "decode_steps": 8,
              "rel_rms_tol": 0.011, "decode_over_prefill_max": 1.2,
              "choice_disagree_max": 0.07, "choice_margin_max": 0.025,
              "served": {"prompt_chars": [100, 120, 90], "shared_head_chars": 48, "new_tokens": 8,
                         "prefill_chunk": 32, "steps_per_tick": 4, "token_gap_tol": 0.03,
                         "logprob_tol": 0.02}},
}


@pytest.fixture
def scratch_family(monkeypatch):
    """The registration: the family's module under the name ``load_family``
    looks up, its reference importable by the name the family gives."""
    monkeypatch.syspath_prepend(str(HERE))
    import scratch_moe_family

    monkeypatch.setitem(sys.modules, "benchmark.families.scratch_moe", scratch_moe_family)
    return scratch_moe_family


def test_a_routed_family_passes_the_check_as_files(scratch_family):
    out = run_check(MODEL, MODEL["check"], seed=11)
    assert out["ok"], out
    assert out["finite"] and out["layers"] == 2 and out["served_problems"] == []
    # logits AND served answers: chunked prefill, a radix-served prior, ticks
    assert out["served_requests"] == 3 and out["served_prefix_hit_tokens"][0] == 0
    assert all(n >= MODEL["check"]["page_size"] for n in out["served_prefix_hit_tokens"][1:])
    assert max(out["prefill_rel_rms"], out["decode_rel_rms"]) < 1e-5


def test_a_tampered_reference_of_that_family_fails(scratch_family):
    """One expert a token fewer than the program takes: the reference,
    given the program's two picks, computes what the program computed — and
    its own single best never equals them. Left to itself it is 10 % off."""
    out = run_check(MODEL, MODEL["check"], seed=11,
                    tamper=lambda kw: {**kw, "experts_per_token": 1})
    assert not out["ok"], out
    assert out["choice_disagree_share"] == 1.0 and out["served_choice_disagree_share"] == 1.0
    assert out["choice_worst_margin"] > 1.0 > out["choice_margin_max"]
    assert min(out["unforced_prefill_rel_rms"], out["unforced_decode_rel_rms"]) > 0.1


def test_the_control_one_precision_down_fails(scratch_family):
    """bf16 where the configuration states float32."""
    out = run_check({**MODEL, "torch_dtype": "bfloat16"}, MODEL["check"], seed=11)
    assert not out["ok"], out
    assert min(out["prefill_rel_rms"], out["decode_rel_rms"]) > 30 * out["tolerance"]


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_a_routed_family_passes_in_bf16_with_its_choices_followed(scratch_family, seed):
    out = run_check(MODEL_BF16, MODEL_BF16["check"], seed=seed)
    assert out["ok"], out
    assert out["served_problems"] == [] and out["choice_pairs"] == 2 * (120 + 90 + 2 * 8)
    # the program did choose otherwise than the reference, and only at near-ties
    assert 0 < out["choice_disagree_share"] <= out["choice_disagree_max"]
    assert 0 < out["choice_worst_margin"] <= out["choice_margin_max"]
    # what following the choices is for: left to its own the reference reads 3 to 12 times as much
    assert max(out["unforced_prefill_rel_rms"], out["unforced_decode_rel_rms"]) > 2 * out["tolerance"]
    assert max(out["prefill_rel_rms"], out["decode_rel_rms"]) < out["tolerance"]


def test_the_family_says_which_leaves_are_matrices(scratch_family):
    """A stack of experts is rounded and degraded like a matrix, a norm's
    scale is left alone; the dense family keeps its own rule."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import llama

    rng = np.random.default_rng(0)
    tree = {"stack": jnp.asarray(rng.standard_normal((4, 8, 16)), jnp.bfloat16),
            "matrix": jnp.asarray(rng.standard_normal((8, 16)), jnp.bfloat16),
            "scale": jnp.ones((8,), jnp.float32)}
    for how in ("weights_fp8", "weights_int8"):
        routed = degrade(tree, how, scratch_family.is_matrix)
        dense = degrade(tree, how, llama.is_matrix)
        assert not np.array_equal(routed["stack"], tree["stack"])
        assert np.array_equal(dense["stack"], tree["stack"])
        assert np.array_equal(routed["matrix"], dense["matrix"])
        assert not np.array_equal(routed["matrix"], tree["matrix"])
        assert routed["scale"].dtype == jnp.float32 and np.array_equal(routed["scale"], tree["scale"])
    # int8 is per column of EACH expert's matrix, as for a single matrix
    one = degrade({"m": tree["stack"][2]}, "weights_int8", llama.is_matrix)["m"]
    assert np.array_equal(degrade(tree, "weights_int8", scratch_family.is_matrix)["stack"][2], one)
