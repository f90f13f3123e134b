"""A second architecture goes through the check as FILES: a family module and
a reference beside this test, registered for the test alone, a configuration
as a dict — and not one line of ``benchmark/*.py`` knows it.

The family is the program's own ``moe`` family at ``MoeConfig.tiny`` widths
with room for every token in every expert; the reference is dropless top-k.
The configuration states float32: in bf16 the router's near-ties flip on
rounding in half of the seeds and a flipped token reads 30 % off (PERF.md,
Open questions) — the control below shows it; no tolerance was loosened.
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
              "served": {"prompt_chars": [44, 52, 40], "shared_head_chars": 24, "new_tokens": 8,
                         "prefill_chunk": 16, "steps_per_tick": 4, "token_gap_tol": 1e-4,
                         "logprob_tol": 1e-4}},
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
    out = run_check(MODEL, MODEL["check"], seed=11,
                    tamper=lambda kw: {**kw, "experts_per_token": 1})
    assert not out["ok"], out
    assert min(out["prefill_rel_rms"], out["decode_rel_rms"]) > 0.1
    assert out["served_logprob_err"] > out["served_logprob_tol"]


def test_the_control_one_precision_down_fails(scratch_family):
    """bf16 where the configuration states float32."""
    out = run_check({**MODEL, "torch_dtype": "bfloat16"}, MODEL["check"], seed=11)
    assert not out["ok"], out
    assert min(out["prefill_rel_rms"], out["decode_rel_rms"]) > 30 * out["tolerance"]


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
