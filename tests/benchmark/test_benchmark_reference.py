"""``reference.py`` against the program's paged engine at tiny size on the
CPU: the comparison every chip run makes at published widths — logits of
prefill and of decode through the pages, and answers served through the
engine's own admission and step programs."""

import json

import pytest
from bench_tree import REPO

from benchmark.check import run_check

CONFIGS = sorted((REPO / "benchmark" / "configs").glob("*.json"))
# every configuration goes through its OWN family's pieces and reference; the
# tampered terms below are the dense reference's
DENSE = next(p for p in CONFIGS if json.loads(p.read_text())["family"] == "llama")


def tiny(path):
    model = json.loads(path.read_text())
    return {**model, **model["rehearsal"]}


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_paged_engine_agrees_with_the_reference(path):
    model = tiny(path)
    spec = model["check"]
    out = run_check(model, spec, seed=11)
    assert out["ok"], out
    assert out["finite"] and out["layers"] == 2 and out["paged_attention"] == "xla"
    # decode went through pages: the sequences straddle a page boundary
    assert any(n % spec["page_size"] for n in out["sequences"])
    # the served answers took the paths the cells take: every prompt in
    # segments, the later ones over a prior the radix cache served
    assert out["served_problems"] == [] and out["served_requests"] == 3
    assert out["served_prefix_hit_tokens"][0] == 0
    assert all(n >= spec["page_size"] for n in out["served_prefix_hit_tokens"][1:])
    assert all(n == spec["served"]["new_tokens"] for n in out["served_tokens"])


@pytest.mark.parametrize("what,tamper", [
    ("rope theta", lambda kw: {**kw, "rope_theta": kw["rope_theta"] * 100}),
    ("norm eps", lambda kw: {**kw, "norm_eps": 1e-2}),
])
def test_the_tolerance_catches_a_wrong_term(what, tamper):
    model = tiny(DENSE)
    out = run_check(model, model["check"], seed=11, tamper=tamper)
    assert not out["ok"], (what, out)
    assert max(out["prefill_rel_rms"], out["decode_rel_rms"]) > 5 * out["tolerance"]
    # and the served answers show it too, not only the logits
    assert out["served_logprob_err"] > out["served_logprob_tol"]


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_the_tolerance_catches_weights_in_a_coarser_type(path):
    """fp8 matrices on the program's side, true ones in the reference. (What
    int8 pages and int8 weights give at published widths is in PERF.md: tiny
    widths are too noisy to tell them from bf16.)"""
    model = tiny(path)
    out = run_check(model, model["check"], seed=11, variant="weights_fp8")
    assert not out["ok"], out
    assert min(out["prefill_rel_rms"], out["decode_rel_rms"]) > 2 * out["tolerance"]


def test_int8_pages_are_a_variant_of_the_program_side_only():
    model = tiny(DENSE)
    out = run_check(model, model["check"], seed=11, variant="kv_int8")
    plain = run_check(model, model["check"], seed=11)
    assert out["kv_quant"] == "int8" and plain["kv_quant"] == "none"
    # prefill computes from a fresh cache: the page type touches decode alone
    assert out["prefill_rel_rms"] == plain["prefill_rel_rms"]
    assert out["decode_rel_rms"] > plain["decode_rel_rms"]
