"""The engine's prefill through the flash kernel that knows a prior
(kernels/prefill_attention.py, chosen in runtime/paged.py where the decode
kernel is chosen), at tiny widths on the CPU, in float32: a prompt prefilled in
NINE segments over priors that grow to 40 pages — the long mix's shape, which
the dense configuration's reference check on the chip never reaches — with the
kernel asked for (interpret mode) against the XLA path AND against ONE whole
forward of the family's plain float32 reference over prompt and answer.

Tolerances are the families' own (tests/test_deepseek_v2.py,
tests/test_cohere2_moe.py: float32 logits agree to ``F32``, and a greedy token
may differ from the reference's only where the two best logits lie within
``GAP``); the dense family takes the same 3e-5.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import test_cohere2_moe as routed_tests  # noqa: E402
import test_deepseek_v2 as latent_tests  # noqa: E402
from benchmark import reference as dense_reference  # noqa: E402
from benchmark.families import llama as dense_family  # noqa: E402
from sentio_tpu.models.llama import LlamaConfig, init_llama  # noqa: E402
from sentio_tpu.runtime.paged import ContinuousBatchingEngine  # noqa: E402

PAGE, CHUNK, ANSWER = 8, 40, 6       # a segment is five pages: priors of 0, 5 ... 40 pages
PROMPT = ("the archive keeps every ledger of the harbour, and a clerk who asks for one "
          "is sent to the third room. " * 4)[:348]          # BOS + 348 bytes: 8 segments and 29 tokens


def dense():
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype="float32")
    tree = init_llama(jax.random.PRNGKey(0), cfg)

    def logits(ids):
        params = jax.tree.map(jnp.asarray, dense_family.reference_params(jax.device_get(tree), cfg.n_layers))
        return np.asarray(dense_reference.forward(
            params, jnp.asarray(ids), n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps))

    return cfg, tree, logits, 3e-5


def latent():
    cfg = latent_tests.tiny()
    tree = latent_tests.seeded(cfg)
    return cfg, tree, lambda ids: latent_tests.ref_forward(cfg, tree, ids)[0], latent_tests.F32


def windowed():
    cfg = routed_tests.tiny()            # a window of 24 beside a full layer, 4 query heads a kv head
    tree = routed_tests.seeded(cfg)
    return cfg, tree, lambda ids: routed_tests.ref_forward(cfg, tree, ids)[0], routed_tests.F32


FAMILIES = {"dense": dense, "latent": latent, "windowed": windowed}


def served(cfg, tree, use_pallas):
    engine = ContinuousBatchingEngine(
        model_config=cfg, params=tree, max_slots=2, page_size=PAGE, max_pages_per_seq=48,
        num_pages=1 + 2 * 48, prefill_chunk=CHUNK, ignore_eos=True, use_pallas=use_pallas)
    path = "pallas" if use_pallas else "xla"
    assert engine.stats()["prefill_attention"] == path
    assert engine.stats()["paged_attention"] == path
    [result] = engine.run_all([PROMPT], max_new_tokens=ANSWER)
    assert result.prefill_segments == 9 and result.prompt_tokens == 349
    assert len(result.tokens) == ANSWER and result.logprob_count == ANSWER
    return engine, result


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_nine_segments_through_the_kernel_are_the_xla_path_and_the_reference(name):
    cfg, tree, reference_logits, tol = FAMILIES[name]()
    engine, got = served(cfg, tree, True)
    _, xla = served(cfg, tree, None)      # on the CPU nothing is chosen unless asked for

    # the XLA path: the same greedy tokens, the same log-probabilities
    assert got.tokens == xla.tokens
    assert abs(got.logprob_sum - xla.logprob_sum) < ANSWER * tol
    assert abs(got.logprob_min - xla.logprob_min) < tol

    # ONE forward of the plain reference over the prompt and the answer: its
    # logits after the prompt and after each answer token but the last
    prompt = engine.tokenizer.encode(PROMPT, add_bos=True)
    ids = np.asarray(list(prompt) + got.tokens[:-1], np.int32)
    logits = reference_logits(ids)[len(prompt) - 1:]
    logp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
    for step, token in enumerate(got.tokens):
        best = int(np.argmax(logits[step]))
        assert token == best or logits[step, best] - logits[step, token] < tol, (step, token, best)
    chosen = logp[np.arange(ANSWER), got.tokens]         # measured: within 4e-6 of the engine's
    assert abs(got.logprob_sum - chosen.sum()) < ANSWER * tol
    assert abs(got.logprob_min - chosen.min()) < tol


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_an_engine_says_how_its_expert_matmuls_are_tiled(name):
    """``stats()["expert_tiles"]``: for a routed family the tile of each of a
    layer's three grouped matmuls in the decode program — what
    ``models/moe.py::expert_tile`` answers for the served matrices under the
    decode rows — and null for a family without routed experts."""
    from sentio_tpu.models import moe

    cfg, tree, _, _ = FAMILIES[name]()
    engine = ContinuousBatchingEngine(model_config=cfg, params=tree, max_slots=2, page_size=PAGE,
                                      max_pages_per_seq=4)
    tiles = engine.stats()["expert_tiles"]
    if name == "dense":
        assert tiles is None
        return
    layer = next(lp["moe"] for lp in engine.params.values() if isinstance(lp, dict) and "moe" in lp)
    assert sorted(tiles) == ["w_down", "w_gate", "w_up"]
    for matrix, said in tiles.items():
        _, k, n = layer[matrix].shape
        rows, tk, tn = said["tile"]
        assert rows == moe.row_tile(2 * cfg.experts_per_token, cfg.n_experts)
        assert (tk, tn) == moe.expert_tile(k, n, rows, 4, 4) and said["steps_per_expert"] == (k // tk) * (n // tn)


def test_an_engine_that_keeps_the_xla_form_says_why(caplog):
    """The kernel is chosen by what the engine can see: asked for under a
    caller's own forward it is left out, with the line logged, and the stat
    says which path the prefill programs run."""
    import functools
    import logging

    from sentio_tpu.models.llama import llama_forward

    cfg = LlamaConfig.tiny()
    tree = init_llama(jax.random.PRNGKey(0), cfg)
    with caplog.at_level(logging.WARNING, logger="sentio_tpu.runtime.paged"):
        own = ContinuousBatchingEngine(
            model_config=cfg, params=tree, max_slots=1, page_size=PAGE, max_pages_per_seq=4,
            use_pallas=True, forward_fn=functools.partial(llama_forward, attn_fn=None))
    assert own.stats()["prefill_attention"] == "xla" and own.stats()["paged_attention"] == "pallas"
    assert "prefill attention runs the XLA form" in caplog.text and "own forward_fn" in caplog.text
    chosen = ContinuousBatchingEngine(model_config=cfg, params=tree, max_slots=1, page_size=PAGE,
                                      max_pages_per_seq=4, use_pallas=True)
    assert chosen.stats()["prefill_attention"] == "pallas"
    # a rebuilt engine makes the same choice from the same ask, through the family's own forward
    again = chosen.spawn_fresh()
    assert again.stats()["prefill_attention"] == "pallas" and again.forward_fn.func is llama_forward
    assert chosen.spawn_fresh().stats() == chosen.stats()


def test_the_timing_script_rehearses_on_the_cpu(capsys):
    """``python -m sentio_tpu.eval.prefill_attn_timing --tiny``: the control
    flow of the chip's timing run at toy shapes — kernel beside XLA form at
    every point, the error between them, the host's clock named as such."""
    import json

    from sentio_tpu.eval import prefill_attn_timing

    assert prefill_attn_timing.main(["--tiny", "--calls", "1", "--no-trace"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    summary, points = lines[-1], lines[:-1]
    assert summary["ok"] and summary["clock"] == "host" and summary["device"]["platform"] == "cpu"
    assert {(p["geometry"], p["form"]) for p in points} == {
        (g, f) for g in prefill_attn_timing.TINY for f in ("kernel", "xla")}
    assert all(p["max_abs_err_kernel_vs_xla"] <= 2.0 ** -8 and "peak_share" not in p for p in points)
