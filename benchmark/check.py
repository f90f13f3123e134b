"""The reference check: the program's serving path against the plain
reference of the configuration's family.

``python benchmark/check.py --config <file> --seed <n> [--rehearsal]`` runs
as a child of ``run.py`` AFTER the server has exited (it takes the chip) and
prints one JSON object: ``{"ok": ..., "prefill_rel_rms": ..., ...}``.

The program's own ``ContinuousBatchingEngine`` is built at the
configuration's PUBLISHED WIDTHS with ``check.layers`` layers (2): every
later check of every PR pays this, and its purpose is shapes and arithmetic,
which depth only compounds. Everything that depends on the architecture is
asked of ``families/<family>.py`` (its docstring has the contract): the
program's config object, seeded parameters and which of their leaves are
matrices, the reference module with its keyword arguments and parameter
names, the teacher-forced pieces, and what the forward decides by rank. This
file keeps the sequences, the comparison and the tolerances. Weights are
made on the device from ``--seed`` and rounded to bf16, as a checkpoint
holds them. Two comparisons, both
against the reference's full float32 forward over the whole sequence:

1. LOGITS (``logits_part``). The served programs return sampled tokens and
   their log-probabilities, never logits, so the logits come from the pieces
   those programs are made of (``family.paged_pieces``): a prefill that fills
   the engine's pool and a decode step through the pool, teacher-forced for a
   few steps, over token ids drawn from the whole vocabulary. Held to
   ``rel_rms_tol`` (root-mean-square error over the logits' own root mean
   square), and decode through the pool may not be worse than
   ``decode_over_prefill_max`` times prefill: the pool holds what prefill
   computed, so reading it back adds nothing but a coarser pool type.
2. SERVED ANSWERS (``served_part``). Requests go through ``submit`` and
   ``step``: admission, chunked prefill (cold and over a prior the radix
   cache served), the merge into the decode batch and the fused ticks, two
   rows decoding together. Each answer's greedy tokens are replayed through
   the reference: every served token must be the reference's own choice or
   within ``token_gap_tol`` of it (logit gap over the mean top logit; with
   random weights near-ties flip on rounding, so tokens are never compared
   for equality), and the mean and the least log-probability the engine
   reports for its tokens must agree with the reference's within
   ``logprob_tol`` on the same scale.

A MODEL THAT CHOOSES. Where a forward decides something by rank (which 8
of 128 experts a token goes to; which 2,048 positions a query attends to), a
rank can turn on the last bit of a score. In bf16 the router's input differs
from the float32 one by 0.7 %, 3 to 7 pairs of layer and position in a
hundred then choose another expert than the reference, and the pooled error
reads 3 to 8 % where the same block with nothing to choose reads 0.7 %
(PERF.md, Findings of PR 28): over int8 weights, so no tolerance that passed
it would still catch them. Leaving near-tied positions out does not repair
it: a token whose expert flipped carries a changed key and value into the
next layer and every later token attends to it (with every position under a
margin of 0.04 of the spread left out, 0.4 of them stay and still read 1 to
2.3 %). So for a family that declares ``CHOICES`` (``families/llama.py`` has
the contract) the one comparison becomes two, and no position is left out:

* FORCED LOGITS. The family's pieces also return ``chosen``: what the
  PROGRAM chose in that call at every layer and position, read out of the
  timed code's own routing. The reference is given those picks (``forced``)
  and takes them in place of its own, so both compute the same function;
  the logits are pooled and held to the same tolerances as a dense family's.
* AGREEMENT (``choice_agreement``). The reference also returns its own
  ``scores`` on that trajectory. At every layer and position the program's
  picks are set against the reference's own best by those scores; where the
  sets differ, the margin of the swap is the best score the reference chose
  and the program left out, less the worst the program chose and the
  reference did not, over the standard deviation of that position's scores.
  ``choice_disagree_share`` (pairs of layer and position with any
  difference) may not pass ``check.choice_disagree_max`` and
  ``choice_worst_margin`` may not pass ``check.choice_margin_max``: a
  program may choose otherwise than the reference only where the reference
  itself could hardly tell. How many the reference takes is its own keyword
  (``CHOICES`` names it), so a reference that takes one fewer disagrees
  everywhere.

Neither proves anything alone: a program that sends every token to experts
0 to 7 passes the forced logits, one with a wrong expert product passes the
agreement; ``tests/benchmark/test_benchmark_choices.py`` shows each failing
alone. The reference left to its own choices is reported for the record
(``unforced_*``) and judged by nothing.

The SERVED ANSWERS need the picks of the served path, and ``run_all``
returns tokens and log-probabilities. Two sources, the second over the
first: (a) the family's teacher-forced prefill piece run over each prompt
with its served answer (``replayed_picks``) — two bf16 programs of one block
choose alike far more often than bf16 and float32 do, but not always: where
one pick of the served path fell the other way at an answer's position, that
token reads far off. On the chip at 128 experts of width 768, 12 seeds read
a token gap of 0 seven times, then 0.005, 0.011, 0.022, 0.024 and 0.036 —
over the limit of 0.03, which the dense family meets with 0.007 at most; on
the CPU at hidden 512, 2 of 17 seeds read 0.027 and a log-probability error
of 0.019 (limit 0.02) where the rest read under 0.005 and 0.004; (b) a family's ``served(engine, prompts,
max_new_tokens)`` → ``(results, picks)``, which goes through ``run_all`` and
asks the engine for each request's own picks; where it hands one back
(non-negative) that pick is followed, where not (a position the radix cache
served was routed by an earlier request) the replay's. The program has no
such output yet and this PR may not touch the program, so the scratch
family runs on (a); a routed CELL should wait for (b) (PERF.md, Open
questions): a check that one seed in twelve fails by chance refuses sound
PRs. The logits and the agreement do not share that tail: 12 of 12.

The tolerances stand in the configuration file's ``check`` block. Their
reason is what the chip gave at published widths, both configurations alike
(PERF.md, Findings of PR 24): bf16 as served reads 0.78 to 0.83 % relative
RMS, decode 1.01 to 1.04 times prefill, token gaps up to 0.0070 and
log-probability errors up to 0.0059 of the mean top logit. ``rel_rms_tol``
0.011 is a third above that ceiling (seeds differ by 3 %) and under int8
pages (1.33 to 1.37 % in decode, 1.7 times prefill: ``decode_over_prefill_max``
1.2 catches them a second time), int8 weights (3.0 to 3.2 %) and fp8 weights
(11 %). ``token_gap_tol`` 0.03 and ``logprob_tol`` 0.02 are the largest over
a few dozen tokens, which grows with every seed read (0.0052 over 6 seeds,
0.0059 over 11): they stand at 3 to 4 times the bf16 ceiling and under fp8
weights (0.030, 0.038); the int8 variants never showed in them. The
rehearsal blocks are looser (tiny widths are noisier, and say nothing about
the chip).

``--variant`` degrades the PROGRAM's side on purpose (the reference keeps
the true weights) to show what the tolerances catch: ``kv_int8`` (int8
pages), ``weights_fp8`` / ``weights_int8`` (matrices rounded to float8's
three bits of mantissa / through per-column int8 and back). ``tests/benchmark`` runs them at
tiny size; PERF.md has them at published widths.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

VARIANTS = ("none", "kv_int8", "weights_fp8", "weights_int8")


def rel_rms(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / (np.sqrt(np.mean(want ** 2)) + 1e-30))


def degrade(tree, how: str, is_matrix):
    """The program's matrices (the leaves the family calls so) through a
    coarser type and back to bf16."""
    import jax
    import jax.numpy as jnp

    def fp8(a):
        # float8_e4m3's three bits of mantissa with bf16's exponent (a scaled
        # fp8), rounded to nearest-even on the bits: the TPU compiler folds a
        # convert to float8 and back into nothing (my chip run, PR 24)
        bits = jax.lax.bitcast_convert_type(a.astype(jnp.bfloat16), jnp.uint16)
        bits = (bits + 7 + ((bits >> 4) & 1)) & jnp.uint16(0xFFF0)
        return jax.lax.bitcast_convert_type(bits, jnp.bfloat16)

    def int8(a):
        # per column of each matrix (a stack of experts is matrices on its last two axes)
        scale = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=-2, keepdims=True) / 127.0
        return (jnp.round(a.astype(jnp.float32) / scale) * scale).astype(jnp.bfloat16)

    fn = {"weights_fp8": fp8, "weights_int8": int8}[how]
    return jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: fn(a) if is_matrix(a) else a, t))(tree)


def choice_agreement(chosen, scores, depth: int) -> tuple[int, int, float]:
    """The program's picks ``chosen [layers, T, k]`` (a negative pick is none)
    against the reference's own ``depth`` best of ``scores [layers, T,
    candidates]`` → (pairs of layer and position, pairs whose two sets
    differ, the worst margin). The margin of a differing pair: the best score
    the reference chose and the program left out, less the worst the program
    chose and the reference did not, over the standard deviation of that
    position's finite scores. Where two sets differ in size, or a pick is one
    the reference scores ``-inf``, the side without a score takes the
    position's best or worst: the widest margin the position has."""
    import numpy as np

    chosen, scores = np.asarray(chosen), np.asarray(scores, np.float64)
    candidates = scores.shape[-1]
    finite = np.isfinite(scores)

    def mask(index):  # an index of ``candidates`` lands in a column that is cut off
        out = np.zeros((*scores.shape[:-1], candidates + 1), bool)
        np.put_along_axis(out, index, True, axis=-1)
        return out[..., :candidates]

    picked = mask(np.where((chosen >= 0) & (chosen < candidates), chosen, candidates))
    order = np.argsort(-np.where(finite, scores, -np.inf), axis=-1, kind="stable")
    ranked = mask(order[..., :depth]) & finite
    differ = (picked != ranked).any(axis=-1)
    left_out = np.where(ranked & ~picked, scores, -np.inf).max(axis=-1)
    taken = np.where(picked & ~ranked, scores, np.inf).min(axis=-1)
    best = np.where(finite, scores, -np.inf).max(axis=-1)
    worst = np.where(finite, scores, np.inf).min(axis=-1)
    spread = np.nanstd(np.where(finite, scores, np.nan), axis=-1)
    margin = (np.where(np.isfinite(left_out), left_out, best)
              - np.where(np.isfinite(taken), taken, worst)) / np.where(spread > 0, spread, 1.0)
    return differ.size, int(differ.sum()), float(margin[differ].max(initial=0.0))


class Agreement:
    """The tally of ``choice_agreement`` over sequences and kinds of choice,
    held to the check block's two limits. Without ``depths`` (a family that
    chooses nothing) it reports nothing and passes."""

    def __init__(self, depths: dict, spec: dict):
        self.depths, self.pairs, self.differ, self.worst = depths, 0, 0, 0.0
        self.share_max = float(spec["choice_disagree_max"]) if depths else None
        self.margin_max = float(spec["choice_margin_max"]) if depths else None

    def add(self, forced: dict, scores: dict) -> None:
        for name, depth in self.depths.items():
            pairs, differ, worst = choice_agreement(forced[name], scores[name], depth)
            self.pairs, self.differ = self.pairs + pairs, self.differ + differ
            self.worst = max(self.worst, worst)

    def report(self) -> dict:
        if not self.depths:
            return {"ok": True}
        share = self.differ / max(self.pairs, 1)
        return {"ok": bool(self.pairs and share <= self.share_max and self.worst <= self.margin_max),
                "choice_disagree_share": share, "choice_disagree_max": self.share_max,
                "choice_worst_margin": self.worst, "choice_margin_max": self.margin_max,
                "choice_pairs": self.pairs}


def with_picks(out):
    """A piece of a family that chooses nothing returns ``(logits, state)``."""
    return out if len(out) == 3 else (*out, {})


def logits_part(engine, cfg, params, spec: dict, seed: int, ref_forward, family, depths: dict) -> dict:
    import numpy as np

    page, steps = engine.page_size, int(spec["decode_steps"])
    prompt_lens = [int(n) for n in spec["prompt_tokens"]]
    rows = len(prompt_lens)
    width = -(-max(prompt_lens) // page) * page
    pages_per_seq = engine.max_pages_per_seq
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(0, cfg.vocab_size, size=n + steps, dtype=np.int32) for n in prompt_lens]
    ids = np.zeros((rows, width), np.int32)
    for r, (seq, n) in enumerate(zip(seqs, prompt_lens)):
        ids[r, :n] = seq[:n]
    lens = np.asarray(prompt_lens, np.int32)
    table = np.zeros((rows, pages_per_seq), np.int32)
    for r in range(rows):  # page 0 is the engine's scratch page
        table[r] = 1 + r * pages_per_seq + np.arange(pages_per_seq)
    positions = np.broadcast_to(np.arange(width, dtype=np.int32), ids.shape)

    state, prefill, decode = family.paged_pieces(engine, cfg, rows, width)
    got_prefill, state, picks = with_picks(
        prefill(params, ids, positions, lens, table[:, : width // page], state))
    got_prefill = np.asarray(got_prefill)
    picks_prefill = {name: np.asarray(picks[name]) for name in depths}
    got_decode, picks_decode = [], []
    for t in range(steps):
        tok = np.asarray([seq[n + t] for seq, n in zip(seqs, prompt_lens)], np.int32)
        logits, state, picks = with_picks(decode(params, tok, lens + t, table, state))
        got_decode.append(np.asarray(logits))
        picks_decode.append({name: np.asarray(picks[name]) for name in depths})

    agreement = Agreement(depths, spec)
    got_p, want_p, got_d, want_d, free_p, free_d = [], [], [], [], [], []
    for r, (seq, n) in enumerate(zip(seqs, prompt_lens)):
        # what the program chose at every layer and position of this row
        forced = {name: np.concatenate(
            [picks_prefill[name][:, r, :n], np.stack([p[name][:, r] for p in picks_decode], axis=1)],
            axis=1) for name in depths}
        want, scores = ref_forward(seq, forced)
        agreement.add(forced, scores)
        got_p.append(got_prefill[r, :n])
        want_p.append(want[:n])
        got_d.append(np.stack([got_decode[t][r] for t in range(steps)]))
        want_d.append(want[n: n + steps])
        if depths:  # for the record: the reference left to its own choices
            free = ref_forward(seq, None)[0]
            free_p.append(free[:n])
            free_d.append(free[n: n + steps])
    got_p, want_p, got_d, want_d = (np.concatenate(x) for x in (got_p, want_p, got_d, want_d))
    finite = bool(all(np.isfinite(x).all() for x in (got_p, want_p, got_d, want_d)))
    # pooled over every position of every row: one number each, on one scale
    worst_prefill, worst_decode = rel_rms(got_p, want_p), rel_rms(got_d, want_d)
    tol, ratio = float(spec["rel_rms_tol"]), float(spec["decode_over_prefill_max"])
    agreed = agreement.report()
    agreed_ok = agreed.pop("ok")
    unforced = {"unforced_prefill_rel_rms": rel_rms(got_p, np.concatenate(free_p)),
                "unforced_decode_rel_rms": rel_rms(got_d, np.concatenate(free_d))} if depths else {}
    return {
        "ok": bool(finite and worst_prefill <= tol and worst_decode <= tol
                   and worst_decode <= ratio * worst_prefill and agreed_ok),
        "prefill_rel_rms": worst_prefill, "decode_rel_rms": worst_decode,
        "decode_over_prefill": worst_decode / max(worst_prefill, 1e-30),
        "tolerance": tol, "decode_over_prefill_max": ratio, "finite": finite,
        "sequences": prompt_lens, "decode_steps": steps, **agreed, **unforced,
    }


def replayed_picks(engine, cfg, params, family, depths: dict, seqs: list) -> list:
    """What the program chooses over whole sequences (a prompt with its
    served answer): the family's teacher-forced prefill piece, a sequence a
    call, written to the scratch page. → one ``{name: [layers, T, k]}`` each."""
    import numpy as np

    page = engine.page_size
    width = -(-max(len(seq) for seq in seqs) // page) * page
    state, prefill, _ = family.paged_pieces(engine, cfg, 1, width)
    positions = np.arange(width, dtype=np.int32)[None]
    out = []
    for seq in seqs:
        ids = np.zeros((1, width), np.int32)
        ids[0, : len(seq)] = seq
        _, _, picks = prefill(params, ids, positions, np.asarray([len(seq)], np.int32),
                              np.zeros((1, width // page), np.int32), state)
        out.append({name: np.array(picks[name])[:, 0, : len(seq)] for name in depths})
    return out


def plain_run_all(engine, prompts, max_new_tokens):
    """The engine's door as every family goes through it; no picks handed back."""
    return engine.run_all(prompts, max_new_tokens=max_new_tokens), [None] * len(prompts)


def served_part(engine, spec: dict, seed: int, ref_forward, agreement: Agreement, replay,
                run_all=plain_run_all) -> dict:
    """``run_all`` is ``plain_run_all`` or a choosing family's ``served``:
    the same door, asked for each request's picks as well."""
    import numpy as np

    rng = random.Random(f"check-{seed}")
    text = lambda n: "".join(rng.choice("abcdefghijklmnopqrstuvwxyz ,.") for _ in range(n))  # noqa: E731
    head = text(int(spec["shared_head_chars"]))
    prompts = [head + text(int(n) - len(head)) for n in spec["prompt_chars"]]
    new = int(spec["new_tokens"])
    # the first alone and cold; the rest together, over the head it cached
    (first, first_picks), (rest, rest_picks) = run_all(engine, prompts[:1], new), run_all(engine, prompts[1:], new)
    results, handed = first + rest, list(first_picks) + list(rest_picks)

    worst_gap = worst_lp = 0.0
    problems, answers = [], []
    for i, (prompt, res) in enumerate(zip(prompts, results)):
        ids = engine.tokenizer.encode(prompt, add_bos=True)
        if res.prompt_tokens != len(ids) or res.finish_reason not in ("stop", "length") \
                or not res.tokens or res.logprob_count != len(res.tokens):
            problems.append(f"request {i}: {res.finish_reason}, {res.prompt_tokens} prompt tokens "
                            f"of {len(ids)}, {len(res.tokens)} tokens, {res.logprob_count} logprobs")
            continue
        if i and res.prefix_hit_tokens < engine.page_size:
            problems.append(f"request {i}: no cached prior ({res.prefix_hit_tokens} hit tokens)")
        if len(ids) - res.prefix_hit_tokens <= engine.prefill_chunk:
            problems.append(f"request {i}: prefill was not chunked")
        answers.append((ids, res, handed[i]))
    # what the teacher-forced pieces choose over the same tokens, and over it
    # every pick the engine itself handed back (a negative one is none: a
    # position the radix cache served was routed by an earlier request)
    forced = replay([ids + list(res.tokens) for ids, res, _ in answers]) \
        if agreement.depths and answers else [{} for _ in answers]
    from_engine = total = 0
    for (ids, res, own), picks in zip(answers, forced):
        for name, replayed in picks.items() if own else ():
            got = np.asarray(own[name])[:, : replayed.shape[1]]
            replayed[:, : got.shape[1]] = np.where(got >= 0, got, replayed[:, : got.shape[1]])
            from_engine += int((got >= 0).all(axis=-1).sum())
        total += sum(p.shape[0] * p.shape[1] for p in picks.values())
        want, scores = ref_forward(np.asarray(ids + list(res.tokens), np.int32), picks)
        agreement.add(picks, scores)
        rows = want[len(ids) - 1: len(ids) - 1 + len(res.tokens)].astype(np.float64)
        at_token = rows[np.arange(len(res.tokens)), res.tokens]
        top = rows.max(axis=-1)
        # rounding errors grow with a logit's size and the served tokens sit
        # at the top: the scale is the mean top logit, not all logits' spread
        scale = float(np.abs(top).mean())
        logprob = at_token - (top + np.log(np.exp(rows - top[:, None]).sum(axis=-1)))
        worst_gap = max(worst_gap, float((top - at_token).max()) / scale)
        worst_lp = max(worst_lp,
                       abs(res.logprob_sum / res.logprob_count - float(logprob.mean())) / scale,
                       abs(res.logprob_min - float(logprob.min())) / scale)
    gap_tol, lp_tol = float(spec["token_gap_tol"]), float(spec["logprob_tol"])
    agreed = agreement.report()
    agreed_ok = agreed.pop("ok")
    return {
        "ok": bool(not problems and worst_gap <= gap_tol and worst_lp <= lp_tol and agreed_ok),
        "token_gap": worst_gap, "token_gap_tol": gap_tol,
        "logprob_err": worst_lp, "logprob_tol": lp_tol, "problems": problems,
        "requests": len(results), "tokens": [len(r.tokens) for r in results],
        "prefix_hit_tokens": [r.prefix_hit_tokens for r in results],
        "prefill_tokens": engine.stats()["prefill_tokens"], **agreed,
        **({"choices_from_engine_share": from_engine / max(total, 1)} if agreement.depths else {}),
    }


def run_check(model: dict, spec: dict, seed: int, kv_quant: str = "none",
              tamper=None, variant: str = "none") -> dict:
    """``tamper(reference_kwargs)`` lets a test break the reference on
    purpose (wrong theta, ...); ``variant`` degrades the program's side."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import load_family
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    family = load_family(model)
    layers, page = int(spec["layers"]), int(spec["page_size"])
    served = spec["served"]
    # the page window holds the longest sequence of either part; the engine
    # keeps the answer's length again in reserve before it truncates a prompt
    longest = max(max(spec["prompt_tokens"]) + int(spec["decode_steps"]),
                  max(served["prompt_chars"]) + 1 + 2 * int(served["new_tokens"]) + 4)
    # positions end with the page window: the engine folds RoPE's table for
    # ``max_len`` positions into every decode program as a constant, 45 MB a
    # program at 32k positions, and a run's programs then outgrow a compile
    # cache held to 192 MiB, so that every run compiles anew (my chip runs, PR 24)
    max_len = (longest // page + 1) * page
    cfg = family.check_config(model, layers, max_len)

    @jax.jit
    def make(key):
        tree = family.init_params(key, cfg)
        # what a checkpoint holds: matrices in bf16, norm scales in float32
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16) if family.is_matrix(a) else a, tree)

    params = make(jax.random.PRNGKey(seed % (2 ** 31)))
    reference = importlib.import_module(family.REFERENCE)
    ref_kwargs = family.reference_kwargs(model)
    if tamper is not None:
        ref_kwargs = tamper(ref_kwargs)
    ref_params = jax.tree_util.tree_map(
        jnp.asarray, family.reference_params(jax.device_get(params), layers))
    if variant.startswith("weights"):  # the true weights live on in ``ref_params`` alone
        params = degrade(params, variant, family.is_matrix)
    engine = ContinuousBatchingEngine(
        model_config=cfg, params=params,
        max_slots=max(len(spec["prompt_tokens"]), len(served["prompt_chars"]) - 1),
        page_size=page, max_pages_per_seq=longest // page + 1,
        steps_per_tick=int(served["steps_per_tick"]), prefill_chunk=int(served["prefill_chunk"]),
        prefix_cache=True, kv_quant="int8" if variant == "kv_int8" else kv_quant)
    del params  # the engine holds what it serves (``engine.params``)

    # what the family's forward decides by rank, and how many the REFERENCE takes of each
    depths = {name: int(ref_kwargs[key]) for name, key in getattr(family, "CHOICES", {}).items()}
    if depths:
        ref_jit = jax.jit(lambda p, x, forced: reference.forward(p, x, forced=forced, **ref_kwargs))
    else:
        ref_jit = jax.jit(lambda p, x, forced: (reference.forward(p, x, **ref_kwargs), {}))

    def ref_forward(ids, forced):
        """→ (logits [T, V], scores {name: [layers, T, candidates]}); with
        ``forced {name: [layers, T, k]}`` the reference takes those picks in
        place of its own, with ``None`` it is left to its own."""
        # one compiled length for every sequence: attention is causal, so
        # what follows a sequence changes nothing before its end
        padded = np.zeros(max_len, np.int32)
        padded[: len(ids)] = ids
        if forced is not None:  # past the end: the first k candidates, which nothing reads
            forced = {name: np.concatenate(
                [picks, np.broadcast_to(np.arange(picks.shape[-1], dtype=picks.dtype),
                                        (picks.shape[0], max_len - picks.shape[1], picks.shape[-1]))],
                axis=1) for name, picks in forced.items()}
        logits, scores = ref_jit(ref_params, jnp.asarray(padded), forced)
        return (np.asarray(logits)[: len(ids)],
                {name: np.asarray(s)[:, : len(ids)] for name, s in scores.items()})

    logits = logits_part(engine, cfg, engine.params, spec, seed, ref_forward, family, depths)
    answers = served_part(
        engine, served, seed, ref_forward, Agreement(depths, spec),
        lambda seqs: replayed_picks(engine, cfg, engine.params, family, depths, seqs),
        getattr(family, "served", plain_run_all))
    out = {
        "ok": bool(logits.pop("ok") & answers.pop("ok")), **logits,
        **{f"served_{k}": v for k, v in answers.items()},
        "layers": layers, "variant": variant, "kv_quant": engine.kv_quant,
        "paged_attention": engine.stats().get("paged_attention"),
        "platform": jax.devices()[0].platform,
    }
    # every number held to a limit, beside it: what a run's last lines carry
    limits = {"prefill_rel_rms": "tolerance", "decode_rel_rms": "tolerance",
              "decode_over_prefill": "decode_over_prefill_max",
              "served_token_gap": "served_token_gap_tol", "served_logprob_err": "served_logprob_tol"}
    for part in ("", "served_") if depths else ():
        limits.update({f"{part}choice_disagree_share": f"{part}choice_disagree_max",
                       f"{part}choice_worst_margin": f"{part}choice_margin_max"})
    out["compared"] = {name: [out[name], out[limit]] for name, limit in limits.items()}
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", type=Path, required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearsal", action="store_true")
    parser.add_argument("--variant", choices=VARIANTS, nargs="+", default=["none"],
                        help="several: one object a variant, one process (how PERF.md's table was made)")
    args = parser.parse_args()
    t0 = time.perf_counter()
    model = json.loads(args.config.read_text())
    if args.rehearsal:
        model = {**model, **model["rehearsal"]}
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_compile_cache")
    for variant in args.variant:
        out = run_check(model, model["check"], args.seed,
                        model["serve_env"].get("KV_QUANT", "none"), variant=variant)
        out["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
