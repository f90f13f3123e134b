"""Checkpoint family ``llama``: dense decoders that the program runs through
``LlamaConfig`` (RMSNorm, RoPE, GQA, SwiGLU, untied head) — Mistral, Yi.

A family file gives the harness everything that depends on the architecture;
``check.py``, ``run.py``, ``readers.py`` and ``trace.py`` name no model, no
config class and no kind of state a token leaves behind. The contract:

for the served run (``run.py``)
* ``program_config(model)`` — the published ``config.json`` keys of a
  configuration file mapped onto the program's own config fields, every
  field of the program's config class (``/info`` must report them, and
  ``check_config`` builds its object from them);
* ``WIDTHS`` — ``{published key: field of that object}``: which keys of the
  file this family holds the program to, unchanged. It is how a
  configuration is held to ITS family: ``tests/benchmark/
  test_benchmark_costs.py`` asks ``load_family`` of every file it globs, and
  asserts that ``program_config`` builds the object ``check_config`` returns
  at the file's depth and positions and that every key here reads the same
  in the file and in that object (here: ``hidden_size → dim``,
  ``intermediate_size → mlp_dim``, ``num_attention_heads → n_heads``,
  ``num_key_value_heads → n_kv_heads``, ``head_dim``, ``vocab_size``,
  ``num_hidden_layers → n_layers``, ``rope_theta``, ``rms_norm_eps →
  norm_eps``, ``max_position_embeddings → max_len``, ``torch_dtype →
  dtype``). A family whose heads are not ``hidden / heads`` wide, or that
  has no ``rms_norm_eps``, states its own keys and the test names no other;
* ``write_checkpoint(path, model, seed)`` — seeded bf16 weights in the
  program's checkpoint format (``LLM_CHECKPOINT`` is the surface a user has);
* ``pool_bytes(model, env)`` — the bytes ``/info`` must report for the pool
  the server's environment asks for (here pages of K and V; a family with
  state per slot, or a third stream per token, counts it here);

for the reference check (``check.py``)
* ``check_config(model, layers, max_len)`` — the program's config object (it
  has a ``vocab_size``) at the check's depth and positions;
* ``init_params(key, cfg)`` — the program's own seeded initialiser, and
  ``is_matrix(leaf)`` — which leaves a checkpoint holds in bf16 (the cast,
  and ``degrade``'s coarser types, follow it);
* ``REFERENCE`` — the module of the plain reference (``forward(params, ids,
  **kwargs)``), ``reference_kwargs(model)`` — what it takes from the
  configuration (a test tampers with these), ``reference_params(tree,
  n_layers)`` — the program's tree under the reference's flat names;
* ``paged_pieces(engine, cfg, rows, width)`` — the teacher-forced pieces the
  served programs are made of: what the engine's pool holds, a prefill that
  fills it, a decode step that reads and extends it;

* ``CHOICES`` (only a family whose forward decides something by RANK: the
  experts a token goes to, the positions a query attends to) — ``{name:
  keyword}``: each kind of choice, and the keyword of ``reference_kwargs``
  that says how many the reference takes (``{"experts":
  "experts_per_token"}``). A family without it, like this one, goes through
  the check as it always did. With it (``check.py``'s docstring says why):
  each piece of ``paged_pieces`` returns ``(logits, state, chosen)``,
  ``chosen[name]`` int32 being what the PROGRAM chose in that call, a layer,
  a position and ``k`` picks deep — prefill ``[layers, rows, width, k]``,
  decode ``[layers, rows, k]``, a negative pick for none — read out of the
  timed code's own routing, not computed beside it; ``REFERENCE.forward(
  params, ids, forced=..., **kwargs)`` returns ``(logits, scores)``: with
  ``forced[name] [layers, T, k]`` it takes those picks in place of its own
  (gates renormalised over them, as published), with ``forced=None`` it is
  left to its own, and ``scores[name] [layers, T, candidates]`` are its own
  float32 scores on the trajectory it ran (``-inf`` for a candidate a
  position may not take; any monotone form of what it ranks). Several names
  a layer and any depth go through the same code; experts are what is
  proven (``tests/benchmark``). The configuration's ``check`` block then
  holds ``choice_disagree_max`` and ``choice_margin_max`` with the readings
  they came from;
* ``served(engine, prompts, max_new_tokens)`` (optional, with ``CHOICES``) →
  ``(results, picks)``: the requests through ``engine.run_all`` and nothing
  else of the engine, asking it for what each REQUEST was routed by, one
  ``{name: [layers, prompt + answer tokens - 1, k]}`` a result, negative
  where it cannot say. Without it the check replays each answer through the
  prefill piece, which one seed in twelve does not survive (``check.py``);

for the rooflines (``readers.py``; the yardstick a later PR cannot edit)
* ``decode_substep_cost(model, rows, context_tokens)`` — bytes and
  operations of one decode sub-step of the whole model;
* ``KERNEL_COSTS[name](model, rows, context_tokens)`` — bytes and operations
  of ONE call of a named kernel (a metric file's ``cost`` names the entry).

For this family the check's pieces are: the admission forward
(``engine.forward_fn``) writing a fresh ``init_cache``, ``scatter_prefill`` of
its K and V into the engine's page pool (``engine.pool.k``, ``engine.pool.v``),
and ``paged_decode_forward`` over that pool with the engine's own kernel
selection (the Pallas page-table walk on a TPU). The state a token leaves is
K and V and nothing else. The reference is ``benchmark/reference.py``.

Another family (``moe``) is another file beside this one, chosen by the
``family`` key of the configuration file, with its own reference file. That
its files are ENOUGH is proven by laying them into a copy of ``benchmark/``
and running the copy: ``tests/benchmark/test_benchmark_second_family.py``
takes a routed family (``tests/benchmark/scratch_moe_family.py``) through
``run.py``, ``server.py`` and ``check.py`` as child processes, and through
the tests that glob configurations, mixes and metric files.
"""

from __future__ import annotations

import concurrent.futures
import os
from pathlib import Path

import ml_dtypes
import numpy as np

BYTES_BF16 = 2


# published key → field of the program's config object (``LlamaConfig``;
# ``head_dim`` is its property, hidden over heads)
WIDTHS = {
    "hidden_size": "dim", "intermediate_size": "mlp_dim",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_len", "torch_dtype": "dtype",
}


def program_config(model: dict) -> dict:
    """Published keys → ``sentio_tpu.models.llama.LlamaConfig`` fields."""
    return dict(
        vocab_size=int(model["vocab_size"]),
        dim=int(model["hidden_size"]),
        n_layers=int(model["num_hidden_layers"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        mlp_dim=int(model["intermediate_size"]),
        max_len=int(model["max_position_embeddings"]),
        rope_theta=float(model["rope_theta"]),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        norm_eps=float(model["rms_norm_eps"]),
    )


# ------------------------------------------------------------ seeded weights


def normal_bf16(rng: np.random.Generator, shape, std: float) -> np.ndarray:
    return (rng.standard_normal(shape, dtype=np.float32) * std).astype(
        ml_dtypes.bfloat16)


def _dense(rng, n_in: int, n_out: int) -> dict:
    return {"kernel": normal_bf16(rng, (n_in, n_out), n_in ** -0.5)}


def _layer(rng, cfg: dict) -> dict:
    dim, mlp = cfg["dim"], cfg["mlp_dim"]
    kv_dim = cfg["n_kv_heads"] * (dim // cfg["n_heads"])
    return {
        "attn_norm": {"scale": np.ones((dim,), np.float32)},
        "attn": {"wq": _dense(rng, dim, dim), "wk": _dense(rng, dim, kv_dim),
                 "wv": _dense(rng, dim, kv_dim), "wo": _dense(rng, dim, dim)},
        "mlp_norm": {"scale": np.ones((dim,), np.float32)},
        "mlp": {"w_gate": _dense(rng, dim, mlp), "w_up": _dense(rng, dim, mlp),
                "w_down": _dense(rng, mlp, dim)},
    }


def parallel_layers(make, cfg: dict, seeds) -> dict:
    """One generator per layer, so layers fill in parallel (numpy releases
    the GIL while sampling) and the tree depends on the seed alone."""
    with concurrent.futures.ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as pool:
        built = list(pool.map(lambda s: make(np.random.default_rng(s), cfg), seeds))
    return {f"layers_{i}": layer for i, layer in enumerate(built)}


# ByteTokenizer's 256 bytes and 5 specials (pad, bos, eos, cls, sep)
TEXT_IDS = 261


def seeded_tree(cfg: dict, seed: int, layer) -> dict:
    """Embedding, head, final norm and ``layer(rng, cfg)`` for every layer, in
    bf16, from ``seed`` (``cfg``: the program's config fields; a family with
    this trunk and other layers passes its own ``layer``).

    The head's columns for ``TEXT_IDS`` are zero, so those logits are 0 and a
    greedy answer never holds one (the largest of the other 32k logits is
    above 0): no seed's model ends an answer early on EOS, and every answer
    token renders as one replacement character of 3 bytes. The audit's
    prompt, which quotes the answer, is then as long as the mix declares for
    EVERY seed; a shorter one lands in a prefill program the warm-up never
    compiled."""
    head, *layer_seeds = np.random.SeedSequence(seed).spawn(1 + cfg["n_layers"])
    rng = np.random.default_rng(head)
    embedding = normal_bf16(rng, (cfg["vocab_size"], cfg["dim"]), 0.02)
    lm_head = _dense(rng, cfg["dim"], cfg["vocab_size"])
    lm_head["kernel"][:, :TEXT_IDS] = 0
    return {
        "embed_tokens": {"embedding": embedding},
        "lm_head": lm_head,
        "final_norm": {"scale": np.ones((cfg["dim"],), np.float32)},
        **parallel_layers(layer, cfg, layer_seeds),
    }


def make_params(model: dict, seed: int) -> dict:
    """The tree of the program's ``init_llama``, in bf16, from ``seed``."""
    return seeded_tree(program_config(model), seed, _layer)


def write_checkpoint(path: Path, model: dict, seed: int) -> None:
    from sentio_tpu.runtime.checkpoint import save_pytree

    save_pytree(path, make_params(model, seed),
                meta={"family": "llama", "config": program_config(model)})


# ------------------------------------------------ bytes and operations


def weight_bytes(model: dict) -> dict:
    """Bytes of bf16 weights: per layer, embeddings, head."""
    d, m = model["hidden_size"], model["intermediate_size"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    q = model["num_attention_heads"] * model["head_dim"]
    layer = (d * q + 2 * d * kv + q * d + 3 * d * m) * BYTES_BF16
    table = model["vocab_size"] * d * BYTES_BF16
    return {"layer": layer, "embed": table, "head": table,
            "layers": layer * model["num_hidden_layers"]}


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token over all layers, bf16 pages."""
    return (2 * model["num_key_value_heads"] * model["head_dim"] * BYTES_BF16
            * model["num_hidden_layers"])


def decode_substep_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """One decode sub-step over ``rows`` slots (every slot of the fixed batch
    is computed, occupied or not) whose occupied rows hold
    ``context_tokens`` tokens of KV in total.

    bytes: every layer's weights and the head once (the embedding is a
    gather of ``rows`` rows), plus the KV the attention must read;
    activations are small against both and left out (the share is then a
    little high, never low).
    flops: 2 per multiply-add of every matmul per row, plus the attention's
    QK and PV over the context.
    """
    w = weight_bytes(model)
    d = model["hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    n_layers = model["num_hidden_layers"]
    bytes_ = (w["layers"] + w["head"] + rows * d * BYTES_BF16
              + context_tokens * kv_bytes_per_token(model))
    matmul = 2 * rows * (w["layers"] + w["head"]) / BYTES_BF16
    attn = 4 * context_tokens * q * n_layers
    return {"bytes": float(bytes_), "flops": float(matmul + attn)}


def pool_bytes(model: dict, env: dict) -> int:
    """The page pool the server's environment asks for: the scratch page and
    ``slots x pages`` more, ``page`` tokens each, K and V of every layer."""
    pages = 1 + int(env["LLM_MAX_BATCH"]) * int(env["KV_MAX_PAGES_PER_SEQ"])
    return pages * int(env["KV_PAGE_SIZE"]) * kv_bytes_per_token(model)


def paged_attention_cost(model: dict, rows: int, context_tokens: float) -> dict:
    """ONE call of the decode attention kernel: one layer of one sub-step,
    over ``rows`` slots whose occupied rows hold ``context_tokens`` tokens.

    bytes: K and V of those tokens in this layer — what the rows HOLD, not
    the pages their tables name. The queries in and the result out
    (``rows x heads x head_dim``, twice, under 2 % of the K and V at the
    cells' sizes) are left out, so the share reads a little low, never high.
    flops: QK and PV over the context, 2 per multiply-add.
    """
    q = model["num_attention_heads"] * model["head_dim"]
    bytes_ = context_tokens * kv_bytes_per_token(model) / model["num_hidden_layers"]
    return {"bytes": float(bytes_), "flops": float(4 * context_tokens * q)}


KERNEL_COSTS = {"paged_attention": paged_attention_cost}


# ------------------------------------------------------ the reference check

REFERENCE = "benchmark.reference"


def check_config(model: dict, layers: int, max_len: int):
    from sentio_tpu.models.llama import LlamaConfig

    return LlamaConfig(**{**program_config(model), "n_layers": layers, "max_len": max_len})


def init_params(key, cfg) -> dict:
    from sentio_tpu.models.llama import init_llama

    return init_llama(key, cfg)


def is_matrix(leaf) -> bool:
    """What a checkpoint holds in bf16; norm scales stay float32."""
    return leaf.ndim == 2


def reference_kwargs(model: dict) -> dict:
    return dict(n_heads=int(model["num_attention_heads"]),
                n_kv_heads=int(model["num_key_value_heads"]),
                head_dim=int(model["head_dim"]), rope_theta=float(model["rope_theta"]),
                norm_eps=float(model["rms_norm_eps"]))


def paged_pieces(engine, cfg, rows: int, width: int):
    """→ ``(state, prefill, decode)``. ``state`` is what the engine's pool
    holds (K pages, V pages); ``prefill(params, ids, positions, lens, blocks,
    state)`` runs the admission forward over ``[rows, width]`` ids into a
    fresh cache and scatters it into the pages ``blocks [rows, width/page]``
    names, → ``(logits [rows, width, V], state)``; ``decode(params, tok,
    lens, table, state)`` is one step through the pool with the engine's
    kernel selection, → ``(logits [rows, V], state)``."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.models.llama import init_cache
    from sentio_tpu.runtime.paged import paged_decode_forward, scatter_prefill

    forward_fn, attn_impl = engine.forward_fn, engine._attn_impl

    @jax.jit
    def prefill(params, ids, positions, lens, blocks, state):
        k_pages, v_pages = state
        cache = init_cache(cfg, rows, width)
        pad = jnp.arange(width)[None, :] < lens[:, None]
        logits, cache = forward_fn(params, cfg, ids, positions=positions, cache=cache,
                                   cache_index=0, pad_mask=pad)
        return logits, scatter_prefill(k_pages, v_pages, cache["k"], cache["v"], blocks)

    @jax.jit
    def decode(params, tok, lens, table, state):
        k_pages, v_pages = state
        logits, k_pages, v_pages = paged_decode_forward(
            params, cfg, tok, lens, table, k_pages, v_pages, attn_impl=attn_impl)
        return logits, (k_pages, v_pages)

    return (engine.pool.k, engine.pool.v), prefill, decode


def _f32(a) -> np.ndarray:
    return np.asarray(a, np.float32)


def reference_trunk(tree: dict) -> dict:
    """Embedding, head and final norm under the reference's names, and an
    empty ``layers`` (a family with this trunk and other layers fills it)."""
    return {"embed": _f32(tree["embed_tokens"]["embedding"]),
            "head": _f32(tree["lm_head"]["kernel"]),
            "final_norm": _f32(tree["final_norm"]["scale"]), "layers": []}


def reference_attention(lp: dict) -> dict:
    """One layer's norms and attention matrices under the reference's names."""
    return {"attn_norm": _f32(lp["attn_norm"]["scale"]), "mlp_norm": _f32(lp["mlp_norm"]["scale"]),
            **{k: _f32(lp["attn"][k]["kernel"]) for k in ("wq", "wk", "wv", "wo")}}


def reference_params(tree: dict, n_layers: int) -> dict:
    """The program's tree → the flat float32 names of ``reference.py``."""
    out = reference_trunk(tree)
    for i in range(n_layers):
        lp = tree[f"layers_{i}"]
        out["layers"].append({**reference_attention(lp), **{
            k: _f32(lp["mlp"][k]["kernel"]) for k in ("w_gate", "w_up", "w_down")}})
    return out
