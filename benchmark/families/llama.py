"""Checkpoint family ``llama``: dense decoders that the program runs through
``LlamaConfig`` (RMSNorm, RoPE, GQA, SwiGLU, untied head) — Mistral, Yi.

A family file gives the harness everything that depends on the architecture:

* ``program_config(model)`` — the published ``config.json`` keys of a
  configuration file mapped onto the program's own config fields;
* ``write_checkpoint(path, model, seed)`` — seeded bf16 weights in the
  program's checkpoint format (``LLM_CHECKPOINT`` is the surface a user has);
* ``decode_substep_cost`` — the bytes and floating-point operations the
  algorithm needs, from shapes alone (the yardstick for the roofline share;
  a later PR cannot edit it);
* ``reference_params(tree)`` — the program's parameter tree renamed into the
  flat names ``benchmark/reference.py`` takes.

Another family (``moe``) is another file beside this one, chosen by the
``family`` key of the configuration file.
"""

from __future__ import annotations

import concurrent.futures
import os
from pathlib import Path

import ml_dtypes
import numpy as np

BYTES_BF16 = 2


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


def make_params(model: dict, seed: int) -> dict:
    """The tree of the program's ``init_llama``, in bf16, from ``seed``.

    The head's columns for ``TEXT_IDS`` are zero, so those logits are 0 and a
    greedy answer never holds one (the largest of the other 32k logits is
    above 0): no seed's model ends an answer early on EOS, and every answer
    token renders as one replacement character of 3 bytes. The audit's
    prompt, which quotes the answer, is then as long as the mix declares for
    EVERY seed; a shorter one lands in a prefill program the warm-up never
    compiled."""
    cfg = program_config(model)
    head, *layer_seeds = np.random.SeedSequence(seed).spawn(1 + cfg["n_layers"])
    rng = np.random.default_rng(head)
    embedding = normal_bf16(rng, (cfg["vocab_size"], cfg["dim"]), 0.02)
    lm_head = _dense(rng, cfg["dim"], cfg["vocab_size"])
    lm_head["kernel"][:, :TEXT_IDS] = 0
    return {
        "embed_tokens": {"embedding": embedding},
        "lm_head": lm_head,
        "final_norm": {"scale": np.ones((cfg["dim"],), np.float32)},
        **parallel_layers(_layer, cfg, layer_seeds),
    }


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


# -------------------------------------------------------------- reference


def reference_params(tree: dict, n_layers: int) -> dict:
    """The program's tree → the flat float32 names of ``reference.py``."""
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    out = {
        "embed": f32(tree["embed_tokens"]["embedding"]),
        "head": f32(tree["lm_head"]["kernel"]),
        "final_norm": f32(tree["final_norm"]["scale"]),
        "layers": [],
    }
    for i in range(n_layers):
        lp = tree[f"layers_{i}"]
        out["layers"].append({
            "attn_norm": f32(lp["attn_norm"]["scale"]),
            "wq": f32(lp["attn"]["wq"]["kernel"]), "wk": f32(lp["attn"]["wk"]["kernel"]),
            "wv": f32(lp["attn"]["wv"]["kernel"]), "wo": f32(lp["attn"]["wo"]["kernel"]),
            "mlp_norm": f32(lp["mlp_norm"]["scale"]),
            "w_gate": f32(lp["mlp"]["w_gate"]["kernel"]), "w_up": f32(lp["mlp"]["w_up"]["kernel"]),
            "w_down": f32(lp["mlp"]["w_down"]["kernel"]),
        })
    return out
