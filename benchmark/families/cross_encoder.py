"""Seeded weights of the reranker: the tree of the program's
``models.cross_encoder.init_cross_encoder`` in bf16 (copied from
``chip_smoke.py``, PR 21). The widths come from the configuration file's
``encoders.reranker``."""

from __future__ import annotations

from pathlib import Path

import numpy as np

from benchmark.families.llama import normal_bf16, parallel_layers


def _dense(rng, n_in: int, n_out: int) -> dict:
    return {"kernel": normal_bf16(rng, (n_in, n_out), n_in ** -0.5),
            "bias": np.zeros((n_out,), np.float32)}


def _layer(rng, cfg: dict) -> dict:
    dim, mlp = cfg["dim"], cfg["mlp_dim"]
    norm = lambda: {"scale": np.ones((dim,), np.float32),  # noqa: E731
                    "bias": np.zeros((dim,), np.float32)}
    return {
        "attn": {k: _dense(rng, dim, dim) for k in ("wq", "wk", "wv", "wo")},
        "attn_norm": norm(),
        "mlp": {"w_in": _dense(rng, dim, mlp), "w_out": _dense(rng, mlp, dim)},
        "mlp_norm": norm(),
    }


def write_checkpoint(path: Path, cfg: dict, seed: int) -> None:
    from sentio_tpu.runtime.checkpoint import save_pytree

    head, *layer_seeds = np.random.SeedSequence(seed).spawn(1 + cfg["n_layers"])
    rng = np.random.default_rng(head)
    dim = cfg["dim"]
    encoder = {
        "embed_tokens": {"embedding": normal_bf16(rng, (cfg["vocab_size"], dim), 0.02)},
        "embed_positions": {"embedding": normal_bf16(rng, (cfg["max_len"], dim), 0.02)},
        "embed_types": {"embedding": normal_bf16(rng, (cfg["n_types"], dim), 0.02)},
        "embed_norm": {"scale": np.ones((dim,), np.float32), "bias": np.zeros((dim,), np.float32)},
        **parallel_layers(_layer, cfg, layer_seeds),
    }
    params = {"encoder": encoder, "head": _dense(rng, dim, 1)}
    save_pytree(path, params, meta={"family": "cross-encoder", "config": cfg})
