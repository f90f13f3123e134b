"""One checkpoint, served on one device and on a tensor-parallel mesh.

``python -m sentio_tpu.eval.mesh_parity <checkpoint> --tp 4`` loads a llama
checkpoint ONCE and, in this one process (one process can drive every chip
of a host; two processes cannot share one), compares

* **prefill logits** — the model forward the paged engine's admission runs
  (``llama_forward`` writing a fresh cache), with the weights on device 0
  and with the weights placed by ``LLAMA_TP_RULES`` on a ``tp`` mesh;
* **greedy tokens** — ``ContinuousBatchingEngine.run_all`` on both
  placements, decode attention through the engine's own kernel selection
  (on a TPU: the Pallas page-table walk, inside ``shard_map`` on the mesh).

Tensor parallelism changes the order in which bf16 partial sums meet (one
psum after ``wo`` and ``w_down`` per block), so the two agree within
rounding, not bit for bit: the largest logit difference must stay within
``LOGITS_RTOL`` of the largest logit; greedy-token agreement is reported, not required — a random-init
checkpoint's near-flat logits flip an argmax at a tie.

Prints one JSON object as its last stdout line; exit code 0 when the logits
agree. ``chip_smoke.py --chips 4`` runs this as its child.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from sentio_tpu.infra.compile_cache import ensure_compile_cache

LOGITS_RTOL = 0.05  # max |logit difference| as a share of max |logit|
NEW_TOKENS = 16

PROMPTS = (
    "The systolic array multiplies matrices by streaming operand tiles.",
    "Decode attention walks the page table of each row.",
)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("checkpoint")
    parser.add_argument("--tp", type=int, default=4)
    parser.add_argument("--page-size", type=int, default=128)
    args = parser.parse_args(argv)

    ensure_compile_cache()
    import jax
    import numpy as np

    from sentio_tpu.config import MeshConfig
    from sentio_tpu.models.llama import init_cache, llama_forward
    from sentio_tpu.models.tokenizer import ByteTokenizer, batch_encode
    from sentio_tpu.parallel.mesh import build_mesh
    from sentio_tpu.parallel.sharding import LLAMA_TP_RULES, shard_params
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.weights import load_model

    t_start = time.perf_counter()
    devices = jax.devices()
    if len(devices) < args.tp:
        print(f"need {args.tp} devices for tp={args.tp}, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2
    host_params, cfg, _ = load_model(args.checkpoint, expect_family="llama")
    mesh = build_mesh(MeshConfig(dp_size=1, tp_size=args.tp),
                      devices=devices[:args.tp])
    placements = {
        "one_device": (shard_params(host_params, None, LLAMA_TP_RULES), None),
        f"tp{args.tp}": (shard_params(host_params, mesh, LLAMA_TP_RULES), mesh),
    }
    del host_params

    tokenizer = ByteTokenizer(cfg.vocab_size)
    width = 128
    ids, mask = batch_encode(tokenizer, list(PROMPTS), max_len=width,
                             add_bos=True, pad_to=width)
    positions = np.broadcast_to(np.arange(width, dtype=np.int32), ids.shape)

    @jax.jit
    def prefill_logits(params, ids, positions):
        cache = init_cache(cfg, ids.shape[0], width)
        logits, _ = llama_forward(params, cfg, ids, positions=positions,
                                  cache=cache, cache_index=0)
        return logits

    engine_kw = dict(
        model_config=cfg, tokenizer=tokenizer, max_slots=4,
        page_size=args.page_size, max_pages_per_seq=4, steps_per_tick=8,
    )
    logits, tokens, paged_attention, prefill_attention = {}, {}, {}, {}
    for name, (params, placed_on) in placements.items():
        out = np.asarray(prefill_logits(params, ids, positions), np.float32)
        logits[name] = out[mask]  # real positions only
        engine = ContinuousBatchingEngine(params=params, mesh=placed_on, **engine_kw)
        results = engine.run_all(list(PROMPTS), max_new_tokens=NEW_TOKENS)
        tokens[name] = [r.tokens for r in results]
        paged_attention[name] = engine.stats()["paged_attention"]
        prefill_attention[name] = engine.stats()["prefill_attention"]

    ref, got = logits["one_device"], logits[f"tp{args.tp}"]
    finite = bool(np.isfinite(ref).all() and np.isfinite(got).all())
    max_logit = float(np.abs(ref).max())
    max_diff = float(np.abs(ref - got).max())
    pairs = [(a, b) for row_a, row_b in zip(tokens["one_device"], tokens[f"tp{args.tp}"])
             for a, b in zip(row_a, row_b)]
    ok = finite and max_diff <= LOGITS_RTOL * max_logit and bool(pairs)
    print(json.dumps({
        "ok": ok,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                   "count": len(devices)},
        "tp": args.tp,
        "model": {"dim": cfg.dim, "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
                  "n_kv_heads": cfg.n_kv_heads, "vocab_size": cfg.vocab_size},
        "logits_finite": finite,
        "logits_max_abs": round(max_logit, 4),
        "logits_max_abs_diff": round(max_diff, 4),
        "logits_tolerance": round(LOGITS_RTOL * max_logit, 4),
        "logits_argmax_agreement": round(float(
            (ref.argmax(-1) == got.argmax(-1)).mean()), 4),
        "greedy_token_agreement": round(
            sum(a == b for a, b in pairs) / max(len(pairs), 1), 4),
        "greedy_tokens_compared": len(pairs),
        "paged_attention": paged_attention,
        "prefill_attention": prefill_attention,
        "seconds": round(time.perf_counter() - t_start, 1),
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
