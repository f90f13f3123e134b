"""Audit targets: tiny-config engines + per-family variant lowering.

The audit builds REAL engines at micro scale (1-layer, dim-16 model on
CPU), asks each for its declared compile-variant space
(``compile_variant_space()`` — derived from the same ``bucket_size`` /
``_prefill_width`` / ``_prior_bucket`` / tick-ladder helpers the serving
paths call), and abstractly lowers every declared variant through the
engine's OWN jitted functions. Nothing here re-implements a signature: the
args handed to ``.lower()`` are the engine's live state arrays plus
host-numpy call args shaped exactly like ``_dispatch_tick`` /
``_prefill_chunk`` would shape them.

Variant-space honesty notes:

* the spaces scale with engine config — the micro configs here keep the
  tier-1 lowering count at ~100; a production-config audit enumerates the
  production bucket sets with the same code;
* mesh variants lower the same families with 2-device tp-sharded state and
  record the ``sdy.sharding`` argument signatures; the live params/pool
  sharding specs land in the report's ``sharding`` section.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["build_audit_report", "MICRO_VOCAB"]

MICRO_VOCAB = 320  # ByteTokenizer floor is 261


def _micro_cfg():
    from sentio_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=MICRO_VOCAB, dim=16, n_layers=1, n_heads=2, n_kv_heads=2,
        mlp_dim=32, max_len=64, rope_theta=10_000.0,
    )


def _micro_draft_cfg():
    from sentio_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=MICRO_VOCAB, dim=8, n_layers=1, n_heads=2, n_kv_heads=2,
        mlp_dim=16, max_len=64, rope_theta=10_000.0,
    )


def _variant_key(desc: dict) -> str:
    return "|".join(f"{k}={desc[k]}" for k in sorted(desc))


# ------------------------------------------------------------------- engines


def _paged_engine(prefill_chunk: Optional[int] = 8, draft: bool = False,
                  kv_quant: str = "none"):
    import jax

    from sentio_tpu.models.llama import init_llama
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    kwargs: dict = {}
    if draft:
        dcfg = _micro_draft_cfg()
        kwargs.update(
            draft_params=init_llama(jax.random.PRNGKey(7), dcfg),
            draft_config=dcfg, spec_k=2, prefill_chunk=None,
        )
    else:
        kwargs.update(prefill_chunk=prefill_chunk)
    return ContinuousBatchingEngine(
        model_config=_micro_cfg(), max_slots=2, page_size=8,
        max_pages_per_seq=4, steps_per_tick=4, max_tick_steps=8,
        use_pallas=False, kv_quant=kv_quant, **kwargs,
    )


# ------------------------------------------------------- per-family lowering


def _paged_args(eng, family: str, desc: dict):
    """(args, static_kwargs) for one paged-engine variant descriptor —
    shaped exactly like the engine's own dispatch sites shape them."""
    import numpy as np

    S = eng.max_slots
    page = eng.page_size

    def prefill_common(rows: int, width: int):
        ids = np.full((rows, width), eng.tokenizer.pad_id, np.int32)
        lens = np.ones(rows, np.int32)
        temps = np.zeros(rows, np.float32)
        scat = np.zeros((rows, width // page), np.int32)
        positions = np.zeros((rows, width), np.int32)
        return ids, positions, lens, temps, scat

    if family == "paged.step_n":
        return (
            (eng.params, np.zeros(S, np.int32), np.zeros(S, np.int32),
             np.zeros(S, bool), eng._page_table.copy(), eng.pool.k,
             eng.pool.v, eng._rng, np.zeros(S, np.float32),
             np.zeros(S, np.int32), np.zeros(S, np.int32),
             # per-slot running logprob accumulators (sum / min / count) —
             # traced [S] data like temps/budgets, no new variant axis
             np.zeros(S, np.float32), np.zeros(S, np.float32),
             np.zeros(S, np.int32)),
            {"steps": desc["steps"]},
        )
    if family == "paged.merge_admitted":
        r = desc["rows"]
        return (
            (np.zeros(S, np.int32), np.zeros(S, np.int32), np.zeros(S, bool),
             np.zeros(S, np.float32), np.zeros(S, np.float32),
             np.zeros(S, np.int32),
             np.zeros(r, np.int32), np.zeros(r, np.float32),
             np.zeros(r, np.int32), np.full(r, S, np.int32)),
            {},
        )
    if family == "paged.prefill_scatter":
        ids, positions, lens, temps, scat = prefill_common(
            desc["rows"], desc["width"])
        return (
            (eng.params, ids, positions, lens, eng._rng, temps, scat,
             eng.pool.k, eng.pool.v, np.zeros(desc["rows"], np.int32)),
            {},
        )
    if family == "paged.prior_prefill_scatter":
        rows = desc["rows"]
        ids, positions, lens, temps, scat = prefill_common(
            rows, desc["width"])
        prior = np.zeros((rows, desc["pnb"]), np.int32)
        n_prior = np.zeros(rows, np.int32)
        return (
            (eng.params, ids, positions, lens, eng._rng, temps, scat,
             eng.pool.k, eng.pool.v, prior, n_prior,
             np.zeros(rows, np.int32)),
            {"do_sample": desc["do_sample"]},
        )
    if family == "paged.draft_prefill":
        eng._ensure_draft_cache()
        rows = desc["rows"]
        ids = np.full((rows, desc["width"]), eng.tokenizer.pad_id, np.int32)
        return (
            (eng.draft_params, ids, eng._spec_dk, eng._spec_dv,
             np.full(rows, S, np.int32), np.ones(rows, np.int32)),
            {},
        )
    if family == "paged_spec.spec_tick":
        eng._ensure_draft_cache()
        steps = desc["steps"]
        return (
            (eng.params, eng.draft_params, np.zeros(S, np.int32),
             np.zeros(S, np.int32), np.zeros(S, bool),
             eng._page_table.copy(), eng.pool.k, eng.pool.v, eng._spec_dk,
             eng._spec_dv, eng._rng, np.zeros(S, np.float32),
             np.zeros(S, np.int32)),
            {"k": eng.spec_k, "out_w": steps + eng.spec_k + 1},
        )
    raise KeyError(f"no arg builder for paged family {family!r}")


def _paged_fn(eng, family: str):
    return {
        "paged.step_n": eng._step_n,
        "paged.merge_admitted": eng._merge_admitted,
        "paged.prefill_scatter": eng._prefill_scatter,
        "paged.prior_prefill_scatter": eng._prior_prefill_scatter,
        "paged.draft_prefill": getattr(eng, "_draft_prefill", None),
        "paged_spec.spec_tick": eng._spec_tick,
    }[family]


# --------------------------------------------------------------- the report


def _audit_family(name, fn, variants, arg_builder) -> dict:
    from sentio_tpu.analysis.audit.lowering import audit_variant
    from sentio_tpu.analysis.audit.registry import get_family

    fam = get_family(name)
    donate = fam.donate_argnums if fam is not None else ()
    statics = fam.static_argnames if fam is not None else ()
    entry: dict = {
        "static_argnames": list(statics),
        "donate_argnums": list(donate),
        "variant_count": len(variants),
        "variants": {},
    }
    for desc in variants:
        args, static_kwargs = arg_builder(desc)
        entry["variants"][_variant_key(desc)] = audit_variant(
            fn, donate, args, static_kwargs
        )
    return entry


def _sharding_section(mesh) -> dict:
    """Live-array sharding specs for the hot-path state: params leaves and
    the paged KV pool. A leaf whose spec string changes (e.g. silently
    replicating a tp-sharded weight) fails the manifest diff."""
    import jax

    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    out: dict = {}
    paged = ContinuousBatchingEngine(
        model_config=_micro_cfg(), max_slots=2, page_size=8,
        max_pages_per_seq=4, mesh=mesh, use_pallas=False,
    )
    for path, leaf in jax.tree_util.tree_flatten_with_path(paged.params)[0]:
        key = "params" + jax.tree_util.keystr(path)
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        out[key] = str(spec)
    out["paged.pool.k"] = str(paged.pool.k.sharding.spec)
    out["paged.pool.v"] = str(paged.pool.v.sharding.spec)

    # mesh lowering: the sdy.sharding argument signature of the hottest
    # family — replication creep inside the COMPILED artifact
    from sentio_tpu.analysis.audit.lowering import audit_variant

    mesh_variants: dict = {}
    steps = min(paged.tick_step_sizes())
    args, statics = _paged_args(paged, "paged.step_n", {"steps": steps})
    mesh_variants["paged.step_n"] = dict(
        audit_variant(paged._step_n, (5, 6), args, statics,
                      collect_shardings=True),
        variant=f"steps={steps}",
    )
    return {"state": out, "lowered": mesh_variants}


def build_audit_report(include_mesh: bool = True) -> dict:
    """Build every audit engine, lower every declared variant, and return
    the manifest-shaped report dict."""
    import jax

    from sentio_tpu.models.llama import init_llama, llama_loss

    report: dict = {"version": 1, "families": {}, "sharding": None}

    plain = _paged_engine(prefill_chunk=8)
    plain_space = plain.compile_variant_space()
    for name in ("paged.step_n", "paged.merge_admitted",
                 "paged.prefill_scatter", "paged.prior_prefill_scatter"):
        report["families"][name] = _audit_family(
            name, _paged_fn(plain, name), plain_space[name],
            lambda desc, _n=name: _paged_args(plain, _n, desc),
        )

    # kv_quant="int8": the SAME jit families lower over the {"q","s"} pool
    # pytree — audited as separate manifest entries (name@int8) so the
    # quantized variant space, its donation aliasing (the dict pool still
    # updates in place) and its static footprint are each gated on their
    # own. merge_admitted never touches the pool and needs no second entry.
    quant = _paged_engine(prefill_chunk=None, kv_quant="int8")
    quant_space = quant.compile_variant_space()
    for name in ("paged.step_n", "paged.prefill_scatter",
                 "paged.prior_prefill_scatter"):
        report["families"][name + "@int8"] = _audit_family(
            name, _paged_fn(quant, name), quant_space[name],
            lambda desc, _n=name: _paged_args(quant, _n, desc),
        )

    # the committed footprint claim: int8 pages + bf16 per-vector scales vs
    # bf16 pages at identical pool geometry. Measured at a SERVING head_dim
    # (64 — the llama/GQA families this engine serves), not the dim-16
    # lowering micro-config: per-vector scale overhead is 2/head_dim bytes,
    # so head_dim 8 would overstate it 8x. tests/test_audit.py gates the
    # <= 0.6x ratio against both this report and the committed manifest.
    from sentio_tpu.models.llama import LlamaConfig
    from sentio_tpu.runtime.paged import init_pool

    pool_cfg = LlamaConfig(
        vocab_size=MICRO_VOCAB, dim=512, n_layers=2, n_heads=8,
        n_kv_heads=2, mlp_dim=64, max_len=64, rope_theta=10_000.0,
    )
    bf16_pool = init_pool(pool_cfg, num_pages=64, page_size=16)
    int8_pool = init_pool(pool_cfg, num_pages=64, page_size=16,
                          quantized=True)
    report["pools"] = {
        "head_dim": pool_cfg.head_dim,
        "bf16_pool_bytes": bf16_pool.hbm_bytes,
        "int8_pool_bytes": int8_pool.hbm_bytes,
        "ratio": round(int8_pool.hbm_bytes / bf16_pool.hbm_bytes, 4),
    }

    spec = _paged_engine(draft=True)
    spec_space = spec.compile_variant_space()
    for name in ("paged.draft_prefill", "paged_spec.spec_tick"):
        report["families"][name] = _audit_family(
            name, _paged_fn(spec, name), spec_space[name],
            lambda desc, _n=name: _paged_args(spec, _n, desc),
        )

    # training objective (multi-chip dry-run train step): one canonical shape
    import numpy as np

    cfg = _micro_cfg()
    loss_params = init_llama(jax.random.PRNGKey(0), cfg)

    def loss_args(desc):
        b, t = desc["b"], desc["t"]
        return (
            (loss_params, cfg, np.zeros((b, t + 1), np.int32),
             np.ones((b, t + 1), np.int32)),
            {},
        )

    report["families"]["llama.loss"] = _audit_family(
        "llama.loss", llama_loss, [{"b": 2, "t": 16}], loss_args,
    )

    if include_mesh and len(jax.devices()) >= 2:
        from sentio_tpu.config import MeshConfig
        from sentio_tpu.parallel.mesh import build_mesh

        mesh = build_mesh(MeshConfig(dp_size=1, tp_size=2),
                          devices=jax.devices()[:2])
        report["sharding"] = _sharding_section(mesh)
    return report
