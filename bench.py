"""End-to-end RAG serving benchmark. It measures the accelerator, so a run
that finds none FAILS; ``JAX_PLATFORMS=cpu`` set by the caller asks for a
rehearsal of the control flow, stamped ``cpu`` — never a device number.

Three phases, all through the DEFAULT serving path (paged KV continuous
batching — concurrent callers share fused decode dispatches):

A. **RAG e2e** — the full retrieve → rerank → select → generate → verify
   graph with every model in-process on the device, driven by N concurrent
   clients. Reports per-request p50/p95, QPS, per-node p50 breakdown, and
   decode-batch occupancy.
B. **Measured baseline** — the reference's architecture shape (HTTP hops to
   loopback mock models, python-loop retrieval math; eval/baseline.py) over
   the SAME corpus and queries. ``vs_baseline`` is measured-vs-measured: a
   deliberate LOWER bound for the reference (zero network latency, zero
   model compute — real deployments add 10-400 ms WAN per hop).
C. **Decode at scale** — continuous-batched generation on the largest
   Llama-class model that fits single-chip HBM in bf16 (~1.4B by default,
   BENCH_SERVE_SCALE=8b for an 8B-layer-geometry variant), reporting
   tokens/s, MFU (= tok/s x 2 x params / peak bf16 FLOPs), and HBM
   bandwidth utilization of the decode loop.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
Details go to stderr.

Env knobs: BENCH_FAST=1 (tiny models, quick smoke), BENCH_QUERIES=N,
BENCH_CORPUS=N, BENCH_NEW_TOKENS=N, BENCH_CONCURRENCY=N,
BENCH_SKIP_SCALE=1 (skip phase C), BENCH_SERVE_SCALE=1b|8b|moe,
BENCH_SCALE_TOKENS=N, BENCH_SPECULATIVE=1 (add phase E: plain-vs-
speculative decode on the serve-scale target, greedy-exact),
BENCH_VERIFY_SWEEP=1 (phase A once per VERIFY_MODE — sync|async|gated —
reporting p50/p95 e2e, the answer_ms/verdict_ms split, and the gate skip
rate; BENCH_VERIFY_THRESHOLD overrides the confidence gate).
"""

from __future__ import annotations

import json
import os
import sys
import time

# Peak specs for the MFU / bandwidth denominators, keyed by the
# ``device_kind`` JAX reports. A device that is not in the table is an
# error, not a default. Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s HBM per chip).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_gbs": 819.0},
}


def device_peaks() -> dict:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no peak specs for device kind {kind!r}: MFU and bandwidth "
            f"utilization have no denominator (known: {sorted(DEVICE_PEAKS)})"
        )
    return DEVICE_PEAKS[kind]


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def device_platform() -> str:
    """cpu | tpu | gpu — stamped into EVERY artifact section so a rehearsal
    can never be mistaken for a device round."""
    import jax

    return jax.default_backend()


def require_accelerator() -> None:
    """The benchmark's numbers are device numbers. With no accelerator it
    stops here — unless the CALLER set ``JAX_PLATFORMS=cpu``, which asks
    for a rehearsal (stamped ``device_platform: cpu`` in every section)."""
    plat = device_platform()
    if plat != "cpu":
        return
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        log("REHEARSAL on cpu (JAX_PLATFORMS=cpu): control flow only — no "
            "number from this run is a device metric")
        return
    raise SystemExit(
        "bench.py found no accelerator (JAX initialised on cpu) and will not "
        "measure the host in its place; set JAX_PLATFORMS=cpu to rehearse"
    )


def build_corpus(n: int) -> list:
    from sentio_tpu.models.document import Document

    topics = [
        ("tpu", "TPU v5e chips pair a 128x128 MXU systolic array with {i} MiB of VMEM; "
                "matmul throughput peaks in bfloat16 when tiles stay MXU-aligned."),
        ("jax", "JAX traces pure functions into XLA programs; version {i} introduced "
                "sharding improvements for pjit and shard_map collectives."),
        ("rag", "Retrieval augmented generation pipeline number {i} fuses BM25 with "
                "dense retrieval and reranks candidates before generation."),
        ("ir", "Classic information retrieval experiment {i} shows BM25 term "
               "saturation controlled by k1 and length normalization by b."),
        ("net", "Inter-chip interconnect study {i}: ring all-reduce bandwidth scales "
                "with torus links while DCN hops dominate cross-slice latency."),
    ]
    docs = []
    for i in range(n):
        key, template = topics[i % len(topics)]
        docs.append(
            Document(
                text=template.replace("{i}", str(i)),
                id=f"{key}-{i}",
                metadata={"source": f"{key}.md"},
            )
        )
    return docs


def count_params(params) -> int:
    import jax

    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def _percentile(vals, q):
    vals = sorted(vals)
    if not vals:
        return 0.0
    return vals[min(int(len(vals) * q), len(vals) - 1)]


def _flight_artifacts():
    """Fold the flight recorder + TTFT/TPOT histograms into artifact form:
    the tick-level occupancy timeline (downsampled to <= 160 events) with
    summary percentiles, and the per-sequence TTFT/TPOT distributions. This
    is the round-5 fix: the committed BENCH json now carries the engine's
    own per-tick record of what the decode batch did, not prose."""
    from sentio_tpu.infra.flight import get_flight_recorder
    from sentio_tpu.infra.metrics import get_metrics

    snap = get_flight_recorder().snapshot()
    ticks = snap["ticks"]
    out = {"ticks": {"n": snap["ticks_recorded"], "retained": len(ticks)}}
    if ticks:
        occ = [t.get("active_slots", 0) for t in ticks]
        dur = [t.get("dur_ms", 0.0) for t in ticks]
        queue = [t.get("queue_depth", 0) + t.get("inbox_depth", 0) for t in ticks]
        out["ticks"].update({
            "occupancy_mean": round(sum(occ) / len(occ), 2),
            "occupancy_max": max(occ),
            "dur_p50_ms": round(_percentile(dur, 0.50), 2),
            "dur_p95_ms": round(_percentile(dur, 0.95), 2),
            "queue_depth_p95": _percentile(queue, 0.95),
            "prefill_tokens": sum(t.get("prefill_tokens", 0) for t in ticks),
            "decode_tokens": sum(t.get("decode_tokens", 0) for t in ticks),
        })
        stride = -(-len(ticks) // 160)  # ceil: keeps the timeline <= 160 events
        out["ticks"]["timeline"] = [
            {"t_s": t["t_s"], "active": t.get("active_slots", 0),
             "queued": t.get("queue_depth", 0) + t.get("inbox_depth", 0),
             "free_pages": t.get("free_pages")}
            for t in ticks[::stride]
        ]
    histos = get_metrics().memory.snapshot()["histograms"]
    for label, key in (("ttft_ms", "ttft"), ("tpot_ms", "tpot")):
        merged = [h for k, h in histos.items() if k.startswith(key + "(")]
        if merged:
            h = merged[0]  # one path label in-bench ("paged")
            out[label] = {
                "p50": round(h["p50"] * 1e3, 3),
                "p95": round(h["p95"] * 1e3, 3),
                "mean": round(h["mean"] * 1e3, 3),
                "n": h["count"],
                "dropped": h["dropped"],
            }
    return out


def phase_0_rtt():
    """Raw host↔device round-trip cost: dispatch a trivial jitted op on a
    1-element array and fetch the result. It bounds every per-tick and
    per-fetch cost in the phases below, so it is published IN the artifact
    beside the latencies it sits under."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((1,), jnp.float32)
    np.asarray(f(x))  # compile
    samples = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(f(x))
        samples.append((time.perf_counter() - t0) * 1000.0)
    out = {
        "device_rtt_p50_ms": round(_percentile(samples, 0.50), 1),
        "device_rtt_min_ms": round(min(samples), 1),
    }
    log(f"phase 0: device round-trip p50={out['device_rtt_p50_ms']}ms "
        f"min={out['device_rtt_min_ms']}ms")
    return out


def phase_a_rag(settings, enc_cfg, llm_cfg, docs, queries, n_queries,
                new_tokens, concurrency, kv_quant="none", verify_mode=None):
    """Full graph with paged continuous batching, N concurrent clients.

    ``verify_mode`` (sync|async|gated, default = the settings tree's value)
    rebuilds the graph with that verification wiring — the
    BENCH_VERIFY_SWEEP driver runs this phase once per mode on the same
    corpus/queries so the off-critical-path claim lands as measurement."""
    import threading
    from dataclasses import replace as _dc_replace

    from sentio_tpu.config import EmbedderConfig, GeneratorConfig, RerankConfig
    from sentio_tpu.graph.factory import GraphConfig, build_basic_graph
    from sentio_tpu.graph.state import create_initial_state
    from sentio_tpu.ops.bm25 import BM25Index
    from sentio_tpu.ops.dense_index import TpuDenseIndex
    from sentio_tpu.ops.embedder import TpuEmbedder
    from sentio_tpu.ops.generator import LLMGenerator, TpuProvider
    from sentio_tpu.ops.reranker import CrossEncoderReranker
    from sentio_tpu.ops.retrievers import DenseRetriever, HybridRetriever, SparseRetriever
    from sentio_tpu.ops.verifier import AnswerVerifier
    from sentio_tpu.runtime.engine import GeneratorEngine
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.service import PagedGenerationService

    if verify_mode is not None:
        settings = settings.with_overrides(
            generator=_dc_replace(settings.generator, verify_mode=verify_mode)
        )
    verify_mode = settings.generator.verify_mode

    log("phase A: building corpus + indexes ...")
    embedder = TpuEmbedder(
        EmbedderConfig(provider="tpu", batch_size=128), model_config=enc_cfg
    )
    t0 = time.perf_counter()
    corpus_vecs = embedder.embed_many([d.text for d in docs])
    embed_s = time.perf_counter() - t0
    docs_per_s = len(docs) / max(embed_s, 1e-9)
    log(f"  embedded {len(docs)} docs in {embed_s:.1f}s ({docs_per_s:.0f} docs/s)")

    dense_index = TpuDenseIndex(dim=enc_cfg.dim)
    dense_index.add(docs, corpus_vecs)
    bm25 = BM25Index().build(docs)
    retriever = HybridRetriever(
        retrievers=[DenseRetriever(embedder, dense_index), SparseRetriever(bm25)],
        config=settings.retrieval,
    )
    reranker = CrossEncoderReranker(RerankConfig(batch_size=32), model_config=enc_cfg)
    engine = GeneratorEngine(
        config=GeneratorConfig(model_preset="bench", max_new_tokens=new_tokens),
        model_config=llm_cfg,
    )
    paged = ContinuousBatchingEngine(
        model_config=llm_cfg, params=engine.params, tokenizer=engine.tokenizer,
        max_slots=max(concurrency, 4), page_size=16,
        max_pages_per_seq=llm_cfg.max_len // 16, steps_per_tick=16,
        max_tick_steps=64, pipeline_depth=2, kv_quant=kv_quant,
        # random-init weights greedy-sample EOS almost immediately — fixed-
        # length generation measures the cost real tuned models actually pay
        ignore_eos=True,
    )
    service = PagedGenerationService(paged)
    generator = LLMGenerator(
        provider=TpuProvider(engine=engine, service=service), config=settings.generator
    )
    verifier = AnswerVerifier(generator=generator, config=settings.generator)
    graph = build_basic_graph(
        retriever, generator, reranker=reranker, verifier=verifier,
        config=GraphConfig(settings=settings),
    )

    log("phase A: warmup (compilation, full-concurrency burst) ...")
    t0 = time.perf_counter()
    warm_threads = [
        threading.Thread(
            target=graph.invoke,
            args=(create_initial_state(queries[i % len(queries)], metadata={"mode": "fast"}),),
        )
        for i in range(concurrency)
    ]
    for t in warm_threads:
        t.start()
    for t in warm_threads:
        t.join()
    log(f"  warmup done in {time.perf_counter() - t0:.1f}s")

    # drain the warmup pump, then zero the flight recorder + metrics so the
    # embedded tick timeline / TTFT-TPOT distributions cover ONLY the timed
    # run (warmup ticks carry multi-second jit compiles)
    from sentio_tpu.infra.flight import get_flight_recorder
    from sentio_tpu.infra.metrics import MetricsCollector, set_metrics

    t_drain = time.perf_counter()
    while service._pump is not None and service._pump.is_alive():
        if time.perf_counter() - t_drain > 10.0:
            break
        time.sleep(0.01)

    # compile accounting over the TIMED window (analysis/audit/fence.py):
    # warmup pays the jit cost up front, so a steady-state run should
    # report xla_compiles == 0 — any other number is a recompile the
    # latency percentiles silently absorbed. SENTIO_COMPILE_FENCE=1 arms
    # the fence so such a recompile fails the bench outright; the graph
    # burst above only compiled the variants its prompts happened to hit,
    # so the declared width/prior buckets are warmed explicitly first.
    from sentio_tpu.analysis.audit import fence

    if fence.enabled():
        service.warmup()
    get_flight_recorder().clear()
    set_metrics(MetricsCollector())
    compiles_before = fence.compiles_total()
    if fence.enabled():
        fence.arm()

    latencies: list[float] = []
    lat_pairs: list[tuple[int, float]] = []
    node_ms: dict[str, list[float]] = {}
    lock = threading.Lock()
    pending = [(i, queries[i % len(queries)]) for i in range(n_queries)]
    stats_before = service.stats()

    def worker():
        while True:
            with lock:
                if not pending:
                    return
                i, q = pending.pop()
            t0 = time.perf_counter()
            # ids namespaced per verify mode: the recorder is cleared per
            # phase run, but a sweep must never risk one mode's late
            # verify record merging onto another mode's id
            state = graph.invoke(create_initial_state(
                q, metadata={"mode": "fast",
                             "query_id": f"bench-{verify_mode}-{i}"}
            ))
            dt = (time.perf_counter() - t0) * 1000.0
            with lock:
                latencies.append(dt)
                lat_pairs.append((i, dt))
                for node, ms in (state["metadata"].get("node_timings_ms") or {}).items():
                    node_ms.setdefault(node, []).append(ms)

    t_run = time.perf_counter()
    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_run
    # detached verifies (async/gated) still decode on this service — join
    # them before closing it, and so their verdict_ms land on the records
    from sentio_tpu.graph.executor import wait_detached

    wait_detached(timeout_s=120.0)
    stats = service.stats()
    if fence.enabled():
        fence.disarm()
    xla_compiles = fence.compiles_total() - compiles_before
    service.close()

    # answer vs verdict split (ISSUE 11): answer_ms is what the CALLER
    # waited for the answer (graph invoke — under async/gated the graph
    # returns at the gate, so verify is already excluded; under sync the
    # recorded verdict_ms is subtracted out), verdict_ms is the audit
    # decode wherever it ran. gate_skip_rate counts skipped_confident.
    recorder = get_flight_recorder()
    answer_ms_list: list[float] = []
    verdict_ms_list: list[float] = []
    skipped = 0
    verified = 0
    for i, dt in lat_pairs:
        verify_rec = (recorder.get(f"bench-{verify_mode}-{i}") or {}).get(
            "verify") or {}
        vms = verify_rec.get("verdict_ms")
        outcome = verify_rec.get("outcome")
        if outcome == "skipped_confident":
            skipped += 1
        elif outcome in ("pass", "warn", "fail"):
            # real audit verdicts only: deadline/empty skips are neither a
            # gate payoff nor a completed verification and must not skew
            # the reported gate_skip_rate
            verified += 1
        if vms is not None:
            verdict_ms_list.append(float(vms))
        answer_ms_list.append(
            dt - float(vms) if verify_mode == "sync" and vms is not None
            else dt
        )

    ticks = stats["ticks"] - stats_before["ticks"]
    active = stats["avg_active_slots"] * stats["ticks"] - (
        stats_before["avg_active_slots"] * stats_before["ticks"]
    )
    result = {
        "p50_ms": round(_percentile(latencies, 0.50), 1),
        "p95_ms": round(_percentile(latencies, 0.95), 1),
        "qps": round(len(latencies) / wall, 2),
        "concurrency": concurrency,
        "n_queries": len(latencies),
        "node_p50_ms": {
            k: round(_percentile(v, 0.50), 1) for k, v in sorted(node_ms.items())
        },
        # per-node percentiles WITH sample counts (round-5 verdict: a p50
        # without its n is prose) + the flight recorder's tick timeline and
        # TTFT/TPOT distributions — the artifact carries its own evidence
        "node_percentiles": {
            k: {"p50_ms": round(_percentile(v, 0.50), 1),
                "p95_ms": round(_percentile(v, 0.95), 1),
                "n": len(v)}
            for k, v in sorted(node_ms.items())
        },
        **_flight_artifacts(),
        "verify": {
            "mode": verify_mode,
            "answer_ms": {
                "p50": round(_percentile(answer_ms_list, 0.50), 1),
                "p95": round(_percentile(answer_ms_list, 0.95), 1),
                "n": len(answer_ms_list),
            },
            "verdict_ms": {
                "p50": round(_percentile(verdict_ms_list, 0.50), 1),
                "p95": round(_percentile(verdict_ms_list, 0.95), 1),
                "n": len(verdict_ms_list),
            },
            "gate_skip_rate": round(
                skipped / max(skipped + verified, 1), 4),
            "skipped": skipped,
            "verified": verified,
        },
        "avg_active_slots": round(active / max(ticks, 1), 2),
        "max_active_slots": stats["max_active_slots"],
        "ingest_docs_per_s": round(docs_per_s, 1),
        "xla_compiles": xla_compiles,
        # footprint next to latency: the int8-vs-bf16 claim rides the
        # artifact as measurement (BENCH_KV_QUANT_SWEEP runs both)
        "kv_quant": kv_quant,
        "pool_hbm_bytes": paged.pool.hbm_bytes,
    }
    # radix prefix cache: fraction of admitted prompt tokens served
    # read-only from cached KV over the TIMED window (the before/after
    # deltas exclude the warmup burst, which both seeds the cache and
    # hits it at 100% on its repeats)
    hit = stats.get("prefix_hit_tokens", 0) - stats_before.get("prefix_hit_tokens", 0)
    miss = stats.get("prefix_miss_tokens", 0) - stats_before.get("prefix_miss_tokens", 0)
    if hit + miss:
        result["prefix_hit_token_ratio"] = round(hit / (hit + miss), 4)
    log(f"phase A: p50={result['p50_ms']}ms p95={result['p95_ms']}ms "
        f"qps={result['qps']} occupancy={result['avg_active_slots']} "
        f"nodes={result['node_p50_ms']} "
        f"ttft={result.get('ttft_ms')} tpot={result.get('tpot_ms')} "
        f"prefix_hit={result.get('prefix_hit_token_ratio')} "
        f"xla_compiles={result['xla_compiles']}")
    return result


def phase_b_baseline(docs, queries, n_queries, dim, rtt_ms=0.0):
    """Reference-architecture loopback baseline on the same corpus/queries.
    ``rtt_ms`` > 0 injects a per-hop delay approximating WAN latency to the
    remote model APIs the reference actually calls (still zero model
    compute, so even the rtt variant is a lower bound)."""
    from sentio_tpu.eval.baseline import measure_baseline

    log(f"phase B: measuring reference-architecture loopback baseline "
        f"(rtt={rtt_ms:.0f}ms) ...")
    qs = [(queries[i % len(queries)], "na") for i in range(n_queries)]
    result = measure_baseline(docs, qs, dim=dim, rtt_ms=rtt_ms)
    log(f"phase B: baseline(rtt={rtt_ms:.0f}) p50={result.p50_ms:.1f}ms "
        f"qps={result.qps:.2f} (zero model compute)")
    return {
        "p50_ms": round(result.p50_ms, 1),
        "p95_ms": round(result.p95_ms, 1),
        "qps": round(result.qps, 2),
        "rtt_ms": rtt_ms,
        "http_calls": result.extras.get("http_calls", {}),
    }


def serve_scale_config(kind: str):
    from sentio_tpu.models.llama import LlamaConfig

    if kind == "8b":
        # Llama-3-8B layer geometry (dim 4096 / mlp 14336 / GQA 32:8), layer
        # count cut to fit 16 GB HBM with the KV pool: ~3.5B params ~ 7 GB
        return LlamaConfig(
            vocab_size=32_000, dim=4096, n_layers=12, n_heads=32, n_kv_heads=8,
            mlp_dim=14_336, max_len=2048, rope_theta=500_000.0,
        )
    if kind == "moe":
        # Mixtral-style sparse geometry: ~2.6B total params but only ~0.8B
        # active per token (top-2 of 8 experts) — decode streams the full
        # expert weights, so tok/s vs the dense 1b shows the routing cost
        from sentio_tpu.models.moe import MoeConfig

        return MoeConfig(
            vocab_size=32_000, dim=1024, n_layers=12, n_heads=16, n_kv_heads=8,
            mlp_dim=4096, max_len=2048, rope_theta=500_000.0,
            n_experts=8, experts_per_token=2,
        )
    # ~1.4B: MXU-aligned dims, GQA 16:8
    return LlamaConfig(
        vocab_size=32_000, dim=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        mlp_dim=8192, max_len=2048, rope_theta=500_000.0,
    )


def phase_c_scale(kind: str, new_tokens: int, concurrency: int,
                  kv_quant: str = "none"):
    """Continuous-batched decode throughput at HBM-filling model scale."""
    import threading

    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.service import PagedGenerationService

    import jax

    from sentio_tpu.models.llama import init_llama
    from sentio_tpu.models.moe import MoeConfig, init_moe

    peaks = device_peaks()  # unknown device: fail before the run, not after
    cfg = serve_scale_config(kind)
    init_fn = init_moe if isinstance(cfg, MoeConfig) else init_llama
    log(f"phase C: init {kind} serve-scale model "
        f"(dim={cfg.dim} L={cfg.n_layers} vocab={cfg.vocab_size}) ...")
    t0 = time.perf_counter()
    # store weights in bf16 (init samples f32; converted checkpoints
    # arrive bf16 — f32 residency would put the 8b geometry over HBM).
    # jit fuses init+cast so only the bf16 tree materializes; an eager
    # tree_map would hold BOTH trees (17 GB) and thrash the allocator.
    init_bf16 = jax.jit(
        lambda key: jax.tree_util.tree_map(
            lambda x: x.astype(cfg.jdtype), init_fn(key, cfg)
        )
    )
    params = init_bf16(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    window = 512 if kind == "8b" else 1024
    engine = ContinuousBatchingEngine(
        model_config=cfg, params=params, max_slots=concurrency, page_size=16,
        max_pages_per_seq=window // 16, steps_per_tick=16, kv_quant=kv_quant,
        # one compiled tick size for the 8b smoke — layers are a Python
        # loop, so each variant compiles the block once per layer
        max_tick_steps=16 if kind == "8b" else 64,
        pipeline_depth=2, ignore_eos=True,
    )
    n_params = count_params(engine.params)
    log(f"  {n_params / 1e9:.2f}B params on device in {time.perf_counter() - t0:.1f}s")

    prompt = ("Benchmark prompt: explain how a systolic array performs matrix "
              "multiplication and why bfloat16 doubles its throughput. " * 3)
    service = PagedGenerationService(engine)
    log("phase C: warmup (compilation, full-concurrency burst) ...")
    t0 = time.perf_counter()
    warm = {}

    def warm_worker(i):
        warm[i] = service.generate(prompt, max_new_tokens=engine.max_tick_steps)

    threads = [threading.Thread(target=warm_worker, args=(i,)) for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    log(f"  warmup done in {time.perf_counter() - t0:.1f}s")

    results = {}

    def worker(i):
        results[i] = service.generate(
            prompt + f" variant {i}", max_new_tokens=new_tokens, temperature=0.0
        )

    stats_before = service.stats()
    sub_steps_before = engine.total_sub_steps
    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stats = service.stats()
    service.close()

    total_tokens = sum(len(r.tokens) for r in results.values())
    tok_s = total_tokens / wall
    # each executed device sub-step streams the weights once (the fused scan
    # runs its full static length regardless of per-row halting)
    steps_s = max(engine.total_sub_steps - sub_steps_before, 1) / wall
    weight_bytes = n_params * 2
    out = {
        "model": kind,
        "params_b": round(n_params / 1e9, 2),
        "tokens": total_tokens,
        "wall_s": round(wall, 2),
        "tokens_per_s": round(tok_s, 1),
        "mfu_pct": round(tok_s * 2 * n_params / peaks["bf16_flops"] * 100, 3),
        # decode is bandwidth-bound: each fused step streams the weights once
        "hbm_util_pct": round(steps_s * weight_bytes / (peaks["hbm_gbs"] * 1e9) * 100, 1),
        "concurrency": concurrency,
        "max_active_slots": stats["max_active_slots"],
        "kv_quant": kv_quant,
        "pool_hbm_bytes": engine.pool.hbm_bytes,
    }
    log(f"phase C: {out['tokens_per_s']} tok/s on {out['params_b']}B params "
        f"(MFU {out['mfu_pct']}%, HBM {out['hbm_util_pct']}%) over {wall:.1f}s")
    return out


def phase_e_speculative(kind: str, new_tokens: int):
    """Plain vs speculative greedy decode on the serve-scale target with a
    4-layer draft (same vocab). Opt-in (BENCH_SPECULATIVE=1): adds ~2 model
    inits + 2 bulk generates of chip time. Exactness is asserted, so the
    speedup column can be trusted as same-output."""
    import jax

    from sentio_tpu.config import GeneratorConfig
    from sentio_tpu.models.llama import LlamaConfig, init_llama
    from sentio_tpu.runtime.engine import GeneratorEngine
    from sentio_tpu.runtime.speculative import SpeculativeDecoder

    cfg = serve_scale_config(kind)
    if type(cfg) is not LlamaConfig:
        log("phase E: speculative bench supports dense targets only; skipping")
        return None
    log(f"phase E: speculative decode, {kind} target + 4-layer draft ...")
    init_bf16 = jax.jit(
        lambda key, c=cfg: jax.tree_util.tree_map(
            lambda x: x.astype(c.jdtype), init_llama(key, c)
        )
    )
    engine = GeneratorEngine(
        config=GeneratorConfig(model_preset="bench", max_new_tokens=new_tokens),
        model_config=cfg, params=init_bf16(jax.random.PRNGKey(0)),
    )
    draft_cfg = LlamaConfig(
        vocab_size=cfg.vocab_size, dim=cfg.dim // 2, n_layers=4,
        n_heads=cfg.n_heads // 2, n_kv_heads=max(cfg.n_kv_heads // 2, 1),
        mlp_dim=cfg.mlp_dim // 2, max_len=cfg.max_len,
        rope_theta=cfg.rope_theta,
    )
    draft_params = jax.jit(
        lambda key: jax.tree_util.tree_map(
            lambda x: x.astype(draft_cfg.jdtype), init_llama(key, draft_cfg)
        )
    )(jax.random.PRNGKey(1))
    spec = SpeculativeDecoder(engine, draft_params, draft_cfg, k=4)

    prompts = ["Explain how paged attention amortizes page table walks."] * 4
    # warmup both paths at the TIMED step count — `steps` is a jit static
    # arg, so a shorter warmup would push the full-length compile into the
    # timed region and the "speedup" would compare compile times
    engine.generate(prompts, max_new_tokens=new_tokens, temperature=0.0)
    spec.generate(prompts, max_new_tokens=new_tokens)
    spec.stats = {"rounds": 0, "tokens": 0}  # acceptance stats: timed run only

    t0 = time.perf_counter()
    plain = engine.generate(prompts, max_new_tokens=new_tokens, temperature=0.0)
    plain_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fast = spec.generate(prompts, max_new_tokens=new_tokens)
    spec_s = time.perf_counter() - t0
    # greedy-exactness holds up to argmax ties under float reassociation
    # (T=1 decode vs T=k+1 verify reduce in different orders); report any
    # divergence rather than aborting the whole bench after the expensive
    # phases already ran
    mismatched = sum(
        f.tokens != p.tokens for f, p in zip(fast, plain)
    )

    out = {
        "plain_tok_s": round(sum(len(r.tokens) for r in plain) / plain_s, 1),
        "spec_tok_s": round(sum(len(r.tokens) for r in fast) / spec_s, 1),
        "speedup": round(plain_s / max(spec_s, 1e-9), 2),
        "tokens_per_verify": round(spec.tokens_per_round, 2),
        "mismatched_rows": mismatched,
    }
    log(f"phase E: {out}")
    return out


def phase_f_longctx(new_tokens: int = 32):
    """8K-window serving measurement — the reference's hardest limit made a
    number. The reference truncates every prompt to ~2000 tokens
    (/root/reference/src/core/graph/nodes.py:296-338, factory.py:90 there);
    here a ~6K-token prompt prefills through the paged engine untruncated
    and decodes at full context. Reports prefill TTFT and e2e p50."""
    from sentio_tpu.models.llama import LlamaConfig
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine

    cfg = LlamaConfig(
        vocab_size=512, dim=512, n_layers=12, n_heads=8, n_kv_heads=4,
        mlp_dim=1536, max_len=8192, rope_theta=500_000.0,
    )
    pages = 8192 // 32
    eng = ContinuousBatchingEngine(
        model_config=cfg, max_slots=2, page_size=32, max_pages_per_seq=pages,
        num_pages=1 + 2 * pages, steps_per_tick=16, max_tick_steps=32,
        pipeline_depth=2, ignore_eos=True,
    )
    words = ("pallas mesh ring paged tick fuse shard scan hbm mxu "
             "systolic bfloat collective permute lane sublane ")
    prompt = (words * 90)[:6100]  # ~6.1K tokens under the byte tokenizer
    log("phase F: long-context warmup (6K-token prefill compile) ...")
    t0 = time.perf_counter()
    eng.run_all([prompt], max_new_tokens=2)
    log(f"  warmup done in {time.perf_counter() - t0:.1f}s")
    # drop the warmup's compile-inflated TTFT sample so the reported p50
    # covers only the measured runs
    eng.ttft_samples.clear()
    times = []
    res = None
    for _ in range(3):
        t0 = time.perf_counter()
        [res] = eng.run_all([prompt], max_new_tokens=new_tokens)
        times.append((time.perf_counter() - t0) * 1e3)
    stats = eng.stats()
    times.sort()
    p50 = times[len(times) // 2]
    ttft = stats.get("ttft_p50_ms") or 0.0
    out = {
        "prompt_tokens": res.prompt_tokens,
        "window": cfg.max_len,
        "p50_ms": round(p50, 1),            # prefill + new_tokens, e2e
        "ttft_p50_ms": round(ttft, 1),      # submit → first token visible
        # decode-only rate once the 6K-token prefill is paid; suppressed
        # when cross-run variance puts the TTFT median past the e2e median
        # (they come from different percentile pools)
        "decode_tok_s": round((new_tokens - 1) / ((p50 - ttft) / 1e3), 1)
        if ttft and p50 > ttft else None,
    }
    log(f"phase F longctx: {out}")
    return out


def phase_load(llm_cfg, new_tokens):
    """Open-loop load harness (BENCH_LOAD=1): a Poisson arrival stream of
    concurrent generate ("/chat"-shaped) + streaming ("SSE"-shaped) requests
    against the multi-replica serving tier, swept over an offered-QPS ladder
    and over replica counts. Open-loop means arrivals do NOT wait for
    completions — in-flight requests pile past any fixed client count, which
    is the regime the n=32/c=8 closed-loop phases can never reach. Reports
    per-level SLO attainment (p50/p95/p99 e2e, stream TTFT/TPOT), shed and
    expired rates, the highest offered QPS sustained at a shed-rate SLO,
    per-replica ``prefix_hit_token_ratio`` (requests carry session heads, so
    radix-affinity routing is exercised and measured), and a two-turn
    session affinity probe whose second request must report
    ``prefix_hit_tokens > 0`` on the routed replica.

    ``BENCH_LOAD_MODES`` sweeps the replica ISOLATION tier: "thread" (all
    N pumps in this process — the GIL-bound baseline) and/or "process"
    (each replica a spawned worker process behind the RPC shim,
    runtime/worker.py). With both, the artifact reports the GIL probe PER
    MODE side by side: per-replica host fractions and the sustained-QPS
    scaling ratio — the direct measurement of what escaping the GIL buys
    (ROADMAP item 1).

    Env knobs: BENCH_LOAD_REPLICAS ("1,2"), BENCH_LOAD_QPS ladder
    ("2,4,8,16,32"), BENCH_LOAD_SECONDS per level (8), BENCH_LOAD_SLOTS
    per-replica decode slots (8), BENCH_LOAD_SHED_SLO (0.05),
    BENCH_LOAD_SEED (1234), BENCH_LOAD_MODES ("thread" |
    "thread,process")."""
    import random
    import threading

    from sentio_tpu.infra.exceptions import (
        DeadlineExceededError,
        ServiceOverloaded,
    )
    from sentio_tpu.infra.flight import get_flight_recorder
    from sentio_tpu.infra.metrics import MetricsCollector, set_metrics
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.replica import ReplicaSet
    from sentio_tpu.runtime.service import PagedGenerationService

    replica_counts = sorted({
        int(x) for x in os.environ.get("BENCH_LOAD_REPLICAS", "1,2").split(",")
        if x.strip()
    })
    qps_ladder = [float(x)
                  for x in os.environ.get("BENCH_LOAD_QPS",
                                          "2,4,8,16,32").split(",")
                  if x.strip()]
    level_s = float(os.environ.get("BENCH_LOAD_SECONDS", "8"))
    shed_slo = float(os.environ.get("BENCH_LOAD_SHED_SLO", "0.05"))
    max_slots = int(os.environ.get("BENCH_LOAD_SLOTS", "8"))
    seed = int(os.environ.get("BENCH_LOAD_SEED", "1234"))
    replica_modes = [m.strip().lower()
                     for m in os.environ.get("BENCH_LOAD_MODES",
                                             "thread").split(",")
                     if m.strip()]
    gen_tokens = min(new_tokens, 16)
    stream_frac = 0.3

    # engines are reused across replica counts (compile once); reset()
    # clears pool/radix so every run starts cold
    engines: list = []

    def get_engines(n: int) -> list:
        while len(engines) < n:
            engines.append(ContinuousBatchingEngine(
                model_config=llm_cfg,
                params=engines[0].params if engines else None,
                tokenizer=engines[0].tokenizer if engines else None,
                max_slots=max_slots, page_size=16, max_pages_per_seq=8,
                steps_per_tick=8, max_tick_steps=8, pipeline_depth=2,
                ignore_eos=True,
            ))
        for eng in engines[:n]:
            eng.reset()
        return engines[:n]

    def build_replicas(mode: str, n: int) -> list:
        """N replicas at the requested isolation tier. Thread mode reuses
        the shared-weights in-process engines (compile once across counts);
        process mode spawns fresh worker processes — compiles are per
        worker by construction, which is part of what the mode costs."""
        if mode == "process":
            import dataclasses as _dc

            from sentio_tpu.models.tokenizer import ByteTokenizer
            from sentio_tpu.runtime.worker import ProcessReplica, WorkerSpec

            spec = WorkerSpec(factory_kwargs=dict(
                model_config=_dc.asdict(llm_cfg),
                engine_kwargs=dict(
                    max_slots=max_slots, page_size=16, max_pages_per_seq=8,
                    steps_per_tick=8, max_tick_steps=8, pipeline_depth=2,
                    ignore_eos=True,
                ),
            ))
            tok = ByteTokenizer(llm_cfg.vocab_size)
            return [ProcessReplica(spec, tok, replica_id=i,
                                   build_timeout_s=600.0)
                    for i in range(n)]
        return [PagedGenerationService(eng) for eng in get_engines(n)]

    # 8 distinct session heads: follow-ups within one session share a
    # prefix, so affinity routing has something real to route on
    sessions = [
        f"session {s:02d} shared conversational context head kept identical "
        f"across this session's turns for prefix reuse measurement"
        for s in range(8)
    ]

    from sentio_tpu.infra.phases import duty_fractions

    def _duty_snapshot(rs) -> list[tuple[dict, float]]:
        """(phase_seconds, duty_elapsed_s) per replica, for level diffs."""
        return [
            (dict(s.get("phase_seconds") or {}), s.get("duty_elapsed_s", 0.0))
            for s in rs.stats()["replicas"]
        ]

    def _duty_delta(before, after) -> list[dict]:
        """Per-replica host/device/idle fractions over the window between
        two snapshots — the per-level time-attribution evidence."""
        out = []
        for (b_phase, b_t), (a_phase, a_t) in zip(before, after):
            deltas = {k: a_phase.get(k, 0.0) - b_phase.get(k, 0.0)
                      for k in a_phase}
            out.append(duty_fractions(deltas, a_t - b_t))
        return out

    def run_level(rs, qps: float, rng: random.Random) -> dict:
        stats = {"arrivals": 0, "ok": 0, "shed": 0, "expired": 0, "error": 0}
        e2e: list[float] = []
        ttft: list[float] = []
        tpot: list[float] = []
        lock = threading.Lock()
        duty_before = _duty_snapshot(rs)

        def gen_worker(prompt: str) -> None:
            t0 = time.perf_counter()
            try:
                r = rs.generate(prompt, max_new_tokens=gen_tokens,
                                temperature=0.0, timeout_s=180)
                dt_ms = (time.perf_counter() - t0) * 1e3
                with lock:
                    if r.finish_reason == "error":
                        stats["error"] += 1
                    else:
                        stats["ok"] += 1
                        e2e.append(dt_ms)
            except ServiceOverloaded:
                with lock:
                    stats["shed"] += 1
            except DeadlineExceededError:
                with lock:
                    stats["expired"] += 1
            except Exception:  # noqa: BLE001 — harness: count, don't die
                with lock:
                    stats["error"] += 1

        def stream_worker(prompt: str) -> None:
            t0 = time.perf_counter()
            t_first = first_chars = chars = 0.0
            try:
                for piece in rs.generate_stream(
                    prompt, max_new_tokens=gen_tokens, temperature=0.0,
                    timeout_s=180,
                ):
                    if not t_first:
                        t_first = time.perf_counter() - t0
                        first_chars = len(piece)
                    chars += len(piece)
                dt = time.perf_counter() - t0
                with lock:
                    stats["ok"] += 1
                    e2e.append(dt * 1e3)
                    if t_first:
                        ttft.append(t_first * 1e3)
                        tail = chars - first_chars
                        if tail > 0 and dt > t_first:
                            # byte tokenizer: chars == tokens exactly; for
                            # BPE this is an upper bound on token count
                            tpot.append((dt - t_first) / tail * 1e3)
            except ServiceOverloaded:
                with lock:
                    stats["shed"] += 1
            except DeadlineExceededError:
                with lock:
                    stats["expired"] += 1
            except Exception:  # noqa: BLE001
                with lock:
                    stats["error"] += 1

        threads: list[threading.Thread] = []
        t_start = time.perf_counter()
        stop_t = t_start + level_s
        seq = 0
        while time.perf_counter() < stop_t:
            session = rng.choice(sessions)
            prompt = f"{session} turn {seq}"
            worker = stream_worker if rng.random() < stream_frac else gen_worker
            t = threading.Thread(target=worker, args=(prompt,), daemon=True)
            t.start()
            threads.append(t)
            stats["arrivals"] += 1
            seq += 1
            time.sleep(rng.expovariate(qps))
        for t in threads:
            t.join(timeout=240)
        wall = time.perf_counter() - t_start
        hung = sum(t.is_alive() for t in threads)
        out = {
            "offered_qps": qps,
            "arrivals": stats["arrivals"],
            "completed": stats["ok"],
            "achieved_qps": round(stats["ok"] / max(wall, 1e-9), 2),
            "shed": stats["shed"],
            "expired": stats["expired"],
            "errors": stats["error"] + hung,
            "shed_rate": round(stats["shed"] / max(stats["arrivals"], 1), 4),
            "wall_s": round(wall, 2),
            # per-replica host/device/idle over THIS level's window: how
            # much of each pump's wall time was GIL-holding host work vs
            # blocked-on-device vs idle (infra/phases.py)
            "duty_cycle_per_replica": _duty_delta(
                duty_before, _duty_snapshot(rs)),
        }
        for label, vals in (("e2e_ms", e2e), ("ttft_ms", ttft),
                            ("tpot_ms", tpot)):
            if vals:
                out[label] = {
                    "p50": round(_percentile(vals, 0.50), 2),
                    "p95": round(_percentile(vals, 0.95), 2),
                    "p99": round(_percentile(vals, 0.99), 2),
                    "n": len(vals),
                }
        return out

    def run_mode(mode: str) -> dict:
        out: dict = {"by_replicas": {}}
        sustained: dict[int, float] = {}
        duty_by_count: dict[int, list[dict]] = {}
        for n in replica_counts:
            log(f"phase LOAD[{mode}]: building {n}-replica set ...")
            svcs = build_replicas(mode, n)
            rs = ReplicaSet(svcs)
            log(f"phase LOAD[{mode}]: warmup ({n} replicas) ...")
            t0 = time.perf_counter()
            warm = rs.warmup(max_new_tokens=gen_tokens)
            log(f"  warmup: {warm['prompts']} prompts, "
                f"{warm['xla_compiles']} compiles in "
                f"{time.perf_counter() - t0:.1f}s")
            get_flight_recorder().clear()
            set_metrics(MetricsCollector())  # per-count isolation
            for svc in svcs:
                # ladder duty windows must exclude warmup's
                # compile-dominated ticks, which would swamp the host
                # fraction (process mode: an RPC re-bases the worker's)
                svc.reset_duty_cycle()
            curve = []
            sustained_n = 0.0
            for qps in qps_ladder:
                level = run_level(rs, qps, random.Random(seed))
                curve.append(level)
                log(f"phase LOAD[{mode}]: replicas={n} offered={qps} "
                    f"achieved={level['achieved_qps']} "
                    f"shed_rate={level['shed_rate']} "
                    f"e2e_p50={level.get('e2e_ms', {}).get('p50')}ms")
                if level["shed_rate"] <= shed_slo and level["errors"] == 0:
                    sustained_n = max(sustained_n, level["achieved_qps"])
            # two-turn session probe: affinity measured END TO END — the
            # second turn must land on the replica holding turn one's KV
            # and actually reuse it
            probe_head = ("affinity probe session head long enough to span "
                          "multiple sixteen token cache pages comfortably")
            rs.generate(probe_head + " turn one", max_new_tokens=4,
                        temperature=0.0, timeout_s=180)
            hits_before = [s.get("prefix_hit_tokens", 0)
                           for s in rs.stats()["replicas"]]
            second = rs.generate(probe_head + " turn two", max_new_tokens=4,
                                 temperature=0.0, timeout_s=180)
            set_stats = rs.stats()
            # the replica whose hit counter MOVED between the probe's turns
            # is the one that actually served turn two (cumulative argmax
            # would attribute the probe to whichever replica served the
            # most load-phase session follow-ups)
            probe_deltas = [
                s.get("prefix_hit_tokens", 0) - hits_before[i]
                for i, s in enumerate(set_stats["replicas"])
            ]
            # whole-ladder duty per replica (warmup excluded via the
            # reset): in thread mode the host fraction here, times N, is
            # the single-process GIL load; in process mode each fraction
            # is measured inside its own worker process
            ladder_duty = [svc.duty_cycle() for svc in svcs]
            duty_by_count[n] = ladder_duty
            out["by_replicas"][str(n)] = {
                "levels": curve,
                "sustained_qps_at_slo": sustained_n,
                "routing": set_stats["routing"],
                "duty_cycle_per_replica": ladder_duty,
                "per_replica_prefix_hit_token_ratio": [
                    s.get("prefix_hit_token_ratio", 0.0)
                    for s in set_stats["replicas"]
                ],
                "affinity_probe": {
                    "second_turn_prefix_hit_tokens":
                        second.prefix_hit_tokens,
                    "routed_replica": max(range(n),
                                          key=lambda i: probe_deltas[i]),
                },
            }
            sustained[n] = sustained_n
            rs.close()
        if len(sustained) > 1:
            lo, hi = min(sustained), max(sustained)
            if sustained[lo] > 0:
                out["throughput_ratio"] = {
                    "replicas": [lo, hi],
                    "sustained_qps": [sustained[lo], sustained[hi]],
                    "ratio": round(sustained[hi] / sustained[lo], 3),
                }
        if duty_by_count:
            # THE GIL probe (ROADMAP item 1): per-replica host fraction at
            # each replica count, next to the measured scaling ratio. In
            # thread mode all N pumps share one Python process — summed
            # host fraction approaching 1 is the quantified ceiling; in
            # process mode each replica owns a GIL, so the honest signal
            # is the PER-REPLICA fraction staying flat (and the scaling
            # ratio climbing) as replicas are added.
            out["gil_probe"] = {
                "replica_mode": mode,
                "host_fraction_by_replicas": {
                    str(n): [round(d["host"], 4) for d in duties]
                    for n, duties in duty_by_count.items()
                },
                "host_fraction_sum_by_replicas": {
                    str(n): round(sum(d["host"] for d in duties), 4)
                    for n, duties in duty_by_count.items()
                },
                **({"scaling_ratio": out["throughput_ratio"]["ratio"]}
                   if "throughput_ratio" in out else {}),
                "note": ("thread: summed host fraction ~1.0 means the "
                         "pumps saturate one GIL; process: fractions are "
                         "per-worker-process, one GIL each"),
            }
        log(f"phase LOAD[{mode}]: sustained {sustained}")
        return out

    result: dict = {
        "knobs": {
            "replica_counts": replica_counts, "qps_ladder": qps_ladder,
            "level_s": level_s, "slots_per_replica": max_slots,
            "gen_tokens": gen_tokens, "stream_frac": stream_frac,
            "shed_slo": shed_slo, "seed": seed,
            "replica_modes": replica_modes,
        },
    }
    by_mode = {mode: run_mode(mode) for mode in replica_modes}
    # legacy top-level shape: the first (usually thread) mode's results
    primary = by_mode.get("thread") or next(iter(by_mode.values()))
    result.update(primary)
    if len(by_mode) > 1:
        result["by_mode"] = by_mode
        # the mode comparison the artifact leads with: same ladder, same
        # replica counts, thread vs process — scaling ratio and host
        # fractions side by side
        result["gil_probe_per_mode"] = {
            mode: out.get("gil_probe") for mode, out in by_mode.items()
        }
    set_metrics(MetricsCollector())  # leave a clean collector behind
    return result


def phase_chaos(llm_cfg, new_tokens, replica_mode=None, chaos_mode=None):
    """Replica chaos drill over the open-loop harness (BENCH_CHAOS=1):
    a 2-replica set serves a steady Poisson arrival stream; mid-run one
    replica suffers the scenario picked by ``BENCH_CHAOS_MODE``:

    * ``kill`` (default) — the next decode tick raises AND its
      ``engine.reset()`` is forced to fail: the replica latches broken and
      the supervisor rebuilds it in place from the shared weights;
    * ``stall`` — the next decode tick WEDGES (stall fault: blocks,
      raising nothing) exactly like a hung device dispatch; nothing
      latches, so recovery rests entirely on the pump-heartbeat watchdog:
      quarantine on heartbeat age, inbox handoff to the survivor, engine
      abandonment, in-place rebuild;
    * ``midstream`` — half the traffic is SSE-shaped streams and the
      replica dies while streams are MID-DELIVERY (thread mode: tick
      fault + reset denied; process mode: a real ``SIGKILL`` armed at the
      ``worker.stream_chunk`` point, between delivered chunks). Delivered
      -token streams must RESUME by replay-prefill on the survivor; the
      artifact records ``resumed_streams``, ``replayed_tokens_total``,
      ``splice_exact`` (every resumed stream byte-identical to its
      no-fault greedy reference) and ``non_resumable_errors`` (target 0
      within budget).
    * ``partition`` (socket replicas only) — a HALF-OPEN network
      partition instead of a death: the router's reads from the victim
      stall (no EOF, no error, worker alive and decoding) while writes
      still land, mid-delivery like the midstream drill. Detection rests
      entirely on status-frame staleness (transport-liveness contract);
      recovery is re-registration at a higher incarnation epoch, and
      every pre-partition frame is dropped by the epoch fence. The
      artifact's midstream fields apply, plus ``stale_frames_dropped``,
      ``heal_vs_respawn`` (did the live worker keep its process?), and
      the victim's post-incident ``incarnation``.

    The artifact answers the operator questions: **availability**
    (completed / arrivals — the error-budget fraction is its complement),
    **p95 during the incident window** (requests arriving between the kill
    and the set reporting all-HEALTHY again), **time-to-recover** (kill →
    rebuilt replica back in rotation), **detection latency** (kill → first
    replica out of HEALTHY — for stalls this is the watchdog's whole
    value), and **handed_off_tickets** (inbox tickets moved to survivors
    at quarantine instead of riding caller failover). Untyped errors are
    counted separately and should be zero.

    ``BENCH_CHAOS_REPLICA_MODE=process`` runs the drill against
    PROCESS-mode replicas (runtime/worker.py): ``kill`` becomes a real
    mid-dispatch ``SIGKILL`` of the victim's worker process (armed inside
    the worker via the RPC fault surface — no Python frame unwinds, the
    supervisor must find the corpse from the outside and RESPAWN it), and
    ``stall`` wedges the worker's pump with an in-worker stall fault
    (recovery reaps the whole wedged process instead of abandoning a
    thread).

    Env knobs: BENCH_CHAOS_QPS (8), BENCH_CHAOS_SECONDS (30),
    BENCH_CHAOS_KILL_AT_S (5), BENCH_CHAOS_SLOTS (8),
    BENCH_CHAOS_SEED (1234), BENCH_CHAOS_MODE
    (kill|stall|midstream|elastic — ``elastic`` dispatches to
    :func:`phase_elastic`, membership churn instead of a replica death),
    BENCH_CHAOS_STALL_BUDGET_S (2), BENCH_CHAOS_REPLICA_MODE
    (thread|process, or a comma list — the caller runs this phase once
    per listed mode from one invocation)."""
    import random
    import threading

    from sentio_tpu.infra import faults
    from sentio_tpu.infra.exceptions import (
        DeadlineExceededError,
        SentioError,
        ServiceOverloaded,
    )
    from sentio_tpu.infra.metrics import MetricsCollector, set_metrics
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.replica import ReplicaSet
    from sentio_tpu.runtime.service import PagedGenerationService

    from sentio_tpu.infra.metrics import get_metrics

    qps = float(os.environ.get("BENCH_CHAOS_QPS", "8"))
    run_s = float(os.environ.get("BENCH_CHAOS_SECONDS", "30"))
    kill_at_s = float(os.environ.get("BENCH_CHAOS_KILL_AT_S", "5"))
    max_slots = int(os.environ.get("BENCH_CHAOS_SLOTS", "8"))
    seed = int(os.environ.get("BENCH_CHAOS_SEED", "1234"))
    mode = (chaos_mode
            or os.environ.get("BENCH_CHAOS_MODE", "kill")).strip().lower()
    stall_budget_s = float(os.environ.get("BENCH_CHAOS_STALL_BUDGET_S", "2"))
    if replica_mode is None:
        replica_mode = os.environ.get(
            "BENCH_CHAOS_REPLICA_MODE", "thread").strip().lower()
    if mode == "elastic":
        # membership churn IS the fault here — no replica dies, the fleet
        # grows/flaps/shrinks under load (dedicated harness below)
        return phase_elastic(llm_cfg, new_tokens)
    if mode == "partition" and replica_mode != "socket":
        return {"skipped": "partition chaos needs the socket transport "
                           f"(replica_mode={replica_mode})",
                "mode": mode, "replica_mode": replica_mode}
    # partition traffic IS the midstream shape (all streams, several
    # delivered chunks each) — only the armed fault differs
    streamy = mode in ("midstream", "partition")
    gen_tokens = min(new_tokens, 16)
    rng = random.Random(seed)

    log(f"phase CHAOS: building 2-replica set (mode={mode}, "
        f"replica_mode={replica_mode}) ...")
    # stall mode rests on the watchdog: the per-service stall budget must
    # exceed the slowest legitimate tick (warmup has pre-compiled, so the
    # default 2s is generous) but stay small next to the run window
    svc_kw = ({"tick_stall_budget_s": stall_budget_s}
              if mode == "stall" else {})
    # midstream/partition run smaller ticks so every stream spans SEVERAL
    # delivered chunks — at 8-step ticks an 8-token answer ships in one
    # harvest and the fault can never land "between chunks" of a stream
    tick_steps = 4 if streamy else 8
    engine_kw = dict(max_slots=max_slots, page_size=16, max_pages_per_seq=8,
                     steps_per_tick=tick_steps, max_tick_steps=tick_steps,
                     pipeline_depth=2, ignore_eos=True)
    registry = None
    if replica_mode in ("process", "socket"):
        import dataclasses as _dc

        from sentio_tpu.models.tokenizer import ByteTokenizer
        from sentio_tpu.runtime.worker import ProcessReplica, WorkerSpec

        spec_kw = dict(factory_kwargs=dict(
            model_config=_dc.asdict(llm_cfg),
            engine_kwargs=engine_kw,
            service_kwargs=dict(svc_kw),
        ))
        transport_kw = {}
        if replica_mode == "socket":
            from sentio_tpu.runtime.replica import WorkerRegistry

            registry = WorkerRegistry("bench-chaos", slots=2)
            spec_kw.update(auth_token="bench-chaos", status_interval_s=0.05,
                           reconnect=True, reconnect_backoff_s=0.2,
                           router_silence_timeout_s=0.8)
            transport_kw = dict(transport_mode="socket", registry=registry,
                                partition_timeout_s=1.0, ping_interval_s=0.2,
                                heal_grace_s=15.0)
        spec = WorkerSpec(**spec_kw)
        tok = ByteTokenizer(llm_cfg.vocab_size)
        replicas = [ProcessReplica(spec, tok, replica_id=i,
                                   build_timeout_s=600.0, **transport_kw)
                    for i in range(2)]
    else:
        e0 = ContinuousBatchingEngine(model_config=llm_cfg, **engine_kw)
        e1 = ContinuousBatchingEngine(
            model_config=llm_cfg, params=e0.params, tokenizer=e0.tokenizer,
            **engine_kw,
        )
        replicas = [PagedGenerationService(e0, **svc_kw),
                    PagedGenerationService(e1, **svc_kw)]
    rs = ReplicaSet(
        replicas,
        # fast supervision: the drill measures recovery, not poll cadence
        probe_interval_s=0.05, quarantine_backoff_s=0.25,
        breaker_tick_failures=2, failover_budget=2,
        rebuild_drain_s=1.0,
    )
    log("phase CHAOS: warmup ...")
    rs.warmup(max_new_tokens=gen_tokens)
    # midstream: per-prompt no-fault GREEDY references, computed before
    # the incident — a resumed stream's spliced output must be
    # byte-identical to the run that never saw a fault (splice_exact).
    # Stream answers run LONGER than the generate traffic (several
    # delivered chunks at the shrunken midstream tick) so streams spend
    # most of their life mid-delivery — the window the kill must land in
    stream_tokens = max(gen_tokens, 16) if streamy else gen_tokens
    stream_prompts = [f"midstream chaos session {i:02d} steady turn"
                      for i in range(8)]
    expected_text: dict = {}
    victim_pid = victim_epoch = None
    if replica_mode == "socket":
        victim_pid = replicas[1].pid
        victim_epoch = replicas[1].epoch
    if streamy:
        # references run directly on the designated VICTIM (replica 1 —
        # the one the process-mode SIGKILL arms in): its radix then holds
        # every stream prompt's full prefix, so prefix affinity routes
        # every drill stream onto the replica that will die, and the kill
        # provably lands on a pump with live delivered streams instead of
        # the idle sibling's (seeded replica inits are identical, so the
        # reference text is valid for whichever replica resumes it)
        for p in stream_prompts:
            expected_text[p] = replicas[1].generate(
                p, max_new_tokens=stream_tokens, temperature=0.0,
                timeout_s=180).text
    set_metrics(MetricsCollector())

    lock = threading.Lock()
    stats = {"arrivals": 0, "ok": 0, "shed": 0, "expired": 0,
             "typed_errors": 0, "untyped_errors": 0}
    # midstream bookkeeping: resumed-stream splice checks + streams that
    # delivered tokens and STILL surfaced the typed mid-stream error
    mid = {"streams": 0, "splice_checked": 0, "splice_mismatch": 0,
           "non_resumable_errors": 0}
    # count of streams that have delivered ≥1 chunk and are still
    # mid-delivery RIGHT NOW: the thread-mode kill arms only while this
    # is non-zero, so the tick fault provably lands on a replica set with
    # live delivered streams (process mode needs no gate — its
    # worker.stream_chunk injection point IS between delivered chunks)
    live_delivered = [0]  # guarded-by: lock
    # (arrival time relative to t_start, e2e latency ms) for completions
    completions: list[tuple[float, float]] = []
    t_state = {"kill": None, "detect": None, "recover": None, "done": False}
    # telemetry-plane-under-fire bookkeeping (process/socket): worst
    # telemetry age observed inside the incident window (the observability
    # gap the outage opened) and the worst clock-offset uncertainty bound
    tel = {"gap_max_s": None, "offset_bound_max_s": None}
    stall_release = threading.Event()
    partition_release = threading.Event()

    def worker(prompt: str, t_rel: float) -> None:
        t0 = time.perf_counter()
        try:
            r = rs.generate(prompt, max_new_tokens=gen_tokens,
                            temperature=0.0, timeout_s=180)
            dt_ms = (time.perf_counter() - t0) * 1e3
            with lock:
                if r.finish_reason == "error":
                    stats["typed_errors"] += 1
                else:
                    stats["ok"] += 1
                    completions.append((t_rel, dt_ms))
        except ServiceOverloaded:
            with lock:
                stats["shed"] += 1
        except DeadlineExceededError:
            with lock:
                stats["expired"] += 1
        except SentioError:
            with lock:
                stats["typed_errors"] += 1
        except Exception:  # noqa: BLE001 — the number that must stay zero
            with lock:
                stats["untyped_errors"] += 1

    def stream_worker(prompt: str, t_rel: float) -> None:
        t0 = time.perf_counter()
        so: dict = {}
        pieces: list = []
        try:
            try:
                for piece in rs.generate_stream(
                        prompt, max_new_tokens=stream_tokens,
                        temperature=0.0, timeout_s=180, stats_out=so):
                    pieces.append(piece)
                    if len(pieces) == 1:
                        with lock:
                            live_delivered[0] += 1
                dt_ms = (time.perf_counter() - t0) * 1e3
                with lock:
                    stats["ok"] += 1
                    completions.append((t_rel, dt_ms))
                    if so.get("resumed"):
                        mid["splice_checked"] += 1
                        if "".join(pieces) != expected_text.get(prompt):
                            mid["splice_mismatch"] += 1
            finally:
                # the kill-arming gate reads this: EVERY exit path of a
                # stream that delivered (incl. a resume re-admission shed
                # AFTER chunks were out) must unwind its live increment
                if pieces:
                    with lock:
                        live_delivered[0] -= 1
        except ServiceOverloaded:
            with lock:
                stats["shed"] += 1
        except DeadlineExceededError:
            with lock:
                stats["expired"] += 1
        except SentioError:
            with lock:
                stats["typed_errors"] += 1
                # delivered tokens AND a typed mid-stream error: the
                # resume machinery did not save this stream
                if pieces:
                    mid["non_resumable_errors"] += 1
        except Exception:  # noqa: BLE001 — must stay zero
            with lock:
                stats["untyped_errors"] += 1

    def watcher(t_start: float) -> None:
        # detection clock: kill → first replica out of HEALTHY (for stalls
        # this measures the watchdog, the headline of the scenario);
        # recovery clock: kill → the set reports all-HEALTHY again.
        # Recovery only counts AFTER detection — a stall leaves the set
        # reporting healthy for a full watchdog budget after the wedge,
        # and "recovered before anything was detected" is not recovery
        while t_state["recover"] is None and not t_state["done"]:
            if t_state["kill"] is not None:
                summary = rs.health_summary()
                if t_state["detect"] is None and any(
                        r["state"] != "HEALTHY"
                        for r in summary["replicas"]):
                    t_state["detect"] = time.perf_counter() - t_start
                if t_state["detect"] is not None and \
                        summary["status"] == "healthy":
                    t_state["recover"] = time.perf_counter() - t_start
                    return
            time.sleep(0.02)

    def telemetry_watcher() -> None:
        # the telemetry plane under fire: sample the VICTIM's telemetry
        # age and clock bound through the drill — always re-reading
        # rs._services[1], because heal/respawn replaces the shim object —
        # and keep the worst gap seen inside the incident window
        while not t_state["done"]:
            svc = rs._services[1]
            age_fn = getattr(svc, "telemetry_age", None)
            if callable(age_fn):
                try:
                    age = age_fn()
                except Exception:  # noqa: BLE001 — shim mid-replacement
                    age = None
                if age is not None and t_state["kill"] is not None:
                    if tel["gap_max_s"] is None or age > tel["gap_max_s"]:
                        tel["gap_max_s"] = age
            clock_fn = getattr(svc, "clock_sync", None)
            if callable(clock_fn):
                est = clock_fn()
                if est is not None and (
                        tel["offset_bound_max_s"] is None
                        or est["uncertainty_s"] > tel["offset_bound_max_s"]):
                    tel["offset_bound_max_s"] = est["uncertainty_s"]
            time.sleep(0.05)

    threads: list[threading.Thread] = []
    t_start = time.perf_counter()
    w = threading.Thread(target=watcher, args=(t_start,), daemon=True)
    w.start()
    if replica_mode in ("process", "socket"):
        threading.Thread(target=telemetry_watcher, daemon=True).start()
    killed = False
    seq = 0
    while time.perf_counter() - t_start < run_s:
        t_rel = time.perf_counter() - t_start
        # thread-mode midstream holds its fire until a stream is provably
        # mid-delivery (≥1 chunk out, not finished): a tick fault armed
        # into an idle-stream window would drill plain failover, not
        # resume-by-replay. Process mode needs no gate — the SIGKILL arms
        # at worker.stream_chunk, BETWEEN delivered chunks by definition.
        if streamy and not (mode == "midstream"
                            and replica_mode == "process"):
            with lock:
                midstream_ready = live_delivered[0] > 0
        else:
            midstream_ready = True
        if not killed and t_rel >= kill_at_s and midstream_ready:
            if mode == "partition":
                # half-open partition of the victim: the router's reads
                # from replica 1 wedge (frames buffer unread) while its
                # writes — and the worker itself — stay fully alive
                faults.arm("transport.recv.r1", faults.FaultRule(
                    stall_event=partition_release,
                    stall_s=run_s + 300.0, times=1))
            elif replica_mode in ("process", "socket"):
                # the fault arms INSIDE the victim's worker process via
                # the RPC fault surface: its next decode tick either takes
                # a REAL mid-dispatch SIGKILL (no handler, no unwinding —
                # the supervisor must detect the corpse from the outside
                # and respawn the process) or wedges in-worker
                victim = replicas[1]
                if mode == "stall":
                    victim.inject_fault("paged.step",
                                        stall_s=run_s + 300.0, times=1)
                elif mode == "midstream":
                    # a real SIGKILL BETWEEN delivered stream chunks: the
                    # victim dies exactly while a stream is mid-delivery,
                    # the case only resume-by-replay can save
                    victim.inject_fault("worker.stream_chunk",
                                        kill_process=True, times=1)
                else:
                    victim.inject_fault("paged.step", kill_process=True,
                                        times=1)
            elif mode == "stall":
                # one-shot wedge: the next decode tick anywhere BLOCKS
                # (raising nothing) until released after the run — the
                # watchdog must find it by heartbeat age alone
                faults.arm("paged.step", faults.FaultRule(
                    stall_event=stall_release,
                    stall_s=run_s + 300.0, times=1))
            else:
                # one-shot kill: the next decode tick anywhere fails, and
                # that pump's recovery reset fails too → latched broken
                faults.arm("paged.step", faults.FaultRule(
                    error=RuntimeError("bench chaos: replica kill"),
                    times=1))
                faults.arm("engine.reset", faults.FaultRule(
                    error=RuntimeError("bench chaos: reset denied"),
                    times=1))
            t_state["kill"] = t_rel
            killed = True
            log(f"phase CHAOS: replica {mode} armed at t={t_rel:.1f}s "
                f"({replica_mode})")
        if streamy:
            # the midstream/partition drills' offered traffic is ALL
            # SSE-shaped streams (the generate path is what the
            # kill/stall modes drill): combined with victim-side
            # reference warming above, the one-shot fault lands on a
            # pump with live delivered streams to splice
            sp = stream_prompts[seq % len(stream_prompts)]
            with lock:
                mid["streams"] += 1
            t = threading.Thread(target=stream_worker, args=(sp, t_rel),
                                 daemon=True)
        else:
            prompt = f"chaos session {seq % 8:02d} steady traffic turn {seq}"
            t = threading.Thread(target=worker, args=(prompt, t_rel),
                                 daemon=True)
        t.start()
        threads.append(t)
        with lock:
            stats["arrivals"] += 1
        seq += 1
        time.sleep(rng.expovariate(qps))
    for t in threads:
        t.join(timeout=240)
    hung = sum(t.is_alive() for t in threads)
    # recovery may land after the last arrival; give the supervisor a
    # bounded grace to finish the rebuild before declaring non-recovery —
    # but ONLY if a kill actually happened (kill_at_s past the run window
    # means there is no incident to recover from)
    if killed:
        grace_end = time.perf_counter() + 120
        while t_state["recover"] is None and time.perf_counter() < grace_end:
            time.sleep(0.1)
    t_state["done"] = True  # stop the watcher (it idles if never killed)
    stall_release.set()  # unwedge the abandoned pump so it can exit
    # heal the partition AFTER recovery: the old connection's buffered
    # pre-partition frames drain straight into the stale-epoch fence
    partition_release.set()
    faults.reset()

    t_kill = t_state["kill"]
    t_detect = t_state["detect"]
    t_recover = t_state["recover"]
    incident = [lat for (t_rel, lat) in completions
                if t_kill is not None
                and t_kill <= t_rel <= (t_recover if t_recover is not None
                                        else float("inf"))]
    steady = [lat for (t_rel, lat) in completions
              if t_kill is None or t_rel < t_kill]
    arrivals = max(stats["arrivals"], 1)
    set_stats = rs.stats()
    out = {
        "knobs": {"qps": qps, "run_s": run_s, "kill_at_s": kill_at_s,
                  "slots_per_replica": max_slots, "gen_tokens": gen_tokens,
                  "seed": seed, "mode": mode,
                  "replica_mode": replica_mode,
                  **({"stall_budget_s": stall_budget_s}
                     if mode == "stall" else {}),
                  **({"stream_tokens": stream_tokens}
                     if mode == "midstream" else {})},
        **stats,
        "hung": hung,
        # the headline: fraction of offered requests that completed — its
        # complement is the error budget the incident consumed
        "availability": round(stats["ok"] / arrivals, 4),
        "killed": killed,
        # kill → first replica out of HEALTHY: for the stall scenario this
        # is pure watchdog latency (nothing raised); for kill it is the
        # caller-path breaker's reaction time
        "detection_latency_s": (round(t_detect - t_kill, 2)
                                if t_detect is not None and t_kill is not None
                                else None),
        "time_to_recover_s": (round(t_recover - t_kill, 2)
                              if t_recover is not None and t_kill is not None
                              else None),
        # None (not False) when no kill was armed: there was no incident
        "recovered": (t_recover is not None) if killed else None,
        "detected": (t_detect is not None) if killed else None,
        "health": rs.health_summary(),
        "failovers": set_stats.get("failovers", 0),
        # quarantine inbox handoff: tickets that completed on a survivor
        # WITHOUT consuming their callers' failover budget
        "handed_off_tickets": set_stats.get("handed_off", 0),
        "stall_quarantines": set_stats.get("stall_quarantines", 0),
    }
    if streamy:
        # resumable-stream telemetry: every delivered-token stream the
        # incident touched should RESUME (non_resumable_errors == 0 within
        # budget) and every resumed completion should be byte-identical to
        # its no-fault greedy reference (splice_exact)
        out["streams_offered"] = mid["streams"]
        out["resumed_streams"] = set_stats.get("stream_resumes", 0)
        out["replayed_tokens_total"] = set_stats.get(
            "resume_replayed_tokens", 0)
        out["resume_exhausted"] = set_stats.get("resume_exhausted", 0)
        out["non_resumable_errors"] = mid["non_resumable_errors"]
        out["resumed_completions_checked"] = mid["splice_checked"]
        out["splice_exact"] = (mid["splice_mismatch"] == 0
                               if mid["splice_checked"] else None)
    if mode == "partition" and registry is not None:
        # the epoch fence at work: give the released (previously wedged)
        # old connection a moment to drain its buffered pre-partition
        # frames, then record how many the fence dropped, whether the
        # live worker HEALED (kept its process across re-registration)
        # or had to be respawned, and the victim's final incarnation
        drain_end = time.perf_counter() + 15
        while registry.stale_frames(1) == 0 and \
                time.perf_counter() < drain_end:
            time.sleep(0.1)
        cur = rs._services[1]
        out["stale_frames_dropped"] = registry.stale_frames(1)
        out["heal_vs_respawn"] = (
            ("heal" if cur.pid == victim_pid else "respawn")
            if killed and t_recover is not None else None)
        out["incarnation"] = cur.epoch
        out["incarnation_before"] = victim_epoch
    if replica_mode in ("process", "socket"):
        # the observability plane's own incident report: how long the
        # fleet flew blind (worst telemetry age inside the incident
        # window), how many stale-epoch deltas the merge fence refused
        # (double-count protection at work), and the worst clock-offset
        # uncertainty bound the trace re-basing had to wear
        stale_dropped = sum(
            v for k, v in get_metrics().memory.counters.items()
            if k.startswith("worker_telemetry_dropped")
            and "stale_epoch" in k)
        out["telemetry"] = {
            "gap_max_s": (round(tel["gap_max_s"], 3)
                          if tel["gap_max_s"] is not None else None),
            "stale_deltas_dropped": int(stale_dropped),
            "clock_offset_bound_max_s": (
                round(tel["offset_bound_max_s"], 6)
                if tel["offset_bound_max_s"] is not None else None),
        }
    if steady:
        out["steady_p95_ms"] = round(_percentile(steady, 0.95), 2)
    if incident:
        out["incident_p95_ms"] = round(_percentile(incident, 0.95), 2)
        out["incident_completions"] = len(incident)
    rs.close()
    # let the released (previously wedged) pump unwind before returning:
    # it exits at its next loop top now that its service is closed, and a
    # pump still inside XLA at interpreter exit aborts the process
    unwind_end = time.perf_counter() + 30
    while time.perf_counter() < unwind_end and any(
            t.name == "paged-decode-pump" and t.is_alive()
            for t in threading.enumerate()):
        time.sleep(0.05)
    if registry is not None:
        registry.close()
    if replica_mode in ("process", "socket"):
        # acceptance telemetry: close() must have REAPED every worker
        # (SIGKILLed, wedged, partitioned-then-healed, and respawned
        # alike) — orphan_workers != 0 in the artifact is a failed drill
        import multiprocessing

        reap_end = time.perf_counter() + 30
        while time.perf_counter() < reap_end and \
                multiprocessing.active_children():
            time.sleep(0.05)
        out["orphan_workers"] = len(multiprocessing.active_children())
    set_metrics(MetricsCollector())
    extra = ""
    if streamy:
        extra = (f" resumed={out['resumed_streams']} "
                 f"replayed={out['replayed_tokens_total']} "
                 f"splice_exact={out['splice_exact']} "
                 f"non_resumable={out['non_resumable_errors']}")
    if mode == "partition":
        extra += (f" stale_dropped={out.get('stale_frames_dropped')} "
                  f"outcome={out.get('heal_vs_respawn')} "
                  f"epoch={out.get('incarnation')}")
    log(f"phase CHAOS[{mode}/{replica_mode}]: "
        f"availability={out['availability']} "
        f"detect={out['detection_latency_s']}s "
        f"ttr={out['time_to_recover_s']}s "
        f"incident_p95={out.get('incident_p95_ms')}ms "
        f"handed_off={out['handed_off_tickets']} "
        f"untyped={stats['untyped_errors']}{extra}")
    return out


def phase_elastic(llm_cfg, new_tokens):
    """Elastic-fleet churn drill (``BENCH_CHAOS_MODE=elastic``): a steady
    Poisson mix of generate + SSE-shaped stream traffic rides a fleet
    whose MEMBERSHIP is the fault — a mid-run join storm grows 1→N, a
    flap cycle joins/retires the same slot back to back, and a scale-in
    wave retires every extra replica while streams are mid-delivery
    (graceful drain: delivered-token streams finish or resume, queued
    tickets hand off to survivors). A live duty-cycle autoscaler
    (runtime/autoscaler.py) polls the whole time with aggressive
    thresholds, so the artifact also records the closed loop's own
    decisions racing the scripted churn.

    The artifact answers: **availability** under churn (its complement is
    the error budget membership changes consumed), **retire drain p95**
    (the latency bill of a graceful scale-in), **handed_off_tickets**
    (queued work moved to survivors instead of riding caller failover),
    **autoscale decisions** by direction, and **untyped_errors** — which
    must be ZERO: churn is a planned operation, every caller-visible
    outcome stays typed.

    Env knobs: BENCH_CHAOS_QPS (8), BENCH_CHAOS_SECONDS (30),
    BENCH_CHAOS_SLOTS (8), BENCH_CHAOS_SEED (1234),
    BENCH_ELASTIC_MAX_REPLICAS (3)."""
    import random
    import threading

    from sentio_tpu.infra.exceptions import (
        DeadlineExceededError,
        SentioError,
        ServiceOverloaded,
    )
    from sentio_tpu.infra.metrics import (
        MetricsCollector,
        get_metrics,
        set_metrics,
    )
    from sentio_tpu.runtime.autoscaler import AutoscalePolicy, Autoscaler
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.replica import ReplicaSet
    from sentio_tpu.runtime.service import PagedGenerationService

    qps = float(os.environ.get("BENCH_CHAOS_QPS", "8"))
    run_s = float(os.environ.get("BENCH_CHAOS_SECONDS", "30"))
    max_slots = int(os.environ.get("BENCH_CHAOS_SLOTS", "8"))
    seed = int(os.environ.get("BENCH_CHAOS_SEED", "1234"))
    max_replicas = max(int(os.environ.get(
        "BENCH_ELASTIC_MAX_REPLICAS", "3")), 2)
    gen_tokens = min(new_tokens, 16)
    rng = random.Random(seed)

    log(f"phase ELASTIC: building 1-replica seed fleet "
        f"(max={max_replicas}) ...")
    engine_kw = dict(max_slots=max_slots, page_size=16, max_pages_per_seq=8,
                     steps_per_tick=4, max_tick_steps=4, pipeline_depth=2,
                     ignore_eos=True)
    e0 = ContinuousBatchingEngine(model_config=llm_cfg, **engine_kw)

    def new_service() -> PagedGenerationService:
        eng = ContinuousBatchingEngine(
            model_config=llm_cfg, params=e0.params, tokenizer=e0.tokenizer,
            **engine_kw)
        return PagedGenerationService(eng)

    rs = ReplicaSet(
        [PagedGenerationService(e0)],
        probe_interval_s=0.05, quarantine_backoff_s=0.25,
        failover_budget=2, rebuild_drain_s=5.0,
    )
    log("phase ELASTIC: warmup ...")
    rs.warmup(max_new_tokens=gen_tokens)
    set_metrics(MetricsCollector())

    # the autoscaler runs LIVE through the drill with thresholds low
    # enough that tiny-engine duty under this traffic can trip them — its
    # decisions race the scripted churn below, which is the point
    def launcher() -> None:
        rs.add_replica(new_service())

    scaler = Autoscaler(
        rs,
        AutoscalePolicy(min_replicas=1, max_replicas=max_replicas,
                        window_s=2.0, out_busy=0.3, in_busy=0.1,
                        out_backlog=0.3, out_cooldown_s=2.0,
                        in_cooldown_s=3.0),
        launcher=launcher, poll_interval_s=0.25,
    )
    scaler.start()

    lock = threading.Lock()
    stats = {"arrivals": 0, "ok": 0, "shed": 0, "expired": 0,
             "typed_errors": 0, "untyped_errors": 0}
    churn = {"storm_joins": 0, "flap_cycles": 0, "forced_retires": 0,
             "refused": 0}
    completions: list[float] = []

    def worker(prompt: str) -> None:
        t0 = time.perf_counter()
        try:
            r = rs.generate(prompt, max_new_tokens=gen_tokens,
                            temperature=0.0, timeout_s=180)
            with lock:
                if r.finish_reason == "error":
                    stats["typed_errors"] += 1
                else:
                    stats["ok"] += 1
                    completions.append((time.perf_counter() - t0) * 1e3)
        except ServiceOverloaded:
            with lock:
                stats["shed"] += 1
        except DeadlineExceededError:
            with lock:
                stats["expired"] += 1
        except SentioError:
            with lock:
                stats["typed_errors"] += 1
        except Exception:  # noqa: BLE001 — the number that must stay zero
            with lock:
                stats["untyped_errors"] += 1

    def stream_worker(prompt: str) -> None:
        t0 = time.perf_counter()
        try:
            "".join(rs.generate_stream(prompt, max_new_tokens=gen_tokens,
                                       temperature=0.0, timeout_s=180))
            with lock:
                stats["ok"] += 1
                completions.append((time.perf_counter() - t0) * 1e3)
        except ServiceOverloaded:
            with lock:
                stats["shed"] += 1
        except DeadlineExceededError:
            with lock:
                stats["expired"] += 1
        except SentioError:
            with lock:
                stats["typed_errors"] += 1
        except Exception:  # noqa: BLE001 — must stay zero
            with lock:
                stats["untyped_errors"] += 1

    def _retire(idx: int, deadline_s: float) -> bool:
        # scripted retires race the autoscaler's own scale-ins (and each
        # other): a slot someone else is already retiring reports
        # retired=False, the last-serving guard raises typed — both are
        # refusals, not failures
        try:
            return bool(rs.retire(idx, deadline_s=deadline_s)["retired"])
        except SentioError:
            with lock:
                churn["refused"] += 1
            return False

    def _live_extras() -> list[int]:
        summary = rs.health_summary()
        return [r["replica"] for r in summary["replicas"]
                if r["replica"] != 0
                and r["state"] in ("HEALTHY", "DEGRADED")]

    storm_at = run_s * 0.2
    flap_at = run_s * 0.5
    scale_in_at = run_s * 0.75
    fired = {"storm": False, "flap": False, "scale_in": False}
    threads: list[threading.Thread] = []
    t_start = time.perf_counter()
    seq = 0
    while time.perf_counter() - t_start < run_s:
        t_rel = time.perf_counter() - t_start
        if not fired["storm"] and t_rel >= storm_at:
            # join storm: grow to max back to back under live traffic
            fired["storm"] = True
            while rs.stats()["fleet"]["live_replicas"] < max_replicas:
                rs.add_replica(new_service())
                churn["storm_joins"] += 1
            log(f"phase ELASTIC: join storm done at t={t_rel:.1f}s "
                f"(live={rs.stats()['fleet']['live_replicas']})")
        if not fired["flap"] and t_rel >= flap_at:
            # flap: retire a joiner and immediately re-join its slot
            fired["flap"] = True
            extras = _live_extras()
            if extras and _retire(extras[-1], deadline_s=5.0):
                rs.add_replica(new_service())
                churn["flap_cycles"] += 1
            log(f"phase ELASTIC: flap cycle done at t={t_rel:.1f}s")
        if not fired["scale_in"] and t_rel >= scale_in_at:
            # scale-in wave racing mid-flight streams: graceful drain on
            # every extra replica, survivors absorb handed-off tickets
            fired["scale_in"] = True
            for idx in reversed(_live_extras()):
                if _retire(idx, deadline_s=10.0):
                    churn["forced_retires"] += 1
            log(f"phase ELASTIC: scale-in wave done at t={t_rel:.1f}s "
                f"(live={rs.stats()['fleet']['live_replicas']})")
        prompt = f"elastic churn session {seq % 8:02d} turn {seq}"
        target = stream_worker if seq % 2 else worker
        t = threading.Thread(target=target, args=(prompt,), daemon=True)
        t.start()
        threads.append(t)
        with lock:
            stats["arrivals"] += 1
        seq += 1
        time.sleep(rng.expovariate(qps))
    for t in threads:
        t.join(timeout=240)
    hung = sum(t.is_alive() for t in threads)
    scaler.close()
    set_stats = rs.stats()
    decisions = {
        k: int(v) for k, v in get_metrics().memory.counters.items()
        if k.startswith("autoscale_decisions")
    }
    arrivals = max(stats["arrivals"], 1)
    out = {
        "knobs": {"qps": qps, "run_s": run_s, "slots_per_replica": max_slots,
                  "gen_tokens": gen_tokens, "seed": seed, "mode": "elastic",
                  "max_replicas": max_replicas},
        **stats,
        "hung": hung,
        "availability": round(stats["ok"] / arrivals, 4),
        "churn": churn,
        "fleet": set_stats["fleet"],
        "handed_off_tickets": set_stats.get("handed_off", 0),
        "autoscale": scaler.stats(),
        "autoscale_decisions": decisions,
        "stream_resumes": set_stats.get("stream_resumes", 0),
        "resume_exhausted": set_stats.get("resume_exhausted", 0),
        "pump_leaked": set_stats.get("pump_leaked", 0),
        "health": rs.health_summary(),
    }
    if completions:
        out["e2e_p95_ms"] = round(_percentile(completions, 0.95), 2)
    rs.close()
    # retired engines idle-exit their pumps; a pump still inside XLA at
    # interpreter exit aborts the process
    unwind_end = time.perf_counter() + 30
    while time.perf_counter() < unwind_end and any(
            t.name == "paged-decode-pump" and t.is_alive()
            for t in threading.enumerate()):
        time.sleep(0.05)
    set_metrics(MetricsCollector())
    fleet = out["fleet"]
    log(f"phase ELASTIC: availability={out['availability']} "
        f"joined={fleet['joined']} retired={fleet['retired']} "
        f"drain_p95={fleet.get('retire_drain_p95_s')}s "
        f"handed_off={out['handed_off_tickets']} "
        f"autoscale={out['autoscale']} "
        f"untyped={stats['untyped_errors']}")
    return out


def phase_d_kernels():
    """Kernel-vs-XLA timings on the real chip: flash attention (prefill
    shape) and the paged decode kernel (page-table walk vs gather). Each
    timing wraps the op in jit and measures dispatch→fetch round trips, so
    the delta isolates the kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentio_tpu.kernels.flash_attention import flash_attention
    from sentio_tpu.kernels.paged_attention import paged_attention
    from sentio_tpu.models.layers import attention, causal_mask
    from sentio_tpu.runtime.paged import _paged_attn_xla

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)

    def timeit(fn, *args, n=8):
        np.asarray(fn(*args))  # compile
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        np.asarray(out)
        return (time.perf_counter() - t0) / n * 1000.0

    out = {}
    # prefill-shaped causal attention: B4 T2048 H8 D64 bf16
    b, t, h, d = 4, 2048, 8, 64
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
               for _ in range(3))
    mask = causal_mask(t)
    xla_fn = jax.jit(lambda q, k, v: attention(q, k, v, mask, jnp.bfloat16))
    out["prefill_attn_xla_ms"] = round(timeit(xla_fn, q, k, v), 2)
    if on_tpu:
        flash_fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        out["prefill_attn_flash_ms"] = round(timeit(flash_fn, q, k, v), 2)

    # paged decode attention: 8 rows, layer 1 of a 2-layer 513-page pool,
    # 16-token pages
    bb, hh, hkv, dd, page, nb, pool = 8, 8, 4, 64, 16, 64, 513
    qd = jnp.asarray(rng.standard_normal((bb, hh, dd)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((2, pool, page, hkv, dd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((2, pool, page, hkv, dd)), jnp.bfloat16)
    pt = jnp.asarray(rng.integers(1, pool, (bb, nb)), jnp.int32)
    lens = jnp.asarray(rng.integers(64, nb * page - 1, (bb,)), jnp.int32)
    gather_fn = jax.jit(
        lambda q, k, v, t_, l_: _paged_attn_xla(q[:, None], k, v, 1, t_, l_, hh // hkv)
    )
    out["paged_attn_xla_gather_ms"] = round(timeit(gather_fn, qd, kp, vp, pt, lens), 2)
    if on_tpu:
        out["paged_attn_pallas_ms"] = round(
            timeit(lambda q, k, v, t_, l_: paged_attention(q, k, v, 1, t_, l_),
                   qd, kp, vp, pt, lens), 2,
        )
    log(f"phase D kernels: {out}")
    return out


def main() -> None:
    from sentio_tpu.infra.compile_cache import ensure_compile_cache

    ensure_compile_cache()  # before JAX is imported
    t_start = time.perf_counter()
    require_accelerator()
    fast = os.environ.get("BENCH_FAST") == "1"
    n_queries = int(os.environ.get("BENCH_QUERIES", "24" if not fast else "32"))
    n_corpus = int(os.environ.get("BENCH_CORPUS", "2048" if not fast else "64"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "48" if not fast else "8"))
    concurrency = int(os.environ.get("BENCH_CONCURRENCY", "8"))
    # phase C inits >1B params — pointless in a fast rehearsal
    skip_scale = os.environ.get("BENCH_SKIP_SCALE") == "1" or fast
    serve_scale = os.environ.get("BENCH_SERVE_SCALE", "1b")
    scale_tokens = int(os.environ.get("BENCH_SCALE_TOKENS", "64"))
    # int8 KV pages in BOTH paged engines (phase A serving + phase C scale)
    kv_quant = os.environ.get("BENCH_KV_QUANT") or os.environ.get("KV_QUANT", "none")
    # sweep knob: run phase A at bf16 AND int8 on the same corpus/queries so
    # the footprint-vs-TPOT tradeoff lands in one artifact as measurement
    kv_sweep = os.environ.get("BENCH_KV_QUANT_SWEEP") == "1"

    import jax

    from sentio_tpu.config import Settings
    from sentio_tpu.models.llama import LlamaConfig
    from sentio_tpu.models.transformer import EncoderConfig

    devices = jax.devices()
    log(f"devices: {len(devices)} x {devices[0].platform} ({devices[0].device_kind})")
    rtt = phase_0_rtt()

    if fast:
        enc_cfg = EncoderConfig.tiny()
        llm_cfg = LlamaConfig.tiny()
    else:
        # MXU-friendly mini models: dims multiples of 128, bf16
        enc_cfg = EncoderConfig(
            vocab_size=512, dim=512, n_layers=8, n_heads=8, mlp_dim=2048, max_len=512
        )
        llm_cfg = LlamaConfig(
            vocab_size=512, dim=512, n_layers=12, n_heads=8, n_kv_heads=4,
            mlp_dim=1536, max_len=2048, rope_theta=500_000.0,
        )

    settings = Settings()
    settings.generator.max_new_tokens = new_tokens
    settings.generator.verifier_max_tokens = 64
    # ByteTokenizer ~ 1 token/char vs the selector's 4-chars/token heuristic:
    # keep assembled prompts inside the model window (see eval/runner.py)
    settings.generator.context_token_budget = max(
        (llm_cfg.max_len - new_tokens - 256) // 4, 32
    )

    docs = build_corpus(n_corpus)
    queries = [
        "What does the MXU systolic array do in bfloat16?",
        "How does JAX compile functions with XLA sharding?",
        "Explain BM25 term saturation and length normalization.",
        "How does ring all-reduce bandwidth scale across ICI?",
        "What fuses sparse and dense retrieval before generation?",
    ]

    rag = phase_a_rag(settings, enc_cfg, llm_cfg, docs, queries, n_queries,
                      new_tokens, concurrency, kv_quant=kv_quant)
    rag_int8 = None
    if kv_sweep and kv_quant == "none":
        rag_int8 = phase_a_rag(settings, enc_cfg, llm_cfg, docs, queries,
                               n_queries, new_tokens, concurrency,
                               kv_quant="int8")
    elif kv_sweep:
        log(f"BENCH_KV_QUANT_SWEEP ignored: KV_QUANT={kv_quant!r} already "
            f"pins the repr — unset it so the sweep can run bf16 AND int8")
    # verification-mode sweep (ISSUE 11): phase A once per VERIFY_MODE on
    # the same corpus/queries — sync pays the audit on the critical path,
    # async overlaps it with delivery, gated also skips it outright for
    # confident answers (BENCH_VERIFY_THRESHOLD overrides the gate)
    verify_sweep = None
    if os.environ.get("BENCH_VERIFY_SWEEP") == "1":
        from dataclasses import replace as _dc_replace

        sweep_settings = settings
        threshold_raw = os.environ.get("BENCH_VERIFY_THRESHOLD")
        if threshold_raw:
            sweep_settings = settings.with_overrides(
                generator=_dc_replace(
                    settings.generator,
                    verify_confidence_threshold=float(threshold_raw),
                ))
        # the sweep measures the LATENCY story (audit on vs off the
        # caller's critical path), so it runs lightly loaded by default:
        # under closed-loop saturation every mode is capacity-bound and
        # detached audits simply compete with the next query's decode —
        # throughput stays phase A's job. BENCH_VERIFY_CONCURRENCY raises
        # it for a contended sweep.
        sweep_conc = int(os.environ.get("BENCH_VERIFY_CONCURRENCY", "2"))
        verify_sweep = {}
        for mode in ("sync", "async", "gated"):
            log(f"phase VERIFY_SWEEP: verify_mode={mode} ...")
            r = phase_a_rag(sweep_settings, enc_cfg, llm_cfg, docs, queries,
                            n_queries, new_tokens, sweep_conc,
                            kv_quant=kv_quant, verify_mode=mode)
            verify_sweep[mode] = {
                "p50_ms": r["p50_ms"],
                "p95_ms": r["p95_ms"],
                "qps": r["qps"],
                "answer_p50_ms": r["verify"]["answer_ms"]["p50"],
                "verdict_p50_ms": r["verify"]["verdict_ms"]["p50"],
                "gate_skip_rate": r["verify"]["gate_skip_rate"],
            }
        log(f"phase VERIFY_SWEEP: {verify_sweep}")
    baseline = phase_b_baseline(docs, queries, n_queries, dim=enc_cfg.dim)
    baseline_wan = None if fast else phase_b_baseline(
        docs, queries, n_queries, dim=enc_cfg.dim,
        rtt_ms=float(os.environ.get("BENCH_BASELINE_RTT_MS", "40")),
    )
    scale = None if skip_scale else phase_c_scale(
        serve_scale, scale_tokens, 8, kv_quant=kv_quant
    )
    kernels = None if fast else phase_d_kernels()
    longctx = None if fast else phase_f_longctx()
    speculative = (
        phase_e_speculative(serve_scale, scale_tokens)
        if os.environ.get("BENCH_SPECULATIVE") == "1" and not skip_scale
        else None
    )
    # open-loop multi-replica load harness: LAST, so its collector swaps
    # cannot disturb the phases above
    load = phase_load(llm_cfg, new_tokens) \
        if os.environ.get("BENCH_LOAD") == "1" else None
    # replica-kill chaos drill: availability, incident-window p95, and
    # time-to-recover for a mid-run replica loss. BENCH_CHAOS_REPLICA_MODE
    # accepts a comma list (e.g. "thread,process") — the drill then runs
    # once per replica mode from this one invocation, and the chaos
    # section becomes a per-mode matrix
    chaos = None
    if os.environ.get("BENCH_CHAOS") == "1":
        chaos_modes = [m.strip().lower() for m in os.environ.get(
            "BENCH_CHAOS_REPLICA_MODE", "thread").split(",") if m.strip()]
        scenario = os.environ.get("BENCH_CHAOS_MODE", "kill").strip().lower()
        if len(chaos_modes) <= 1:
            chaos = phase_chaos(
                llm_cfg, new_tokens,
                replica_mode=(chaos_modes[0] if chaos_modes else "thread"))
        else:
            chaos = {
                "replica_mode_matrix": chaos_modes,
                "per_replica_mode": {
                    m: phase_chaos(llm_cfg, new_tokens, replica_mode=m)
                    for m in chaos_modes
                },
            }
        if scenario != "partition" and "socket" in chaos_modes:
            # socket replicas in the matrix: the half-open partition drill
            # rides along (it is the fault class the socket tier exists
            # for) — the artifact gains a dedicated `partition` section
            chaos["partition"] = phase_chaos(
                llm_cfg, new_tokens, replica_mode="socket",
                chaos_mode="partition")

    total_s = time.perf_counter() - t_start
    log(f"bench wall {total_s:.0f}s")

    payload = {
        "metric": "rag_chat_e2e_p50_latency",
        "value": rag["p50_ms"],
        "unit": "ms",
        # measured-vs-measured: the loopback architecture baseline on the
        # same corpus/queries (a LOWER bound for the reference — zero RTT,
        # zero model compute)
        "vs_baseline": round(baseline["p50_ms"] / max(rag["p50_ms"], 1e-9), 3),
        **rtt,
        "rag": rag,
        **({"rag_int8": rag_int8} if rag_int8 else {}),
        **({"kv_quant_sweep": {
            "bf16_pool_hbm_bytes": rag["pool_hbm_bytes"],
            "int8_pool_hbm_bytes": rag_int8["pool_hbm_bytes"],
            "pool_ratio": round(
                rag_int8["pool_hbm_bytes"] / max(rag["pool_hbm_bytes"], 1), 4),
            "p50_ms_bf16": rag["p50_ms"],
            "p50_ms_int8": rag_int8["p50_ms"],
            "tpot_ms_bf16": rag.get("tpot_ms"),
            "tpot_ms_int8": rag_int8.get("tpot_ms"),
        }} if rag_int8 else {}),
        "baseline": baseline,
        **({"baseline_wan": baseline_wan} if baseline_wan else {}),
        **({"serve_scale": scale} if scale else {}),
        **({"kv_quant": kv_quant} if kv_quant != "none" else {}),
        **({"kernels": kernels} if kernels else {}),
        **({"longctx": longctx} if longctx else {}),
        **({"speculative": speculative} if speculative else {}),
        **({"verify_sweep": verify_sweep} if verify_sweep else {}),
        **({"load": load} if load else {}),
        **({"chaos": chaos} if chaos else {}),
        "wall_s": round(total_s, 1),
    }
    # platform stamped top-level AND into every phase section: a section
    # copied out of the artifact in isolation still names its platform
    plat = device_platform()
    payload["device_platform"] = plat
    for section in (rag, rag_int8, baseline, baseline_wan, scale, kernels,
                    longctx, speculative, load, chaos):
        if isinstance(section, dict):
            section["device_platform"] = plat
    # nested per-mode summaries stamped too (PR 12 known gap for
    # verify_sweep; the chaos replica-mode matrix gets the same treatment):
    # any sub-dict copied out of the artifact still names its platform
    if isinstance(verify_sweep, dict):
        for sub in verify_sweep.values():
            if isinstance(sub, dict):
                sub["device_platform"] = plat
    if isinstance(chaos, dict):
        for sub in (chaos.get("per_replica_mode") or {}).values():
            if isinstance(sub, dict):
                sub["device_platform"] = plat
        if isinstance(chaos.get("partition"), dict):
            chaos["partition"]["device_platform"] = plat
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
