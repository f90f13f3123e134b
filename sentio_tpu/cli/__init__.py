"""Command-line interface: ingest / serve / info / trace / convert /
lint / audit / check.

Parity with /root/reference/src/cli/ (Typer app with ``ingest``/``api``/
``ui``/``run``/``studio`` sub-apps, __init__.py:17-23 there) on stdlib
argparse — Typer isn't in the base image, and the UI is served by the API
process itself (GET /), so ``serve`` covers the reference's ``api`` + ``ui``
+ ``run`` trio. ``trace`` is the studio equivalent (the reference launches
LangGraph Studio, cli/studio.py there): it runs one query through the graph
and dumps the full node-by-node execution trace as JSON. ``convert``
imports public HF checkpoints into framework checkpoints (models/convert.py).
``python -m sentio_tpu.cli <cmd>``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

__all__ = ["main"]

# when ``main`` was entered (raw ``perf_counter``): the end of a server's
# ``import`` phase that the process's start began (infra/startup.py)
_t_main = time.perf_counter()


def _cmd_ingest(args: argparse.Namespace) -> int:
    from sentio_tpu.config import get_settings
    from sentio_tpu.ops.ingest import DocumentIngestor

    settings = get_settings()
    ingestor = DocumentIngestor(settings=settings)
    stats = ingestor.ingest_path(args.path, recursive=not args.no_recursive)
    if args.save:
        ingestor.dense_index.save(args.save)
        print(f"index saved to {args.save}", file=sys.stderr)
    print(json.dumps(stats.to_dict()))
    return 0 if not stats.errors else 1


def _cmd_serve(args: argparse.Namespace) -> int:
    import logging

    from sentio_tpu.config import get_settings
    from sentio_tpu.infra import startup
    from sentio_tpu.infra.tracing import install_compile_listeners
    from sentio_tpu.serve.app import run_server

    install_compile_listeners()  # imports JAX: every compile from here on is timed
    # the ``import`` phase: the process's start → the server's own modules
    # and JAX are imported (``main`` was entered at ``main_entered_s``)
    startup.stamp_phase("import", startup.process_start(), time.perf_counter(),
                        main_entered_s=round(_t_main - startup.process_start(), 3))

    # a server's start-up story (weights loaded, kernels selected, address
    # bound) is logged at INFO; without a handler it was never printed
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    settings = get_settings()
    if args.host:
        settings.serve.host = args.host
    if args.port:
        settings.serve.port = args.port
    if args.index:
        settings.retrieval.index_path = args.index
    run_server(settings)
    return 0


def _cmd_trace(args: argparse.Namespace) -> int:
    """Run one query through the full graph and dump the execution trace —
    the offline equivalent of the reference's LangGraph Studio inspection
    (cli/studio.py + langgraph.json there) — joined with the request's
    FLIGHT RECORD: with the paged decode path active, the dump includes the
    engine's tick timeline for this request (batch occupancy, queue depth,
    prefill/decode token split, page-pool levels) plus TTFT/TPOT."""
    import uuid

    from sentio_tpu.config import get_settings
    from sentio_tpu.graph.state import create_initial_state
    from sentio_tpu.infra.flight import get_flight_recorder
    from sentio_tpu.serve.dependencies import DependencyContainer

    settings = get_settings()
    if args.index:
        settings.retrieval.index_path = args.index
    container = DependencyContainer(settings=settings)
    if args.ingest:
        container.ingestor.ingest_path(args.ingest)
    query_id = f"trace-{uuid.uuid4().hex[:8]}"
    state = container.graph.invoke(
        create_initial_state(
            args.query, metadata={"mode": args.mode, "query_id": query_id}
        )
    )
    # async/gated verification: the graph returns before the detached
    # audit lands — join it so the one-shot trace prints the verdict the
    # flight record ends up with (the serving path never waits like this)
    if state["metadata"].get("verify_pending"):
        from sentio_tpu.graph.executor import wait_detached

        wait_detached()
    trace = {
        "query": args.query,
        "request_id": query_id,
        "graph_path": state["metadata"].get("graph_path"),
        "node_timings_ms": state["metadata"].get("node_timings_ms"),
        "num_retrieved": len(state.get("retrieved_documents") or []),
        "num_reranked": len(state.get("reranked_documents") or []),
        "num_selected": len(state.get("selected_documents") or []),
        "answer": state.get("response"),
        # verify verdict (or typed skipped_confident) as the graph saw it;
        # the per-request verify record — mode, confidence, verdict
        # latency, skip reason — rides trace["flight"]["verify"] below
        "evaluation": state.get("evaluation") or None,
        "metadata": {
            k: v for k, v in state["metadata"].items()
            if k not in ("graph_path", "node_timings_ms")
        },
    }
    flight = get_flight_recorder().get(query_id)
    if flight is not None:
        # the graph-state copies above stay authoritative; the flight view
        # adds what only the engine pump saw (ticks, TTFT/TPOT)
        trace["flight"] = {
            k: v for k, v in flight.items()
            if k not in ("node_timings_ms", "graph_path", "request_id")
        }
    if args.chrome:
        # the WHOLE flight timeline (every tick with its phase split, every
        # request span, verify verdicts) as a Chrome/Perfetto trace — open
        # the file in ui.perfetto.dev. --fleet additionally pulls every
        # worker replica's flight buffer and lays the fleet out on ONE
        # clock-aligned timeline (one lane per worker incarnation)
        from sentio_tpu.infra.chrome_trace import flight_to_chrome

        chrome = _fleet_trace(container) if args.fleet else None
        if chrome is None:
            if args.fleet:
                print("--fleet: no worker replicas (thread mode?) — "
                      "falling back to the local timeline", file=sys.stderr)
            chrome = flight_to_chrome()
        with open(args.chrome, "w") as fh:
            json.dump(chrome, fh)
        print(f"chrome trace written to {args.chrome} "
              f"(open in ui.perfetto.dev)", file=sys.stderr)
    if args.documents:
        trace["selected_documents"] = [
            {"id": d.id, "text": d.text[:200], "metadata": d.metadata}
            for d in (state.get("selected_documents") or [])
        ]
    print(json.dumps(trace, indent=2, default=str))
    return 0


def _fleet_trace(container):
    """Fetch every worker replica's flight buffer (ticks + records) over
    the ``fetch_flight`` RPC and lay the fleet out on one clock-aligned
    Chrome trace: router request lanes on top, one synthetic process row
    per worker INCARNATION below, worker timestamps re-based onto the
    router's perf_counter timeline with the ClockSync offset (the lane
    name carries the ± uncertainty bound). Returns None when no worker
    replicas exist (thread mode) — the caller falls back to the local
    single-recorder export.

    DEAD and RETIRED incarnations stay on the timeline: their lanes
    render from the router's cached last telemetry frame, with the
    status suffixed to the lane name — churn reads as history instead
    of a silently missing row."""
    from sentio_tpu.infra.chrome_trace import build_fleet_trace
    from sentio_tpu.infra.flight import get_flight_recorder

    service = container.peek("generation_service")
    members = list(getattr(service, "_services", None) or ())
    healths = list(getattr(service, "_health", None) or ())
    fetchable = [svc for svc in members
                 if callable(getattr(svc, "fetch_flight", None))]
    if not fetchable:
        return None
    recorder = get_flight_recorder()
    router_origin = recorder.origin()
    workers = []
    for idx, svc in enumerate(members):
        if not callable(getattr(svc, "fetch_flight", None)):
            continue
        state = (getattr(healths[idx], "state", "")
                 if idx < len(healths) else "")
        if state in ("RETIRING", "RETIRED"):
            workers.append(svc.cached_flight_lane(router_origin, "retired"))
            continue
        try:
            reply = svc.fetch_flight()
        except Exception as exc:  # noqa: BLE001 — dead worker: cached lane
            print(f"--fleet: replica {getattr(svc, 'replica_id', '?')} "
                  f"unavailable ({type(exc).__name__}) — rendering lane "
                  f"from cached telemetry", file=sys.stderr)
            if callable(getattr(svc, "cached_flight_lane", None)):
                workers.append(
                    svc.cached_flight_lane(router_origin, "dead"))
            continue
        shift, bound = svc.flight_shift_s(router_origin)
        workers.append({
            "replica": reply.get("replica"),
            "epoch": reply.get("epoch") or 0,
            "shift_s": shift,
            "uncertainty_s": bound,
            "ticks": reply.get("ticks") or [],
            "records": reply.get("records") or [],
        })
    return build_fleet_trace(workers, router_ticks=recorder.timeline(),
                             router_records=recorder.records())


def _cmd_convert(args: argparse.Namespace) -> int:
    """Import a local HF checkpoint directory into a framework checkpoint
    (runtime/checkpoint.py format) ready for serve --restore."""
    from sentio_tpu.models import convert as C
    from sentio_tpu.runtime.checkpoint import save_pytree

    if args.family == "llama":
        params, cfg = C.load_llama_dir(args.src, dtype=args.dtype)
    elif args.family == "moe":
        params, cfg = C.load_moe_dir(args.src, dtype=args.dtype)
    elif args.family == "encoder":
        params, cfg = C.load_encoder_dir(args.src, dtype=args.dtype)
    elif args.family == "cross-encoder":
        params, cfg = C.load_encoder_dir(args.src, dtype=args.dtype, cross_encoder=True)
    else:  # pragma: no cover - argparse choices guard this
        raise ValueError(args.family)
    save_pytree(args.dst, params, meta={"family": args.family, "config": cfg.__dict__})
    print(json.dumps({"family": args.family, "dst": args.dst, "config": cfg.__dict__}))
    return 0


def _cmd_eval(args: argparse.Namespace) -> int:
    """Run the BASELINE.json measurement matrix (five configs + the measured
    reference-architecture baseline) and write EVAL.json."""
    from sentio_tpu.eval.runner import run_eval

    payload = run_eval(
        scale=args.scale,
        n_docs=args.docs,
        n_queries=args.queries,
        concurrency=args.concurrency,
        new_tokens=args.new_tokens,
        rtt_ms=args.rtt_ms,
        seed=args.seed,
        skip_baseline=args.skip_baseline,
        configs={c.strip() for c in args.configs.split(",") if c.strip()} or None
        if args.configs else None,
        encoder_checkpoint=args.encoder_checkpoint,
        kv_quant=args.kv_quant,
    )
    text = json.dumps(payload, indent=2)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    print(text)
    return 0


def _cmd_train_encoder(args: argparse.Namespace) -> int:
    """Train the bi-encoder in-tree (eval/train_encoder.py) and save a
    ``load_model``-compatible checkpoint for EMBEDDER_CHECKPOINT /
    ``eval --encoder-checkpoint``."""
    from sentio_tpu.eval.train_encoder import TrainConfig, eval_recall, train_encoder
    from sentio_tpu.models.transformer import EncoderConfig

    enc_cfg = EncoderConfig(
        vocab_size=512, dim=args.dim, n_layers=args.layers,
        n_heads=max(args.dim // 64, 2), mlp_dim=args.dim * 4, max_len=512,
    )
    params, enc_cfg, history = train_encoder(
        enc_cfg=enc_cfg,
        train_cfg=TrainConfig(steps=args.steps, batch=args.batch, lr=args.lr),
        out_path=args.out,
        seed=args.seed,
    )
    payload = {"checkpoint": args.out, "history": history}
    if args.eval_recall:
        payload["recall_at_10"] = round(eval_recall(params, enc_cfg), 3)
    print(json.dumps(payload))
    return 0


def _cmd_lint(args: argparse.Namespace) -> int:
    """Run the static analyzer (analysis/) over the source tree against the
    committed baseline: retrace hazards at jit sites, lock discipline from
    guarded-by annotations, wall-clock and exception hygiene. Exit 1 on any
    finding not in the baseline."""
    from sentio_tpu.analysis.runner import main as lint_main

    forwarded = list(args.paths)
    if args.baseline:
        forwarded += ["--baseline", args.baseline]
    if args.update_baseline:
        forwarded.append("--update-baseline")
    if args.json:
        forwarded.append("--json")
    if args.lock_graph:
        forwarded.append("--lock-graph")
    if args.failures:
        forwarded.append("--failures")
    if args.boundary_graph:
        forwarded.append("--boundary-graph")
    if args.sarif:
        forwarded += ["--sarif", args.sarif]
    return lint_main(forwarded)


def _cmd_audit(args: argparse.Namespace) -> int:
    """AOT-lower every registered jit family on a tiny CPU config and gate
    compile variants / donation aliasing / sharding / static HBM against
    the committed analysis/compile_manifest.json. Exit 1 on regressions."""
    from sentio_tpu.analysis.audit.runner import main as audit_main

    forwarded: list[str] = []
    if args.manifest:
        forwarded += ["--manifest", args.manifest]
    if args.update_manifest:
        forwarded.append("--update-manifest")
    if args.json:
        forwarded.append("--json")
    if args.no_mesh:
        forwarded.append("--no-mesh")
    return audit_main(forwarded)


def _cmd_check(args: argparse.Namespace) -> int:
    """The one-stop static gate: ``sentio lint`` (AST analysis vs baseline)
    then ``sentio audit`` (compile manifest). Exit non-zero when either
    fails; both always run so one invocation reports everything. With
    ``--json`` the two results nest under ONE parseable envelope."""
    if not args.json:
        from sentio_tpu.analysis.audit.runner import main as audit_main
        from sentio_tpu.analysis.runner import main as lint_main

        lint_rc = lint_main([])
        audit_rc = audit_main([])
        return lint_rc or audit_rc

    from sentio_tpu.analysis.audit.runner import _pin_platform, run_audit
    from sentio_tpu.analysis.runner import run_gate

    lint = run_gate()
    _pin_platform()
    audit = run_audit()
    ok = lint.ok and audit.ok
    print(json.dumps({
        "ok": ok,
        "lint": {
            "ok": lint.ok,
            "new": [dict(f.to_json(), line=f.line) for f in lint.new],
            "baselined": [dict(f.to_json(), line=f.line)
                          for f in lint.matched],
            "stale": lint.stale,
        },
        "audit": {
            "ok": audit.ok,
            "families": len(audit.report["families"]),
            "variants": audit.variant_count(),
            "regressions": audit.diff.regressions,
            "stale": audit.diff.stale,
        },
    }, indent=1))
    return 0 if ok else 1


def _cmd_info(args: argparse.Namespace) -> int:
    import jax

    import sentio_tpu
    from sentio_tpu.config import get_settings

    settings = get_settings()
    devices = jax.devices()
    print(json.dumps({
        "version": sentio_tpu.__version__,
        "devices": [{"platform": d.platform, "kind": d.device_kind} for d in devices],
        "retrieval": settings.retrieval.strategy,
        "generator": settings.generator.model_preset,
        "mesh": {
            "dp": settings.mesh.dp_size,
            "tp": settings.mesh.tp_size,
            "sp": settings.mesh.sp_size,
        },
    }, indent=2))
    return 0


def main(argv: list[str] | None = None) -> int:
    global _t_main
    _t_main = time.perf_counter()
    from sentio_tpu.infra.compile_cache import ensure_compile_cache

    ensure_compile_cache()  # before any subcommand imports JAX
    parser = argparse.ArgumentParser(prog="sentio-tpu", description=__doc__)
    sub = parser.add_subparsers(dest="command", required=True)

    p_ingest = sub.add_parser("ingest", help="ingest a file or directory into the index")
    p_ingest.add_argument("path")
    p_ingest.add_argument("--no-recursive", action="store_true")
    p_ingest.add_argument("--save", default="", help="persist the dense index to this path")
    p_ingest.set_defaults(fn=_cmd_ingest)

    p_serve = sub.add_parser("serve", help="run the API server (UI at /)")
    p_serve.add_argument("--host", default="")
    p_serve.add_argument("--port", type=int, default=0)
    p_serve.add_argument("--index", default="", help="load a persisted dense index (from ingest --save)")
    p_serve.set_defaults(fn=_cmd_serve)


    p_trace = sub.add_parser("trace", help="run one query and dump the graph execution trace")
    p_trace.add_argument("query")
    p_trace.add_argument("--ingest", default="", help="ingest this path first")
    p_trace.add_argument("--index", default="", help="load a persisted dense index")
    p_trace.add_argument("--mode", default="balanced",
                         choices=["fast", "balanced", "quality", "creative"])
    p_trace.add_argument("--documents", action="store_true",
                         help="include selected document previews")
    p_trace.add_argument("--chrome", default="", metavar="OUT_JSON",
                         help="also dump the full flight timeline as a "
                              "Chrome/Perfetto trace (ui.perfetto.dev)")
    p_trace.add_argument("--fleet", action="store_true",
                         help="with --chrome: fetch every worker replica's "
                              "flight buffer and emit ONE clock-aligned "
                              "fleet trace (a lane per worker incarnation)")
    p_trace.set_defaults(fn=_cmd_trace)

    p_conv = sub.add_parser("convert", help="convert a local HF checkpoint dir")
    p_conv.add_argument("family", choices=["llama", "moe", "encoder", "cross-encoder"])
    p_conv.add_argument("src", help="HF checkpoint directory (config.json + weights)")
    p_conv.add_argument("dst", help="output framework checkpoint directory")
    p_conv.add_argument("--dtype", default="bfloat16")
    p_conv.set_defaults(fn=_cmd_convert)

    p_eval = sub.add_parser(
        "eval", help="run the BASELINE measurement matrix; write EVAL.json"
    )
    p_eval.add_argument("--scale", default="bench", choices=["tiny", "bench"])
    p_eval.add_argument("--docs", type=int, default=1024)
    p_eval.add_argument("--queries", type=int, default=64)
    p_eval.add_argument("--concurrency", type=int, default=8)
    p_eval.add_argument("--new-tokens", type=int, default=48)
    p_eval.add_argument("--rtt-ms", type=float, default=0.0,
                        help="inject per-hop RTT into the loopback baseline APIs")
    p_eval.add_argument("--seed", type=int, default=0)
    p_eval.add_argument("--skip-baseline", action="store_true")
    p_eval.add_argument("--configs", default="",
                        help="comma list: sparse_api,dense,hybrid_rerank,full_paged,batched")
    p_eval.add_argument("--out", default="", help="also write the JSON here")
    p_eval.add_argument("--kv-quant", default=os.environ.get("KV_QUANT", "none"),
                        choices=["none", "int8"],
                        help="KV page quantization for the paged configs "
                             "(the quality-gate measurement knob)")
    p_eval.add_argument("--encoder-checkpoint", default="",
                        help="trained bi-encoder checkpoint for the dense leg "
                             "(see `train-encoder`)")
    p_eval.set_defaults(fn=_cmd_eval)

    p_tr = sub.add_parser(
        "train-encoder",
        help="contrastively train the bi-encoder on the synthetic bundle "
             "(dense retrieval with zero egress)",
    )
    p_tr.add_argument("out", help="checkpoint output directory")
    p_tr.add_argument("--steps", type=int, default=600)
    p_tr.add_argument("--batch", type=int, default=64)
    p_tr.add_argument("--lr", type=float, default=3e-4)
    p_tr.add_argument("--dim", type=int, default=256)
    p_tr.add_argument("--layers", type=int, default=4)
    p_tr.add_argument("--seed", type=int, default=0)
    p_tr.add_argument("--eval-recall", action="store_true",
                      help="measure recall@10 on the eval bundle (seed 0) "
                           "after training")
    p_tr.set_defaults(fn=_cmd_train_encoder)

    p_lint = sub.add_parser(
        "lint",
        help="static analysis: retrace / lock-discipline / clock / "
             "exception hazards vs the committed baseline",
    )
    p_lint.add_argument("paths", nargs="*",
                        help="files or directories (default: sentio_tpu/)")
    p_lint.add_argument("--baseline", default="",
                        help="baseline JSON (default: analysis/baseline.json)")
    p_lint.add_argument("--update-baseline", action="store_true",
                        help="re-record the baseline from current findings")
    p_lint.add_argument("--json", action="store_true",
                        help="machine-readable output")
    p_lint.add_argument("--lock-graph", action="store_true",
                        dest="lock_graph",
                        help="dump the static lock-order digraph as JSON "
                             "(exit 1 if it has cycles)")
    p_lint.add_argument("--failures", action="store_true",
                        help="report only the failure-surface rules "
                             "(boundary escapes, typed rethrow, swallows, "
                             "codec / frame contracts)")
    p_lint.add_argument("--boundary-graph", action="store_true",
                        dest="boundary_graph",
                        help="dump the failure-surface graph (boundaries "
                             "with reachable escapes, frame channels) as "
                             "JSON")
    p_lint.add_argument("--sarif", metavar="PATH", default="",
                        help="also write the gate result as SARIF 2.1.0")
    p_lint.set_defaults(fn=_cmd_lint)

    p_audit = sub.add_parser(
        "audit",
        help="compile-manifest audit: AOT-lower every jit family and gate "
             "variants/donation/sharding/HBM vs the committed manifest",
    )
    p_audit.add_argument("--manifest", default="",
                         help="manifest JSON (default: "
                              "analysis/compile_manifest.json)")
    p_audit.add_argument("--update-manifest", action="store_true",
                         help="re-record the manifest from the current audit")
    p_audit.add_argument("--json", action="store_true",
                         help="machine-readable output")
    p_audit.add_argument("--no-mesh", action="store_true",
                         help="skip the 2-device sharding section")
    p_audit.set_defaults(fn=_cmd_audit)

    p_check = sub.add_parser(
        "check", help="run `sentio lint` and `sentio audit` as one gate"
    )
    p_check.add_argument("--json", action="store_true",
                         help="machine-readable output")
    p_check.set_defaults(fn=_cmd_check)

    p_info = sub.add_parser("info", help="print version/device/config info")
    p_info.set_defaults(fn=_cmd_info)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
