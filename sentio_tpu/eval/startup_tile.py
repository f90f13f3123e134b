"""What a benchmark cell's set-up is made of, read from inside the program.

``python -m sentio_tpu.eval.startup_tile --workload mistral7b-chat-closed
--seed N [--clear-cache]`` makes the benchmark's own set-up with the
benchmark's own functions (``benchmark/run.py``: device probe, seeded
checkpoints, corpus, the server as a child, ``/upload`` a hundred files a
request, the mix's warm-up bursts, ``/info`` checked) and stops where the
window would begin. It takes the client's clock round each step and asks the
server what it saw: ``/info``'s ``startup`` (the tile of process start →
ready, the compile account, the ingest stages), each upload's own flight
record, the warm-up requests' spans with their ``compile_ms``. Then it sums
the server log's ``Finished XLA compilation`` and ``Persistent compilation
cache hit`` lines by program beside the program's own counters — the two
accounts of one run. ONE chip; its last line is one JSON object, and
``--out`` keeps it with the server's log beside it. The parent never imports
JAX (a chip belongs to one process). ``JAX_PLATFORMS=cpu`` rehearses the
control flow at the cell's rehearsal widths; a CPU second is no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path

T_PROCESS_START = time.perf_counter()
REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

# the server logs every JAX line twice (JAX's own handler and the root's):
# the root's form, which begins with the date, is the one counted
_ROOT = r"(?m)^\d{4}-\d\d-\d\d [\d:,]+ WARNING jax\.\S+: "
COMPILED = re.compile(_ROOT + r"Finished XLA compilation of jit\((\w+)\) in ([0-9.eE+-]+) sec")
CACHE_HIT = re.compile(_ROOT + r"Persistent compilation cache hit for 'jit_(\w+)'")
TRACED = re.compile(_ROOT + r"Finished tracing \+ transforming (\w+) for pjit in ([0-9.eE+-]+) sec")


def log_account(text: str, programs: set[str]) -> dict:
    """The server log's own account of its compiles (``JAX_LOG_COMPILES=1``):
    the backend's seconds and the cache's hits by program — ``other`` what
    ``programs`` does not name, as the program's counters have it."""
    label = lambda name: name if name in programs else "other"  # noqa: E731
    backend: dict[str, float] = {}
    hits: dict[str, int] = {}
    for name, seconds in COMPILED.findall(text):
        backend[label(name)] = backend.get(label(name), 0.0) + float(seconds)
    for name in CACHE_HIT.findall(text):
        hits[label(name)] = hits.get(label(name), 0) + 1
    return {"backend_s": {k: round(v, 3) for k, v in sorted(backend.items())},
            "backend_s_total": round(sum(backend.values()), 3),
            "hits": dict(sorted(hits.items())), "hits_total": sum(hits.values()),
            "finished_tracing_lines": len(TRACED.findall(text))}


def warm_requests(chrome: dict) -> list[dict]:
    """The flight table's requests from its chrome export: per request its
    spans' seconds by name and the compile seconds booked on them."""
    lanes: dict[tuple, dict] = {}
    for event in chrome.get("traceEvents", ()):
        if event.get("ph") != "X" or event.get("tid", 0) == 0:
            continue
        row = lanes.setdefault((event["pid"], event["tid"]), {"spans": {}, "compile_ms": 0.0})
        if event["name"].startswith("request "):
            row.update(id=event["name"][8:], t0_s=event["ts"] / 1e6, seconds=event["dur"] / 1e6)
        else:
            row["spans"][event["name"]] = round(
                row["spans"].get(event["name"], 0.0) + event["dur"] / 1e6, 4)
            row["compile_ms"] += float(event.get("args", {}).get("compile_ms", 0.0))
    return sorted((r for r in lanes.values() if "id" in r), key=lambda r: r["t0_s"])


def run(args) -> dict:
    from benchmark import run as bench
    from benchmark import server, traffic
    from benchmark.families import load_family

    clock = time.perf_counter
    out: dict = {"workload": args.workload, "seed": args.seed, "cleared_cache": args.clear_cache}
    resolved = bench.resolve_cell(args.workload, REPO / "BENCHMARK.json")
    base_env = dict(os.environ)
    base_env.setdefault("JAX_COMPILATION_CACHE_DIR", str(REPO / ".jax_compile_cache"))
    if args.clear_cache:
        shutil.rmtree(base_env["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True)
    rehearsal = base_env.get("JAX_PLATFORMS") == "cpu"
    t = clock()
    probe = server.probe_device(base_env)
    out["device"] = probe
    steps = {"probe": clock() - t}
    config = bench.overlay(resolved["config"], rehearsal)
    mix = bench.overlay(resolved["mix"], rehearsal)
    bench.WORK.mkdir(exist_ok=True)
    t = clock()
    paths = bench.make_checkpoints(config, args.seed)
    steps["checkpoints"] = clock() - t
    env = bench.server_environment(base_env, config, mix, paths)
    family = load_family(config)
    t = clock()
    docs = traffic.make_corpus(mix, args.seed)
    steps["corpus"] = clock() - t
    steps["before_spawn"] = clock() - T_PROCESS_START
    srv = server.Server(env, bench.WORK / "server.log")
    try:
        out["ready_s_from_spawn"] = round(srv.wait_healthy(timeout_s=1100.0), 3)
        steps["spawn_to_health"] = out["ready_s_from_spawn"]
        _status, info = server.http_json(srv.port, "GET", "/info", timeout=60.0)
        out["startup_at_ready"] = info.get("startup")
        uploads = []
        t_upload = clock()
        for k, start in enumerate(range(0, len(docs), 100), 1):
            t = clock()
            server.upload_documents(srv.port, docs[start:start + 100])
            wall = clock() - t
            _status, record = server.http_json(srv.port, "GET", f"/debug/flight/upload-{k}")
            uploads.append({"client_s": round(wall, 3), "server_s": (record.get("latency_ms") or 0) / 1e3,
                            **(record.get("ingest") or {})})
        steps["upload"] = clock() - t_upload
        out["uploads"] = uploads
        t = clock()
        out["warm_requests_sent"] = bench.warm_up(srv, mix, args.seed)
        steps["warm_up"] = clock() - t
        t = clock()
        want = {"generator": family.program_config(config),
                "reranker": config["encoders"]["reranker"],
                "embedder_dim": config["encoders"]["embedder_dim"],
                "kv_quant": env["KV_QUANT"], "platform": probe["platform"],
                "pool_hbm_bytes": family.pool_bytes(config, env),
                "corpus_size": len(docs), "chips": resolved["cell"]["chips"]}
        info, out["info_problems"] = server.check_info(srv.port, want)
        steps["check_info"] = clock() - t
        out["setup_s"] = round(clock() - T_PROCESS_START, 3)
        out["startup"] = info.get("startup")
        _status, chrome = server.http_json(srv.port, "GET", "/debug/flight?format=chrome",
                                           timeout=120.0)
        out["requests"] = [r for r in warm_requests(chrome) if not r["id"].startswith("upload-")]
        out["metrics"] = [(name, labels, value) for name, labels, value in server.scrape(srv.port)
                          if name.startswith(("sentio_tpu_compile_", "sentio_tpu_startup_",
                                              "sentio_tpu_ingest_", "sentio_tpu_bm25_",
                                              "sentio_tpu_xla_compiles"))]
        srv.terminate()
    finally:
        srv.sweep()
    out["steps_s"] = {k: round(v, 3) for k, v in steps.items()}
    programs = set((out["startup"] or {}).get("compile", {}).get("by_program", {})) - {"other"}
    log_text = (bench.WORK / "server.log").read_text(errors="replace")
    out["log"] = log_account(log_text, programs)
    if args.out:
        dest = Path(args.out)
        dest.parent.mkdir(parents=True, exist_ok=True)
        dest.write_text(json.dumps(out, indent=1))
        shutil.copy(bench.WORK / "server.log", dest.with_suffix(".server.log"))
    return out


def summary(out: dict) -> dict:
    """The few numbers a reader wants first; ``--out`` has the rest."""
    start = out.get("startup") or {}
    compile_, ingest = start.get("compile", {}), start.get("ingest", {})
    requests = out.get("requests", [])
    return {
        "workload": out["workload"], "seed": out["seed"], "device": out.get("device"),
        "cleared_cache": out["cleared_cache"], "setup_s": out.get("setup_s"),
        "ready_s_from_spawn": out.get("ready_s_from_spawn"), "ready_s": start.get("ready_s"),
        "steps_s": out.get("steps_s"),
        "phases": {k: v for k, v in (start.get("phases") or {}).items() if v >= 0.01},
        "phases_sum_minus_ready_s": round(sum((start.get("phases") or {}).values())
                                          - (start.get("ready_s") or 0.0), 6),
        "weights": start.get("weights"),
        "compile": {k: v for k, v in compile_.items() if k != "by_program"},
        "compile_by_program": compile_.get("by_program"),
        "log": out.get("log"),
        "ingest": ingest,
        "uploads": [[u["client_s"], round(u["server_s"], 3),
                     {k: round(v / 1e3, 3) for k, v in (u.get("stage_ms") or {}).items()},
                     u.get("index_size")] for u in out.get("uploads", [])],
        "warm_requests": len(requests),
        "warm_compile_s": round(sum(r["compile_ms"] for r in requests) / 1e3, 3),
        "info_problems": out.get("info_problems"),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--clear-cache", action="store_true",
                        help="empty the persistent compile cache first: a cold start")
    parser.add_argument("--out", default="", help="keep the whole reading (JSON) and the server's log")
    args = parser.parse_args()
    print(json.dumps(summary(run(args))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
