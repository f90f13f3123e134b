#!/usr/bin/env python3
"""One run of one benchmark cell against the served system.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration file (``configs/``) and a traffic mix
(``traffic/``); the per-layer metrics it reports are ``layer_metrics/`` files
named in ``BENCHMARK.json``. Nothing about a model, a rate or a metric is
held in this file. One process tree per run: this parent never imports JAX
(one process per chip); it makes the seeded checkpoints, starts ``python -m
sentio_tpu.cli serve`` as a child, uploads the seeded corpus, warms the
programs the mix declares, stamps ``setup_s``, measures for ``--seconds``,
stops the server (exit code 0 required), runs the reference check in a child
that may then take the chip, and prints the result as the LAST line of its
standard output. Earlier lines are JSON notes for a reader.

With no accelerator it exits non-zero and prints no result. With
``JAX_PLATFORMS=cpu`` set by the caller it rehearses the same control flow
at the sizes of the files' ``rehearsal`` blocks, stamps ``cpu`` and reports
``correct: false``.

``--sweep`` replaces the window with the mix's ``sweep`` rates, one server
start, and prints a table (not a result line): how a cell's rate was found.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(REPO))

from benchmark import readers, server, trace, traffic  # noqa: E402
from benchmark.families import load_family  # noqa: E402
from benchmark.server import BenchFailure  # noqa: E402

WORK = HERE / ".work"   # checkpoints, server logs, traces (benchmark/.gitignore)


def note(**fields) -> None:
    print(json.dumps(fields), flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def resolve_cell(name: str, bench_file: Path) -> dict:
    """The cell, its configuration, its mix and its metrics, by name."""
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no cell {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[name]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    in_cell = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return {
        "cell": cell,
        "config": load_json(REPO / config_entry["file"]),
        "config_path": REPO / config_entry["file"],
        "mix": load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in bench["per_layer"] if in_cell(m)],
    }


def overlay(data: dict, rehearsal: bool) -> dict:
    """A file as run: its ``rehearsal`` block laid over it on the CPU."""
    return {**data, **data.get("rehearsal", {})} if rehearsal else data


# ------------------------------------------------------------------- set-up


def make_checkpoints(config: dict, seed: int) -> dict[str, Path]:
    """Seeded checkpoints of the generator and the reranker; one set on disk
    at a time, kept when (configuration, every width, seed) repeat."""
    family = load_family(config)
    from benchmark.families import cross_encoder

    widths = json.dumps([family.program_config(config), config["encoders"]["reranker"]], sort_keys=True)
    tag = f"{config['name']}-{hashlib.sha1(widths.encode()).hexdigest()[:10]}-s{seed}"
    root = WORK / "ckpt"
    done = root / tag / "complete"
    paths = {"llm": root / tag / "llm", "reranker": root / tag / "reranker"}
    if done.exists():
        note(phase="checkpoints", reused=tag)
        return paths
    t0 = time.perf_counter()
    shutil.rmtree(root, ignore_errors=True)   # disk: never two sets at once
    family.write_checkpoint(paths["llm"], config, seed)
    cross_encoder.write_checkpoint(paths["reranker"], config["encoders"]["reranker"], seed + 1)
    done.write_text(tag)
    note(phase="checkpoints", seconds=round(time.perf_counter() - t0, 1),
         bytes=sum(p.stat().st_size for p in root.rglob("arrays.npz")))
    return paths


def server_environment(base_env: dict, config: dict, mix: dict, paths: dict) -> dict:
    env = {
        **base_env,
        "LLM_PROVIDER": "tpu", "LLM_CHECKPOINT": str(paths["llm"]),
        "EMBEDDER_PROVIDER": "tpu", "EMBEDDER_PRESET": config["encoders"]["embedder_preset"],
        "USE_RERANKER": "1", "RERANKER_KIND": "cross_encoder",
        "RERANKER_CHECKPOINT": str(paths["reranker"]),
        # a failed native build is an error, not a quiet numpy run
        "BM25_BACKEND": "native",
        # one load generator on one address: the per-address limits are not
        # what is measured
        "RATE_LIMIT_DEFAULT_PER_MIN": "1000000", "RATE_LIMIT_EMBED_PER_MIN": "1000000",
        # cold compiles of unrolled layers run past the default stall watchdog
        "TICK_STALL_BUDGET_S": "1800",
        # every compilation is logged, so one inside the window is seen
        "JAX_LOG_COMPILES": "1",
        **config["serve_env"], **mix["serve_env"],
    }
    return env


def family_compiles(rows) -> dict[str, int]:
    return {lab["family"]: int(val) for name, lab, val in rows
            if name == "sentio_tpu_xla_compiles_total" and "family" in lab}


def warm_up(srv: server.Server, mix: dict, seed: int) -> int:
    """The mix's bursts, until the program's own compile counters show every
    program the mix declares. Returns the requests sent."""
    send = lambda payload: server.chat_stream(srv.port, payload, mix["verifier"])  # noqa: E731
    sent = 0
    # one round does it in 20 runs of 21 (my chip runs, PR 27): which row
    # bucket an admission takes depends on how a burst falls across ticks
    for round_no in range(5):
        for size in mix["warmup_bursts"]:
            # files from the far end of the corpus: the window starts at file 0
            requests = traffic.make_requests(
                mix, seed, size, f"warm-{round_no}-{size}",
                first_group=traffic.request_groups(mix) - sent - size)
            traffic.burst(send, requests)
            sent += size
            bad = [r.result["problem"] for r in requests if r.result["problem"]]
            if bad:
                raise BenchFailure(f"warm-up request failed: {bad[0]}")
        have = family_compiles(server.scrape(srv.port))
        short = {k: (have.get(k, 0), v) for k, v in mix["warm_programs"].items()
                 if have.get(k, 0) < v}
        if not short:
            break
        note(phase="warm-up-short", round=round_no + 1, requests=sent, short=short)
    else:
        # what the server compiled, by shape: which bucket the bursts never made
        lines = [ln[:300] for ln in srv.log_since(0).splitlines()
                 if "ompil" in ln and any(k.split(".")[-1] in ln for k in short)]
        raise BenchFailure(f"warm-up did not reach the mix's program set (have, want): {short}; "
                           f"the server's compile lines for them: {lines[-12:]}")
    note(phase="warm-up", requests=sent, rounds=round_no + 1, compiled=have)
    return sent


# ------------------------------------------------------------------- window


class Sampler(threading.Thread):
    """Polls ``/metrics`` at 2 Hz through the window (traced runs only)."""

    def __init__(self, port: int) -> None:
        super().__init__(name="bench-sampler", daemon=True)
        self.port, self.samples, self._halt = port, [], threading.Event()

    def run(self) -> None:
        while not self._halt.wait(0.5):
            try:
                self.samples.append(server.scrape(self.port))
            except (OSError, BenchFailure):
                pass

    def stop(self) -> list:
        self._halt.set()
        self.join(timeout=10.0)
        return self.samples


def arm_profile(port: int, after_s: float, seconds: float, out_dir: Path, result: dict) -> threading.Thread:
    def work() -> None:
        time.sleep(after_s)
        status, body = server.http_json(
            port, "GET", f"/debug/profile?seconds={seconds:.1f}&dir={out_dir}", timeout=seconds + 120)
        result.update(status=status, **body)

    thread = threading.Thread(target=work, name="bench-profile", daemon=True)
    thread.start()
    return thread


def measure(srv: server.Server, mix: dict, seed: int, seconds: float, rate: float | None = None,
            first_group: int = 0):
    """One window of the mix's loop. Returns (requests, t0)."""
    send = lambda payload: server.chat_stream(srv.port, payload, mix["verifier"])  # noqa: E731
    if mix["loop"] == "open":
        due = traffic.poisson_schedule(rate or mix["rate_rps"], seconds, seed)
        requests = traffic.make_requests(mix, seed, len(due), "window", first_group=first_group)
        for req, t in zip(requests, due):
            req.due_s = t
        t0 = traffic.run_open_loop(send, requests, seconds, mix["workers"])
        return requests, t0
    requests = traffic.make_requests(mix, seed, traffic.request_groups(mix), "window")
    t0, sent = traffic.run_closed_loop(send, requests, seconds, mix["clients"],
                                        mix.get("stagger_s", 0.0))
    return requests[:sent], t0


def stage_table(port: int, last: int) -> dict:
    """Where the window's requests waited, by the program's own flight
    records (``/debug/flight?last=N``): mean ms a request stage over the
    ``last`` requests that finished. A note for a reader of a run that reads
    apart (was it ``pool_wait``, ``slot_wait`` or delivery?); no metric
    reads it, and a server that cannot give it is no fault of the run."""
    try:
        status, body = server.http_json(port, "GET", f"/debug/flight?last={last}", timeout=60.0)
    except (OSError, BenchFailure) as exc:
        return {"error": str(exc)[:200]}
    if status != 200 or "stages_ms" not in body:
        return {"error": f"status {status}"}
    return {"requests": body["requests"], "ttft_server_ms_mean": (body["ttft_server_ms"] or {}).get("mean"),
            "mean_ms": {stage: row["mean"] for stage, row in body["stages_ms"].items()}}


def child_json(cmd: list[str], env: dict, timeout: float) -> tuple[int, dict]:
    proc = subprocess.run(cmd, cwd=str(REPO), env=env, capture_output=True, text=True,
                          timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if not lines:
        return proc.returncode, {"error": proc.stderr.strip()[-600:]}
    return proc.returncode, json.loads(lines[-1])


# --------------------------------------------------------------------- main


def run(args) -> int:
    resolved = resolve_cell(args.workload, args.benchmark_file)
    cell = resolved["cell"]
    base_env = dict(os.environ)
    if "JAX_COMPILATION_CACHE_DIR" not in base_env:
        base_env["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_compile_cache")
        # the checkout's own cache holds what its cells compile: under a size
        # limit meant for another directory the least recently used programs
        # go, and once a run's programs outgrow it every run compiles anew
        base_env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    rehearsal = base_env.get("JAX_PLATFORMS") == "cpu"
    if rehearsal and cell["chips"] > 1 and "xla_force_host_platform_device_count" not in base_env.get("XLA_FLAGS", ""):
        base_env["XLA_FLAGS"] = (base_env.get("XLA_FLAGS", "")
                                 + f" --xla_force_host_platform_device_count={cell['chips']}").strip()
    probe = server.probe_device(base_env)
    if probe["platform"] != "tpu" and not rehearsal:
        raise BenchFailure(f"no accelerator (JAX sees {probe['platform']}); this benchmark "
                           "never measures on the CPU — set JAX_PLATFORMS=cpu to rehearse")
    if probe["count"] < cell["chips"]:
        raise BenchFailure(f"cell asks {cell['chips']} chips, JAX sees {probe['count']}")
    config = overlay(resolved["config"], rehearsal)
    mix = overlay(resolved["mix"], rehearsal)
    note(phase="plan", cell=cell["name"], config=config["name"], traffic=mix["name"],
         seed=args.seed, seconds=args.seconds, trace=args.trace, probe=probe, rehearsal=rehearsal)

    WORK.mkdir(exist_ok=True)
    paths = make_checkpoints(config, args.seed)
    env = server_environment(base_env, config, mix, paths)
    family = load_family(config)
    docs = traffic.make_corpus(mix, args.seed)
    trace_dir = WORK / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    obs = readers.Observations(model=config, mix=mix, server_env=env, device_kind=probe["kind"],
                               window_s=float(args.seconds))
    profile: dict = {}
    srv = server.Server(env, WORK / "server.log")
    try:
        ready_s = srv.wait_healthy(timeout_s=1100.0)
        server.upload_documents(srv.port, docs)
        warm_sent = warm_up(srv, mix, args.seed)
        want = {"generator": family.program_config(config),
                "reranker": config["encoders"]["reranker"],
                "embedder_dim": config["encoders"]["embedder_dim"],
                "kv_quant": env["KV_QUANT"], "platform": probe["platform"],
                "pool_hbm_bytes": family.pool_bytes(config, env),
                "corpus_size": len(docs), "chips": cell["chips"]}
        _info, info_problems = server.check_info(srv.port, want)
        obs.setup_s = time.perf_counter() - T_PROCESS_START
        note(phase="set-up", setup_s=round(obs.setup_s, 2), ready_s=round(ready_s, 2),
             uploaded=len(docs), warm_requests=warm_sent)

        if args.sweep:
            return sweep(srv, mix, args.seed)

        obs.prom_before = server.scrape(srv.port)
        log_mark = srv.log_size()
        sampler = Sampler(srv.port) if args.trace else None
        profiler = None
        if args.trace:
            sampler.start()
            span = max(min(4.0, args.seconds / 3.0), 0.5)
            profiler = arm_profile(srv.port, max(args.seconds * 0.4, 0.2), span, trace_dir, profile)
        requests, t0 = measure(srv, mix, args.seed, float(args.seconds))
        obs.prom_after = server.scrape(srv.port)
        window_log = srv.log_since(log_mark)
        if sampler is not None:
            obs.prom_samples = sampler.stop()
        if profiler is not None:
            profiler.join(timeout=180.0)
        status, obs.info = server.http_json(srv.port, "GET", "/info", timeout=60.0)
        stages = stage_table(srv.port, len(requests))
        rc = srv.terminate()
    finally:
        srv.sweep()

    obs.client = traffic.reduce_requests(requests, t0, float(args.seconds))
    client = obs.client
    # ---- what makes the run correct
    problems = list(info_problems)
    if probe["platform"] != "tpu":
        problems.append(f"platform is {probe['platform']}, not tpu (rehearsal)")
    in_window = {k: v for k, v in server.compiled_programs(window_log).items()
                 if k in mix["watch_compiles"]}
    before, after = family_compiles(obs.prom_before), family_compiles(obs.prom_after)
    counted = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    if in_window or counted:
        problems.append(f"compiled inside the window: log {in_window}, program counters {counted}")
    problems += server.error_counters(obs.prom_after)
    completed = readers.read_prom_delta(
        {"series": "sentio_tpu_serving_events_total", "label": "event", "sum": ["completed"]}, obs) or 0
    per_request = 2 if mix["verifier"] else 1
    served = client["attempted"] - client["failed"]
    if completed < per_request * served:
        problems.append(f"paged path completed {completed:.0f} generations for {served} "
                        f"answers x {per_request}: some came from the contiguous engine")
    if rc != 0:
        problems.append(f"server exit code {rc} on SIGTERM")
    if args.trace:  # for a reader of ``tick_host_share``: which phase its seconds were
        series = "sentio_tpu_tick_phase_seconds_sum"
        note(phase="tick-phases", seconds={
            phase: round(server.series_value(obs.prom_after, series, {"phase": phase})
                         - (server.series_value(obs.prom_before, series, {"phase": phase}) or 0.0), 4)
            for phase in sorted({labels["phase"] for name, labels, _ in obs.prom_after if name == series})})
    note(phase="stages", **stages)
    if client["failed"]:
        note(phase="failed-requests", count=client["failed"], examples=client["problems"])

    check_cmd = [sys.executable, str(HERE / "check.py"), "--config", str(resolved["config_path"]),
                 "--seed", str(args.seed)] + (["--rehearsal"] if rehearsal else [])
    check_rc, check = child_json(check_cmd, base_env, timeout=900.0)
    compared = check.pop("compared", {})
    note(phase="reference-check", rc=check_rc, **check)
    if check_rc != 0 or not check.get("ok"):
        problems.append(f"reference check failed: {check}")

    device = {"platform": probe["platform"], "kind": probe["kind"], "count": probe["count"],
              "memory_peak_bytes": (((obs.info or {}).get("device") or {}).get("memory") or {}).get("peak_bytes_in_use")}
    breakdown = None
    if args.trace:
        if profile.get("status") != 200:
            raise BenchFailure(f"/debug/profile did not run: {profile}")
        trc, reduced = child_json(
            [sys.executable, str(HERE / "trace.py"), str(trace_dir),
             "--block", json.dumps(trace.kernel_block(config))],
            {**base_env, "JAX_PLATFORMS": "cpu"}, timeout=600.0)
        if trc != 0 or "programs" not in reduced:
            raise BenchFailure(f"trace reduction failed: {reduced}")
        obs.trace = reduced
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = reduced["breakdown"]
        note(phase="trace", devices=reduced["devices"], programs=reduced["programs"])

    note(phase="window", attempted=client["attempted"], failed=client["failed"],
         answer_tokens_per_request=client["answer_tokens_per_request"],
         generator_late_ms_p50=client["generator_late_ms_p50"],
         generator_late_ms_max=client["generator_late_ms_max"], drain_s=client["drain_s"],
         ttft_samples=len(client["ttft_ms"]),
         # for a reader, not metrics: a tail of a few tens of requests, and
         # the longest silence inside any one answer (a stall shows here)
         ttft_p50_ms=traffic.percentile(client["ttft_ms"], 50),
         ttft_p90_ms=traffic.percentile(client["ttft_ms"], 90),
         ttft_max_ms=max(client["ttft_ms"], default=None),
         stream_gap_max_ms=client["stream_gap_max_ms"],
         # every request's gap between tokens, in order: the median of a few
         # tens of them moves in steps (a tick that carries a prefill segment
         # or not), and the list shows which step a run's median stood on
         tpot_ms_sorted=[round(v, 3) for v in sorted(client["tpot_ms"])],
         # and every request's time to its whole answer: answers end on ticks
         # and a closed loop's callers start on them, so these stand in groups
         # a tick apart too, and a stalled run shows the requests it held
         answer_ms_sorted=[round(v, 1) for v in sorted(client["answer_ms"])],
         answer_tokens_in_window=client["answer_tokens_in_window"], problems=problems)

    metrics: dict[str, dict] = {}
    kind = "per_layer" if args.trace else "end_to_end"
    for entry in resolved[kind]:
        value = readers.read_metric(readers.load_metric(kind, entry["name"]), obs)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result = {"correct": not problems, "attempted": client["attempted"],
              "failed": client["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # each number the reference check compared, beside its limit: last in the
    # result and last on the error stream, where a caller keeps only the ends
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, (value, limit) in compared.items()}
    if problems:
        print(f"benchmark: incorrect run of {cell['name']}, seed {args.seed}: {problems}",
              file=sys.stderr, flush=True)
    for name, (value, limit) in compared.items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def sweep(srv: server.Server, mix: dict, seed: int) -> int:
    """Rates of the mix's ``sweep`` block against one running server, each
    in ``orders`` arrival orders (the gaps are one set, shuffled)."""
    spec = mix["sweep"]
    asked = 0   # every row asks about files no earlier row did: the radix
    # cache would serve a repeated file's passages, and the row less work
    for rate in spec["rates_rps"]:
        for order in range(int(spec.get("orders", 1))):
            requests, t0 = measure(srv, mix, seed + order, float(spec["seconds"]), rate=rate,
                                   first_group=asked)
            asked += len(requests)
            client = traffic.reduce_requests(requests, t0, float(spec["seconds"]))
            ok_both = sum(1 for first, gap in client["ttft_tpot_pairs"]
                          if first <= spec["ttft_limit_ms"] and (gap or 0.0) <= spec["tpot_limit_ms"])
            half = len(client["ttft_ms"]) // 2
            note(phase="sweep", rate_rps=rate, order=order, sent=client["attempted"],
                 failed=client["failed"], drain_s=client["drain_s"],
                 within_limits=ok_both / max(client["attempted"], 1),
                 ttft_p50_ms=traffic.percentile(client["ttft_ms"], 50),
                 ttft_p90_ms=traffic.percentile(client["ttft_ms"], 90),
                 tpot_p50_ms=traffic.percentile(client["tpot_ms"], 50),
                 # a growing backlog shows as a later half slower than the first
                 ttft_p50_first_half_ms=traffic.percentile(client["ttft_ms"][:half], 50),
                 ttft_p50_second_half_ms=traffic.percentile(client["ttft_ms"][half:], 50))
    srv.terminate()
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--sweep", action="store_true")
    parser.add_argument("--benchmark-file", type=Path, default=REPO / "BENCHMARK.json",
                        help="another cell list than the repo's (tests: a cell made of scratch files)")
    args = parser.parse_args()
    try:
        return run(args)
    except BenchFailure as exc:
        print(f"benchmark failed: {exc}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
