#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, the way a user does (README "Real weights"):
seeded bf16 checkpoints with their config in the meta, then
``LLM_CHECKPOINT=<dir> python -m sentio_tpu.cli serve`` as a child process,
documents in through ``/upload``, ``/chat`` requests out (one streamed, one
with ``KV_QUANT=int8`` in a second server start), ``/metrics`` and ``/info``
read, SIGTERM, exit code 0. The model is the repo's ``LlamaConfig.llama3_8b``
at full width with ``n_layers`` cut to fit one 16 GB chip (printed as
``reduced``); the embedder is ``EncoderConfig.base``, the reranker a seeded
checkpoint at bge-reranker-base widths; no fake provider stands anywhere.

The serving graph degrades rather than fails ("every stage degrades, nothing
500s"), so a 200 proves nothing. Every answer is held to: not degraded, no
retrieval/generation error, no rerank fallback, a verifier verdict that came
from the model, zero error counters on ``/metrics``, and a decode program
that selected the Pallas kernel.

One process per chip: this parent NEVER imports JAX. It reads the device from
a short-lived probe child and then from the server's own ``/info``.

Contract (builder's instructions): needs one TPU chip with no arguments;
``--chips 4`` runs the tp=4 mesh comparison and nothing else. The last stdout
line is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and the exit code 0 — or ``"ok": false`` with the reason and a non-zero code.
With no TPU it stops within seconds; ``JAX_PLATFORMS=cpu`` set by the caller
asks for a tiny rehearsal of the control flow, which still ends ``"ok":
false``. Earlier lines are JSON too, one object per phase; timings in them
are smoke timings (host clock, cold compiles included), not metrics.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import ml_dtypes
import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

# import-light modules of the program itself (no JAX): standing alone in a
# directory, this script fails right here
from sentio_tpu.infra.compile_cache import ensure_compile_cache  # noqa: E402
from sentio_tpu.runtime.checkpoint import save_pytree  # noqa: E402

WORK = REPO / ".chip_smoke"       # seeded checkpoints, server logs (git-ignored)
OUT = REPO / "chiprun_out"        # what a chip call brings back

# LlamaConfig.llama3_8b() (sentio_tpu/models/llama.py) — widths are never
# cut; tests/test_chip_smoke.py pins this dict to the preset
LLAMA3_8B = dict(
    vocab_size=128_256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    mlp_dim=14_336, max_len=8192, rope_theta=500_000.0, dtype="bfloat16",
    norm_eps=1e-5,
)
# 32 layers are 16 GB in bf16; 8 leave room on a 16 GB chip for the page
# pool (1 GB), EncoderConfig.base (1.1 GB as held: cast to bf16 at load) and the reranker
SMOKE_LAYERS = 8
# bge-reranker-base, the width BASELINE.json names for the reranker
RERANKER_BASE = dict(
    vocab_size=250_002, dim=768, n_layers=12, n_heads=12, mlp_dim=3072,
    max_len=512, n_types=2, dtype="bfloat16",
)
# JAX_PLATFORMS=cpu rehearsal: the same control flow at CPU-test scale
TINY_LLAMA = dict(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, mlp_dim=128,
    max_len=1024, rope_theta=10_000.0, dtype="bfloat16", norm_eps=1e-5,
)
TINY_ENCODER = dict(
    vocab_size=512, dim=64, n_layers=2, n_heads=2, mlp_dim=128, max_len=128,
    n_types=2, dtype="bfloat16",
)

QUESTIONS = [
    "How does the systolic array multiply matrices?",
    "What keeps the key value cache from fragmenting?",
    "Which retrieval legs are fused before reranking?",
    "Why does decode attention walk a page table?",
]


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class SmokeFailure(Exception):
    pass


# ------------------------------------------------------------ seeded weights


def _normal_bf16(rng: np.random.Generator, shape, std: float) -> np.ndarray:
    return (rng.standard_normal(shape, dtype=np.float32) * std).astype(
        ml_dtypes.bfloat16)


def _dense(rng, n_in: int, n_out: int, bias: bool) -> dict:
    out = {"kernel": _normal_bf16(rng, (n_in, n_out), n_in ** -0.5)}
    if bias:
        out["bias"] = np.zeros((n_out,), np.float32)
    return out


def _llama_layer(rng, cfg: dict) -> dict:
    dim, mlp = cfg["dim"], cfg["mlp_dim"]
    kv_dim = cfg["n_kv_heads"] * (dim // cfg["n_heads"])
    return {
        "attn_norm": {"scale": np.ones((dim,), np.float32)},
        "attn": {"wq": _dense(rng, dim, dim, False), "wk": _dense(rng, dim, kv_dim, False),
                 "wv": _dense(rng, dim, kv_dim, False), "wo": _dense(rng, dim, dim, False)},
        "mlp_norm": {"scale": np.ones((dim,), np.float32)},
        "mlp": {"w_gate": _dense(rng, dim, mlp, False), "w_up": _dense(rng, dim, mlp, False),
                "w_down": _dense(rng, mlp, dim, False)},
    }


def _encoder_layer(rng, cfg: dict) -> dict:
    dim, mlp = cfg["dim"], cfg["mlp_dim"]
    norm = lambda: {"scale": np.ones((dim,), np.float32),  # noqa: E731
                    "bias": np.zeros((dim,), np.float32)}
    return {
        "attn": {k: _dense(rng, dim, dim, True) for k in ("wq", "wk", "wv", "wo")},
        "attn_norm": norm(),
        "mlp": {"w_in": _dense(rng, dim, mlp, True), "w_out": _dense(rng, mlp, dim, True)},
        "mlp_norm": norm(),
    }


def _layers(make, cfg: dict, seeds) -> dict:
    """One generator per layer, so layers fill in parallel (numpy releases
    the GIL while sampling) and the tree depends on the seed alone."""
    with concurrent.futures.ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as pool:
        built = list(pool.map(
            lambda s: make(np.random.default_rng(s), cfg), seeds))
    return {f"layers_{i}": layer for i, layer in enumerate(built)}


def write_llama_checkpoint(path: Path, cfg: dict, seed: int) -> None:
    """The tree of ``models.llama.init_llama``, in bf16, from ``seed``."""
    head, *layer_seeds = np.random.SeedSequence(seed).spawn(1 + cfg["n_layers"])
    rng = np.random.default_rng(head)
    params = {
        "embed_tokens": {"embedding": _normal_bf16(rng, (cfg["vocab_size"], cfg["dim"]), 0.02)},
        "lm_head": _dense(rng, cfg["dim"], cfg["vocab_size"], False),
        "final_norm": {"scale": np.ones((cfg["dim"],), np.float32)},
        **_layers(_llama_layer, cfg, layer_seeds),
    }
    save_pytree(path, params, meta={"family": "llama", "config": cfg})


def write_reranker_checkpoint(path: Path, cfg: dict, seed: int) -> None:
    """The tree of ``models.cross_encoder.init_cross_encoder``."""
    head, *layer_seeds = np.random.SeedSequence(seed).spawn(1 + cfg["n_layers"])
    rng = np.random.default_rng(head)
    dim = cfg["dim"]
    encoder = {
        "embed_tokens": {"embedding": _normal_bf16(rng, (cfg["vocab_size"], dim), 0.02)},
        "embed_positions": {"embedding": _normal_bf16(rng, (cfg["max_len"], dim), 0.02)},
        "embed_types": {"embedding": _normal_bf16(rng, (cfg["n_types"], dim), 0.02)},
        "embed_norm": {"scale": np.ones((dim,), np.float32),
                       "bias": np.zeros((dim,), np.float32)},
        **_layers(_encoder_layer, cfg, layer_seeds),
    }
    params = {"encoder": encoder, "head": _dense(rng, dim, 1, True)}
    save_pytree(path, params, meta={"family": "cross-encoder", "config": cfg})


def make_documents(seed: int, n_docs: int = 4) -> list[tuple[str, bytes]]:
    """A few text files on the topics the questions ask about."""
    rng = np.random.default_rng(seed)
    topics = [
        ("mxu.txt", "The systolic array multiplies matrices by streaming operand "
                    "tiles through a grid of multiply accumulate cells"),
        ("paging.txt", "The key value cache is paged so sequences of any length "
                       "share one pool of fixed size pages without fragmenting"),
        ("fusion.txt", "Hybrid retrieval fuses the dense leg and the sparse leg "
                       "with reciprocal rank fusion before the reranker scores pairs"),
        ("decode.txt", "Decode attention walks the page table of each row so only "
                       "the pages a sequence owns ever leave device memory"),
    ]
    filler = ("batch", "kernel", "mesh", "tile", "vector", "cache", "token",
              "prefill", "decode", "shard", "matrix", "memory", "page", "rank")
    docs = []
    for name, lead in topics[:n_docs]:
        sentences = [lead + "."]
        for _ in range(12):
            words = rng.choice(filler, size=int(rng.integers(8, 14)))
            sentences.append(" ".join([lead.split()[1], *words]).capitalize() + ".")
        docs.append((name, " ".join(sentences).encode()))
    return docs


# ------------------------------------------------------------------ children


def probe_device(timeout_s: float = 180.0) -> dict:
    """Ask JAX what it sees, in a child that exits (and lets go of the chip)
    before the server starts. The parent stays off JAX."""
    code = ("import json, jax\n"
            "d = jax.devices()\n"
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d)}))\n")
    try:
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=timeout_s, cwd=str(REPO))
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"device probe hung for {timeout_s:.0f}s")
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        raise SmokeFailure("JAX found no device: " + proc.stderr.strip()[-400:])
    return json.loads(lines[-1])


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, method: str, path: str, body: bytes | None = None,
          headers: dict | None = None, timeout: float = 900.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _json(port: int, method: str, path: str, payload=None, timeout: float = 900.0):
    body = None if payload is None else json.dumps(payload).encode()
    status, raw = _http(port, method, path, body,
                        {"Content-Type": "application/json"}, timeout)
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, {"raw": raw[:300].decode(errors="replace")}


class Server:
    """``python -m sentio_tpu.cli serve`` as a child in its own process
    group, so SIGTERM's stragglers can be swept whatever happened."""

    def __init__(self, name: str, env: dict) -> None:
        self.name = name
        self.port = _free_port()
        self.log_path = WORK / f"server-{name}.log"
        self.t_spawn = time.perf_counter()
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "sentio_tpu.cli", "serve",
             "--host", "127.0.0.1", "--port", str(self.port)],
            cwd=str(REPO), env={**os.environ, **env}, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def wait_healthy(self, timeout_s: float) -> float:
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server {self.name} exited rc={self.proc.returncode} before "
                    f"/health: {self.log_tail()}")
            try:
                status, _ = _http(self.port, "GET", "/health", timeout=5.0)
                if status == 200:
                    return time.perf_counter() - self.t_spawn
            except OSError:
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"server {self.name} not healthy after {timeout_s:.0f}s: "
                           f"{self.log_tail()}")

    def log_tail(self, n: int = 1500) -> str:
        self._log.flush()
        data = self.log_path.read_bytes()[-n:]
        return data.decode(errors="replace")

    def terminate(self) -> int:
        """SIGTERM → the server drains and exits; returns its exit code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=90.0)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"server {self.name} ignored SIGTERM for 90s")

    def sweep(self) -> None:
        """Leave nothing running, however the phase ended."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(timeout=10.0)
        except subprocess.TimeoutExpired:
            pass
        self._log.close()
        OUT.mkdir(exist_ok=True)
        (OUT / self.log_path.name).write_bytes(self.log_path.read_bytes()[-200_000:])


# -------------------------------------------------------------------- checks


def upload_documents(port: int, docs) -> dict:
    boundary = "chipsmoke-boundary-7f3a"
    parts = []
    for name, data in docs:
        parts.append(
            f"--{boundary}\r\nContent-Disposition: form-data; name=\"file\"; "
            f"filename=\"{name}\"\r\nContent-Type: text/plain\r\n\r\n".encode()
            + data + b"\r\n")
    body = b"".join(parts) + f"--{boundary}--\r\n".encode()
    status, raw = _http(port, "POST", "/upload", body,
                        {"Content-Type": f"multipart/form-data; boundary={boundary}"})
    out = json.loads(raw)
    bad = [f for f in out.get("files", []) if "error" in f]
    if status != 200 or bad or len(out.get("files", [])) != len(docs):
        raise SmokeFailure(f"/upload status {status}: {out}")
    return out


def check_verdict(evaluation, where: str) -> None:
    """The verdict must come from the MODEL's audit reply. The verifier's
    soft-fail also answers ``warn`` — with a ``verifier error`` note."""
    if not isinstance(evaluation, dict) or evaluation.get("verdict") not in (
            "pass", "warn", "fail"):
        raise SmokeFailure(f"{where}: no verifier verdict: {evaluation}")
    soft = [n for n in evaluation.get("notes", [])
            if str(n).startswith(("verifier error", "verify failed"))]
    if soft:
        raise SmokeFailure(f"{where}: verifier soft-failed: {soft}")


def chat(port: int, question: str) -> dict:
    t0 = time.perf_counter()
    status, out = _json(port, "POST", "/chat", {"question": question, "top_k": 3,
                                                "mode": "fast"})
    wall = time.perf_counter() - t0
    where = f"/chat {question[:24]!r}"
    if status != 200:
        raise SmokeFailure(f"{where}: status {status}: {out}")
    meta = out.get("metadata", {})
    problems = []
    if meta.get("degraded") is not False:
        problems.append(f"degraded={meta.get('degraded')!r} ({meta.get('error')})")
    for key in ("retrieval_error", "generation_error"):
        if key in meta:
            problems.append(f"{key}={meta[key]!r}")
    if meta.get("rerank_fallback") is not False:
        problems.append(f"rerank_fallback={meta.get('rerank_fallback')!r}")
    if meta.get("reranker") != "cross_encoder":
        problems.append(f"reranker={meta.get('reranker')!r}")
    if meta.get("generator") != "tpu":
        problems.append(f"generator={meta.get('generator')!r}")
    if not meta.get("num_retrieved") or not out.get("sources"):
        problems.append("nothing retrieved")
    if not out.get("answer"):
        problems.append("empty answer")
    if problems:
        raise SmokeFailure(f"{where}: " + "; ".join(problems))
    check_verdict(meta.get("evaluation"), where)
    return {"wall_s": round(wall, 2), "answer_chars": len(out["answer"]),
            "sources": len(out["sources"]), "verdict": meta["evaluation"]["verdict"],
            "generation_ms": meta.get("generation_ms"), "verify_ms": meta.get("verify_ms"),
            "retrieval_ms": meta.get("retrieval_ms"), "rerank_ms": meta.get("rerank_ms")}


def chat_stream(port: int, question: str) -> dict:
    t0 = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900.0)
    events: dict[str, list] = {}
    done = False
    ttft = None
    try:
        conn.request("POST", "/chat", json.dumps(
            {"question": question, "top_k": 3, "mode": "fast", "stream": True}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f"stream: status {resp.status}: {resp.read()[:300]!r}")
        for raw in resp:
            line = raw.decode(errors="replace").strip()
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            if data == "[DONE]":
                done = True
                continue
            for kind, payload in json.loads(data).items():
                if kind == "token" and ttft is None:
                    ttft = time.perf_counter() - t0
                events.setdefault(kind, []).append(payload)
    finally:
        conn.close()
    wall = time.perf_counter() - t0
    if "error" in events:
        raise SmokeFailure(f"stream: error event {events['error']}")
    if not done or not events.get("token") or not (events.get("sources") or [[]])[0]:
        raise SmokeFailure(f"stream: incomplete (done={done}, kinds={sorted(events)})")
    check_verdict((events.get("verdict") or [None])[-1], "stream")
    return {"wall_s": round(wall, 2), "first_token_s": round(ttft, 2),
            "token_events": len(events["token"]),
            "answer_chars": len("".join(events["token"])),
            "verdict": events["verdict"][-1]["verdict"]}


def parse_metrics(text: str) -> list[tuple[str, dict, float]]:
    rows = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        for part in rest.rstrip("}").split(","):
            key, sep, val = part.partition("=")
            if sep:
                labels[key.strip()] = val.strip().strip('"')
        try:
            rows.append((name, labels, float(value)))
        except ValueError:
            continue
    return rows


# serving events that mean a decode tick, a request or a replica went wrong
ERROR_EVENTS = ("tick_failures", "requeued", "pump_leaked", "failovers",
                "shed", "expired", "cancelled")


def check_metrics(port: int, n_chats: int) -> dict:
    status, raw = _http(port, "GET", "/metrics")
    if status != 200:
        raise SmokeFailure(f"/metrics status {status}")
    rows = parse_metrics(raw.decode())
    problems = []
    events = {lab.get("event"): val for name, lab, val in rows
              if name == "sentio_tpu_serving_events_total"}
    for event in ERROR_EVENTS:
        if events.get(event, 0.0) != 0.0:
            problems.append(f"{event}={events[event]}")
    # generate + verify per /chat, all through the paged service: fewer
    # means some answer was not decoded at all (an echo or a canned reply)
    if events.get("completed", 0.0) < 2 * n_chats:
        problems.append(f"paged completed={events.get('completed')} < {2 * n_chats}")
    for name, lab, val in rows:
        if name == "sentio_requests_total" and lab.get("status", "200")[0] in "45" and val:
            problems.append(f"{lab.get('endpoint')} answered {lab.get('status')} x{val:.0f}")
        if name in ("sentio_tpu_shed_total", "sentio_tpu_worker_deaths_total") and val:
            problems.append(f"{name}{lab}={val}")
    if problems:
        raise SmokeFailure("/metrics error counters: " + "; ".join(problems))
    compiles = {lab.get("family"): val for name, lab, val in rows
                if name == "sentio_tpu_xla_compiles_total"}
    return {"xla_compiles": int(sum(compiles.values())),
            "xla_compiles_by_family": {k: int(v) for k, v in sorted(compiles.items())},
            "paged_completed": int(events.get("completed", 0)),
            "ticks": int(events.get("ticks", 0))}


def check_info(port: int, want: dict) -> dict:
    status, info = _json(port, "GET", "/info")
    if status != 200:
        raise SmokeFailure(f"/info status {status}: {info}")
    gen, emb, rer = info["generator"], info["embedder"], info["reranker"]
    problems = []
    if gen["provider"] != "tpu" or emb["provider"] != "tpu" or rer["kind"] != "cross_encoder":
        problems.append(f"fake provider on the path: {gen['provider']}/"
                        f"{emb['provider']}/{rer['kind']}")
    for name, got, expect in (("generator", gen["model"], want["llama"]),
                              ("reranker", rer["model"], want["reranker"])):
        if got != expect:
            problems.append(f"{name} runs {got}, not {expect}")
    if (emb["model"] or {}).get("dim") != want["embedder_dim"]:
        problems.append(f"embedder runs {emb['model']}")
    if gen.get("kv_quant") != want["kv_quant"]:
        problems.append(f"kv_quant={gen.get('kv_quant')!r}, asked {want['kv_quant']!r}")
    if info["retrieval"].get("bm25_backend") != "native":
        problems.append(f"bm25 backend {info['retrieval'].get('bm25_backend')!r}")
    if info["retrieval"]["corpus_size"] <= 0:
        problems.append("empty corpus")
    if want["platform"] == "tpu" and gen.get("paged_attention") != "pallas":
        problems.append(f"decode attention is {gen.get('paged_attention')!r}, not pallas")
    if problems:
        raise SmokeFailure("/info: " + "; ".join(problems))
    return info


# -------------------------------------------------------------------- phases


def serve_phase(name: str, env: dict, want: dict, docs, questions, stream: bool) -> dict:
    """One server start: /health, /upload, /chat ..., /metrics, /info,
    SIGTERM → exit code 0. Returns what is worth keeping."""
    server = Server(name, env)
    try:
        ready_s = server.wait_healthy(timeout_s=900.0)
        upload_documents(server.port, docs)
        chats = [chat(server.port, questions[0])]
        first_answer_s = time.perf_counter() - server.t_spawn
        chats += [chat(server.port, q) for q in questions[1:]]
        if stream:
            chats.append({"stream": True, **chat_stream(server.port, QUESTIONS[-1])})
        metrics = check_metrics(server.port, len(chats))
        info = check_info(server.port, want)
        rc = server.terminate()
        if rc != 0:
            raise SmokeFailure(f"server {name} exited rc={rc} on SIGTERM: "
                               f"{server.log_tail(600)}")
    finally:
        server.sweep()
    device = info["device"]
    record = {
        "phase": f"serve:{name}", "kv_quant": want["kv_quant"],
        # set-up as a caller feels it: process start → weights on the device
        # (/health) → the first answer, which compiles what it touches
        "setup_s": round(first_answer_s, 1), "ready_s": round(ready_s, 1),
        "requests_smoke_timings": chats, **metrics,
        "paged_attention": info["generator"]["paged_attention"],
        "pool_hbm_bytes": info["generator"]["pool_hbm_bytes"],
        "bm25_backend": info["retrieval"]["bm25_backend"],
        "peak_bytes_in_use": (device.get("memory") or {}).get("peak_bytes_in_use"),
        "compile_cache_dir": info["compile_cache_dir"],
        "models": {"generator": info["generator"]["model"],
                   "embedder": info["embedder"]["model"],
                   "reranker": info["reranker"]["model"]},
        "server_exit_code": rc,
    }
    emit(**record)
    record["device"] = {"platform": device["platform"], "kind": device["kind"],
                        "count": device["n_devices"]}
    return record


def deploy(rehearsal: bool, llama: dict, seed: int, platform: str, **env_over):
    """Write the seeded checkpoints of one deployment and return what a
    server start needs: (environment, what /info must then report)."""
    reranker = dict(TINY_ENCODER) if rehearsal else dict(RERANKER_BASE)
    t0 = time.perf_counter()
    shutil.rmtree(WORK / "llama", ignore_errors=True)  # disk: never two at once
    write_llama_checkpoint(WORK / "llama", llama, seed)
    write_reranker_checkpoint(WORK / "reranker", reranker, seed + 1)
    emit(phase="checkpoints", seconds=round(time.perf_counter() - t0, 1),
         bytes=sum(p.stat().st_size for p in WORK.rglob("arrays.npz")))
    env = {
        "LLM_PROVIDER": "tpu", "LLM_CHECKPOINT": str(WORK / "llama"),
        "EMBEDDER_PROVIDER": "tpu", "EMBEDDER_PRESET": "tiny" if rehearsal else "base",
        "USE_RERANKER": "1", "RERANKER_KIND": "cross_encoder",
        "RERANKER_CHECKPOINT": str(WORK / "reranker"),
        "USE_VERIFIER": "1", "VERIFY_MODE": "sync", "KV_QUANT": "none",
        # a failed native build is an error, not a quiet numpy run
        "BM25_BACKEND": "native",
        "LLM_MAX_TOKENS": "32", "VERIFIER_MAX_TOKENS": "32",
        # ByteTokenizer: one token per byte, so 256 "tokens" of context are
        # 1 KiB and the whole prompt stays inside the 2048 prefill bucket
        "CONTEXT_TOKEN_BUDGET": "64" if rehearsal else "256",
        # a 4096-token window (rehearsal: 1024)
        "KV_PAGE_SIZE": "16" if rehearsal else "128",
        "KV_MAX_PAGES_PER_SEQ": "64" if rehearsal else "32",
        # cold compiles of unrolled layers run well past the default 120 s
        # stall watchdog on a busy host
        "TICK_STALL_BUDGET_S": "1800",
        **env_over,
    }
    want = {"llama": llama, "reranker": reranker, "platform": platform,
            "embedder_dim": 64 if rehearsal else 1024, "kv_quant": "none"}
    return env, want


def run_one_chip(args, probe: dict) -> dict:
    rehearsal = probe["platform"] != "tpu"
    llama = dict(TINY_LLAMA) if rehearsal else {**LLAMA3_8B, "n_layers": SMOKE_LAYERS}
    emit(phase="plan", model="LlamaConfig.llama3_8b" if not rehearsal else "tiny (cpu rehearsal)",
         reduced=({} if rehearsal else
                  {"n_layers": {"from": LLAMA3_8B["n_layers"], "to": SMOKE_LAYERS,
                                "why": "32 layers are 16 GB in bf16; one chip has 16 GB"}}),
         seed=args.seed, probe=probe)
    env, want = deploy(rehearsal, llama, args.seed, probe["platform"])
    docs = make_documents(args.seed)
    # three starts in one call: bf16 pages cold, int8 pages, bf16 again —
    # the third finds every program in the compile cache the first left
    plan = [("bf16-cold", "none", QUESTIONS[:2], True),
            ("int8", "int8", QUESTIONS[2:3], False),
            ("bf16-warm", "none", QUESTIONS[:1], False)]
    records = [
        serve_phase(name, {**env, "KV_QUANT": quant}, {**want, "kv_quant": quant},
                    docs, questions, stream)
        for name, quant, questions, stream in plan
    ]
    # what compiling cost each start: health → first answer. The warm start
    # must find the cold one's programs — unless the machine came with a
    # placed cache that was warm already, which shows as a quick cold start
    cold, warm = (round(r["setup_s"] - r["ready_s"], 1) for r in (records[0], records[-1]))
    emit(phase="compile-cache", dir=records[0]["compile_cache_dir"],
         cold_setup_s=records[0]["setup_s"], warm_setup_s=records[-1]["setup_s"],
         cold_first_answer_s=cold, warm_first_answer_s=warm)
    if not rehearsal and cold > 60.0 and warm > 0.5 * cold:
        raise SmokeFailure(
            f"compile cache gave no warm start: first answer {warm}s after "
            f"/health, {cold}s when cold")
    return records[0]["device"]


# ------------------------------------------------------------ four-chip path


def run_mesh(args, probe: dict) -> dict:
    """``--chips 4``: the same depth-cut checkpoint served by the paged
    engine on one device and on a tp=4 mesh, in ONE child process; then,
    with ``--full-depth``, the 32-layer preset booting on the mesh."""
    rehearsal = probe["platform"] != "tpu"
    # the rehearsal's tiny model needs heads that divide over tp=4, and four
    # virtual devices to stand in for the four chips
    tiny = dict(TINY_LLAMA, dim=128, n_heads=8, n_kv_heads=4)
    virtual = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"} if rehearsal else {}
    llama = tiny if rehearsal else {**LLAMA3_8B, "n_layers": SMOKE_LAYERS}
    write_llama_checkpoint(WORK / "llama", llama, args.seed)
    proc = subprocess.run(
        [sys.executable, "-m", "sentio_tpu.eval.mesh_parity", str(WORK / "llama"),
         "--tp", "4", "--page-size", "16" if rehearsal else "128"],
        cwd=str(REPO), env={**os.environ, **virtual}, capture_output=True,
        text=True, timeout=1500.0)
    sys.stderr.write(proc.stderr[-4000:])
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if proc.returncode not in (0, 1) or not lines:
        raise SmokeFailure(f"mesh comparison rc={proc.returncode}: {proc.stderr[-1200:]}")
    result = json.loads(lines[-1])
    emit(phase="mesh:tp4-vs-one-device",
         reduced={} if rehearsal else {"n_layers": {"from": 32, "to": SMOKE_LAYERS}},
         **result)
    if not result["ok"]:
        raise SmokeFailure(f"tp=4 disagrees with one device: {result}")
    if not args.full_depth:
        emit(phase="mesh:full-depth-boot", status="not run")
        return result["device"]
    full = dict(tiny, n_layers=4) if rehearsal else dict(LLAMA3_8B)
    env, want = deploy(rehearsal, full, args.seed, probe["platform"],
                       MESH_TP="4", MESH_DP="1", **virtual)
    record = serve_phase("tp4-full-depth", env, want, make_documents(args.seed),
                         QUESTIONS[:2], stream=False)
    return record["device"]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run the tp=4 mesh comparison and nothing else")
    parser.add_argument("--full-depth", action="store_true",
                        help="with --chips 4: also boot the 32-layer preset on the mesh")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    ensure_compile_cache()  # inherited by every child: one cache for the run
    device = {"platform": "none", "kind": "none", "count": 0}
    ok, reason = False, ""
    try:
        probe = probe_device()
        device = probe
        if probe["platform"] != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
            raise SmokeFailure(
                f"no TPU (JAX sees {probe['platform']}); this smoke never "
                "continues on the CPU — set JAX_PLATFORMS=cpu to rehearse")
        if probe["platform"] == "tpu" and probe["count"] != args.chips:
            raise SmokeFailure(f"--chips {args.chips} but JAX sees {probe['count']}")
        shutil.rmtree(WORK, ignore_errors=True)
        WORK.mkdir(parents=True)
        device = run_mesh(args, probe) if args.chips == 4 else run_one_chip(args, probe)
        ok = device["platform"] == "tpu"
        if not ok:
            reason = f"rehearsal on {device['platform']}: the platform is not a TPU"
    except SmokeFailure as exc:
        reason = str(exc)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    if ok:
        emit(ok=True, device=device)
        return 0
    emit(ok=False, reason=reason, device=device)
    return 1


if __name__ == "__main__":
    sys.exit(main())
