"""The system under test as a user starts it: ``python -m sentio_tpu.cli
serve`` as a child process, spoken to over HTTP.

Copied from ``chip_smoke.py`` (PR 21), where this driver was proven on the
chip: the yardstick may not import a file later PRs can edit. The parent
NEVER imports JAX — one process per chip — and reads the device from a
short-lived probe child and then from the server's own ``/info``.
"""

from __future__ import annotations

import functools
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


class BenchFailure(Exception):
    """The run cannot produce a result (set-up failed, server died)."""


def probe_device(env: dict, timeout_s: float = 180.0) -> dict:
    """Ask JAX what it sees, in a child that exits (and lets go of the chip)
    before the server starts."""
    code = ("import json, jax\n"
            "d = jax.devices()\n"
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d)}))\n")
    try:
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=timeout_s, cwd=str(REPO), env=env)
    except subprocess.TimeoutExpired:
        raise BenchFailure(f"device probe hung for {timeout_s:.0f}s") from None
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        raise BenchFailure("JAX found no device: " + proc.stderr.strip()[-400:])
    return json.loads(lines[-1])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_call(port: int, method: str, path: str, body: bytes | None = None,
              headers: dict | None = None, timeout: float = 900.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def http_json(port: int, method: str, path: str, payload=None, timeout: float = 900.0):
    body = None if payload is None else json.dumps(payload).encode()
    status, raw = http_call(port, method, path, body,
                            {"Content-Type": "application/json"}, timeout)
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, {"raw": raw[:300].decode(errors="replace")}


class Server:
    """``python -m sentio_tpu.cli serve`` as a child in its own process
    group, so SIGTERM's stragglers can be swept whatever happened."""

    def __init__(self, env: dict, log_path: Path) -> None:
        self.port = free_port()
        self.log_path = log_path
        self.t_spawn = time.perf_counter()
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "sentio_tpu.cli", "serve",
             "--host", "127.0.0.1", "--port", str(self.port)],
            cwd=str(REPO), env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def wait_healthy(self, timeout_s: float) -> float:
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited rc={self.proc.returncode} before /health: "
                    f"{self.log_tail()}")
            try:
                status, _ = http_call(self.port, "GET", "/health", timeout=5.0)
                if status == 200:
                    return time.perf_counter() - self.t_spawn
            except OSError:
                pass
            time.sleep(0.5)
        raise BenchFailure(f"server not healthy after {timeout_s:.0f}s: {self.log_tail()}")

    def log_size(self) -> int:
        return self.log_path.stat().st_size

    def log_since(self, offset: int) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(offset)
            return f.read().decode(errors="replace")

    def log_tail(self, n: int = 1500) -> str:
        return self.log_path.read_bytes()[-n:].decode(errors="replace")

    def terminate(self) -> int:
        """SIGTERM → the server drains and exits; returns its exit code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=90.0)
        except subprocess.TimeoutExpired:
            raise BenchFailure("server ignored SIGTERM for 90s") from None

    def sweep(self) -> None:
        """Leave nothing running, however the run ended."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(timeout=10.0)
        except subprocess.TimeoutExpired:
            pass
        self._log.close()


def upload_documents(port: int, docs, batch: int = 100) -> int:
    """``/upload`` multipart, ``batch`` files to a request."""
    boundary = "bench-boundary-7f3a"
    for start in range(0, len(docs), batch):
        part = docs[start:start + batch]
        body = b"".join(
            f"--{boundary}\r\nContent-Disposition: form-data; name=\"file\"; "
            f"filename=\"{name}\"\r\nContent-Type: text/plain\r\n\r\n".encode()
            + data + b"\r\n" for name, data in part
        ) + f"--{boundary}--\r\n".encode()
        status, raw = http_call(
            port, "POST", "/upload", body,
            {"Content-Type": f"multipart/form-data; boundary={boundary}"})
        try:
            out = json.loads(raw)
        except ValueError:
            out = {"raw": raw[:300].decode(errors="replace")}
        bad = [f for f in out.get("files", []) if "error" in f]
        if status != 200 or bad or len(out.get("files", [])) != len(part):
            raise BenchFailure(f"/upload status {status}: {str(out)[:400]}")
    return len(docs)


def verdict_problem(evaluation) -> str | None:
    """The verdict must come from the MODEL's audit reply. The verifier's
    soft-fail also answers ``warn`` — with a ``verifier error`` note."""
    if not isinstance(evaluation, dict) or evaluation.get("verdict") not in (
            "pass", "warn", "fail"):
        return f"no verifier verdict: {str(evaluation)[:120]}"
    soft = [n for n in evaluation.get("notes", [])
            if str(n).startswith(("verifier error", "verify failed"))]
    return f"verifier soft-failed: {soft}" if soft else None


@functools.lru_cache(maxsize=1)
def fallback_openings() -> tuple[str, ...]:
    """How the program's degradation ladder begins its answers (its own
    ``prompts/fallback_*.md``): with no verifier on the path, this is the
    only mark a degraded stream carries."""
    return tuple(p.read_text().strip()[:24] for p in sorted((REPO / "prompts").glob("fallback_*.md")))


def chat_stream(port: int, payload: dict, want_verdict: bool = True,
                clock=time.perf_counter, timeout: float = 600.0) -> dict:
    """One streamed ``/chat``. Returns client-side times of the ``sources``
    event and of every ``token`` event with its text, and the reason the
    answer does not count (``problem``), if any. Never raises for a bad
    answer: a failed request is a data point, not the end of the run."""
    out = {"t_send": clock(), "t_sources": None, "pieces": [], "problem": None,
           "t_done": None}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    events: dict[str, list] = {}
    done = False
    try:
        conn.request("POST", "/chat", json.dumps({**payload, "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            out["problem"] = f"status {resp.status}: {resp.read()[:200]!r}"
            return out
        for raw in resp:
            line = raw.decode(errors="replace").strip()
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            now = clock()
            if data == "[DONE]":
                done = True
                continue
            for kind, value in json.loads(data).items():
                if kind == "token":
                    out["pieces"].append((now, value))
                elif kind == "sources" and out["t_sources"] is None:
                    out["t_sources"] = now
                events.setdefault(kind, []).append(value)
    except (OSError, http.client.HTTPException, ValueError) as exc:
        out["problem"] = f"{type(exc).__name__}: {exc}"
        return out
    finally:
        conn.close()
        out["t_done"] = clock()
    if "error" in events:
        out["problem"] = f"error event {events['error']}"
    elif not done or not out["pieces"]:
        out["problem"] = f"incomplete (done={done}, kinds={sorted(events)})"
    elif not (events.get("sources") or [[]])[0]:
        out["problem"] = "nothing retrieved"
    elif want_verdict:
        # a degraded answer arrives as ONE token event holding the apology
        # ladder's text and no verdict: the verdict check catches it
        out["problem"] = verdict_problem((events.get("verdict") or [None])[-1])
    elif out["pieces"][0][1].startswith(fallback_openings()):
        out["problem"] = f"degraded answer: {out['pieces'][0][1][:60]!r}"
    return out


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


def scrape(port: int) -> list[tuple[str, dict, float]]:
    status, raw = http_call(port, "GET", "/metrics", timeout=30.0)
    if status != 200:
        raise BenchFailure(f"/metrics status {status}")
    return parse_metrics(raw.decode())


def series_value(rows, name: str, labels: dict | None = None) -> float | None:
    """Sum of the rows of ``name`` whose labels include ``labels``."""
    want = labels or {}
    hits = [val for n, lab, val in rows
            if n == name and all(lab.get(k) == v for k, v in want.items())]
    return sum(hits) if hits else None


# serving events that mean a decode tick, a request or a replica went wrong
ERROR_EVENTS = ("tick_failures", "requeued", "pump_leaked", "failovers",
                "shed", "expired", "cancelled")


def error_counters(rows) -> list[str]:
    problems = []
    for event in ERROR_EVENTS:
        val = series_value(rows, "sentio_tpu_serving_events_total", {"event": event})
        if val:
            problems.append(f"{event}={val}")
    for name, lab, val in rows:
        if name == "sentio_requests_total" and lab.get("status", "200")[0] in "45" and val:
            problems.append(f"{lab.get('endpoint')} answered {lab.get('status')} x{val:.0f}")
        if name in ("sentio_tpu_shed_total", "sentio_tpu_worker_deaths_total") and val:
            problems.append(f"{name}{lab}={val}")
    return problems


def check_info(port: int, want: dict) -> tuple[dict, list[str]]:
    """``/info`` against what the configuration file asks for. Returns the
    info and the list of differences (empty = equal)."""
    status, info = http_json(port, "GET", "/info", timeout=60.0)
    if status != 200:
        raise BenchFailure(f"/info status {status}: {info}")
    gen, emb, rer = info["generator"], info["embedder"], info["reranker"]
    problems = []
    if gen["provider"] != "tpu" or emb["provider"] != "tpu" or rer["kind"] != "cross_encoder":
        problems.append(f"fake provider on the path: {gen['provider']}/"
                        f"{emb['provider']}/{rer['kind']}")
    for name, got, expect in (("generator", gen["model"], want["generator"]),
                              ("reranker", rer["model"], want["reranker"])):
        if got != expect:
            problems.append(f"{name} runs {got}, not {expect}")
    if (emb["model"] or {}).get("dim") != want["embedder_dim"]:
        problems.append(f"embedder runs {emb['model']}")
    if gen.get("kv_quant") != want["kv_quant"]:
        problems.append(f"kv_quant={gen.get('kv_quant')!r}, asked {want['kv_quant']!r}")
    if gen.get("pool_hbm_bytes") != want["pool_hbm_bytes"]:
        problems.append(f"pool_hbm_bytes={gen.get('pool_hbm_bytes')}, the "
                        f"configuration's slots x pages x page size give {want['pool_hbm_bytes']}")
    if info["retrieval"].get("bm25_backend") != "native":
        problems.append(f"bm25 backend {info['retrieval'].get('bm25_backend')!r}")
    if info["retrieval"]["corpus_size"] < want["corpus_size"]:
        problems.append(f"corpus {info['retrieval']['corpus_size']} < {want['corpus_size']} uploaded")
    if (info["device"] or {}).get("n_devices") != want["chips"] and want["platform"] == "tpu":
        problems.append(f"{(info['device'] or {}).get('n_devices')} devices, cell asks {want['chips']}")
    if want["platform"] == "tpu" and gen.get("paged_attention") != "pallas":
        problems.append(f"decode attention is {gen.get('paged_attention')!r}, not pallas")
    return info, problems


COMPILE_LINE = re.compile(r"Compiling jit\(([\w.<>-]+)\)")


def compiled_programs(log_text: str) -> dict[str, int]:
    """Programs JAX compiled (or fetched from the persistent cache), counted
    by jitted function name from ``JAX_LOG_COMPILES=1`` lines."""
    counts: dict[str, int] = {}
    for name in COMPILE_LINE.findall(log_text):
        counts[name] = counts.get(name, 0) + 1
    return counts
