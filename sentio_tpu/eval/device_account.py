"""The program's account of the device beside the device's own.

``python -m sentio_tpu.eval.device_account <dir-or-xplane.pb>`` reads one
profiler trace (``/debug/profile``; the benchmark's traced runs leave theirs
under ``benchmark/.work/trace``) and prints one JSON object:

* ``annotations``: per program of ``DEVICE_PROGRAMS`` the ``device.<program>``
  events on ``/host:CPU`` (infra/tracing.py's completion stamps) — their
  count, the sum of the ``dur_ms`` they carry, and the part of those
  intervals (a stamp lies at its program's end) inside the device's window;
* ``modules``: per program name the executions on the first device plane's
  ``XLA Modules`` line — count and total ms (the CPU backend has no such
  plane: empty there) — and ``modules_by_program``, the same under the
  names the stamps book them by (``encoders``: embed and rerank are both
  ``jit_fwd``);
* ``matched``: each ``decode`` and ``prefill`` stamp beside the execution
  that ended just before it — pairs, the two sums over the pairs, and how
  long a stamp lagged its program's end;
* ``window_ms``: first to last device execution.

The two are one clock's view of the same executions; a stamp is taken when
the stamper's thread wakes, so it can lag its program by a thread switch,
and an execution that straddles the window's edge is whole on one side and
cut on the other. Reading a trace never touches the chip
(``JAX_PLATFORMS=cpu`` is the caller's to set).
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

from sentio_tpu.infra.phases import DEVICE_PROGRAMS

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")


# the device plane's program names, by the program the stamps book them under
MODULE_PROGRAMS = (("decode", re.compile(r"^jit_(step_n|spec_tick)")),
                   ("prefill", re.compile(r"^jit_(prior_)?prefill_scatter")),
                   ("admit", re.compile(r"^jit_merge_admitted")),
                   ("encoders", re.compile(r"^jit_fwd")))  # embed and rerank: one name


def _program_of(module: str) -> str:
    return next((p for p, pat in MODULE_PROGRAMS if pat.match(module)), "other")


def _match(stamps: list, runs: dict, lo: float) -> dict:
    """Event for event: each ``decode`` and ``prefill`` stamp beside the
    execution that ended last before it (at most 50 ms before: a stamp lags,
    it never leads). Pairs only, so an execution whose stamp fell outside
    the window is on neither side, and nor is one the window's first
    instant ``lo`` cut: its stamp holds the whole program, the device's row
    only what the window saw of it."""
    out = {}
    for program in ("decode", "prefill"):
        ends = sorted(runs.get(program, ()))
        used, pairs = set(), []
        for _p, ts, ms in sorted(s for s in stamps if s[0] == program):
            if ts - ms * 1e6 < lo:
                continue
            best = None
            for k, (end, run_ms) in enumerate(ends):
                if k not in used and ts - 50e6 <= end <= ts + 1e5:
                    best = k
            if best is not None:
                used.add(best)
                pairs.append((ms, ends[best][1], (ts - ends[best][0]) / 1e6))
        lags = sorted(lag for _a, _b, lag in pairs)
        out[program] = {"pairs": len(pairs),
                        "stamps_ms": round(sum(a for a, _b, _l in pairs), 3),
                        "modules_ms": round(sum(b for _a, b, _l in pairs), 3),
                        "lag_ms_p50": round(lags[len(lags) // 2], 3) if lags else None,
                        "lag_ms_max": round(lags[-1], 3) if lags else None}
    return out


def account(xplane: Path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane))
    stamps: list[tuple] = []  # (program, end ns, ms)
    modules: dict[str, dict] = {}
    runs: dict[str, list] = {}  # program -> [(end ns, ms)] of the device's own executions
    lo, hi = float("inf"), 0.0
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    program = ev.name.removeprefix("device.")
                    if program != ev.name and program in DEVICE_PROGRAMS:
                        stamps.append((program, float(ev.start_ns),
                                       float(dict(ev.stats).get("dur_ms", 0.0))))
        elif DEVICE_PLANE.match(plane.name) and not modules:
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for ev in line.events:
                    name = ev.name.split("(", 1)[0]
                    row = modules.setdefault(name, {"count": 0, "ms": 0.0})
                    row["count"] += 1
                    row["ms"] += float(ev.duration_ns) / 1e6
                    runs.setdefault(_program_of(name), []).append(
                        (float(ev.start_ns) + float(ev.duration_ns), float(ev.duration_ns) / 1e6))
                    lo = min(lo, float(ev.start_ns))
                    hi = max(hi, float(ev.start_ns) + float(ev.duration_ns))
    booked = {p: {"count": 0, "ms": 0.0, "ms_in_window": 0.0} for p in DEVICE_PROGRAMS}
    for program, end_ns, ms in stamps:
        row = booked[program]
        row["count"] += 1
        row["ms"] += ms
        if modules:  # the part of [end - dur, end] inside the device's own window
            row["ms_in_window"] += max(min(end_ns, hi) - max(end_ns - ms * 1e6, lo), 0.0) / 1e6
    by_program = {name: 0.0 for name, _ in MODULE_PROGRAMS} | {"other": 0.0}
    for name, row in modules.items():
        by_program[_program_of(name)] += row["ms"]
    return {"annotations": {p: {k: round(v, 3) for k, v in row.items()}
                            for p, row in booked.items()},
            "matched": _match(stamps, runs, lo),
            "modules": {n: {"count": v["count"], "ms": round(v["ms"], 3)}
                        for n, v in sorted(modules.items(), key=lambda kv: -kv[1]["ms"])},
            "modules_by_program": {p: round(ms, 3) for p, ms in by_program.items()},
            "window_ms": round((hi - lo) / 1e6, 3) if modules else None}


def main(argv: list[str]) -> int:
    path = Path(argv[1])
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        if not found:
            print(json.dumps({"error": f"no *.xplane.pb under {path}"}))
            return 1
        path = found[-1]
    print(json.dumps(account(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
