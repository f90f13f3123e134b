"""From a profiler trace (``*.xplane.pb``) to numbers.

``python benchmark/trace.py <dir-or-file> [--block JSON]`` prints one JSON
object; the block is a configuration's ``trace`` block as ``kernel_block``
reads it. The harness runs it as a child with
``JAX_PLATFORMS=cpu`` so that reading a trace can never touch the chip.

What a trace written by ``GET /debug/profile`` on a TPU holds (looked at by
hand on the first chip run of PR 24): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per program
execution (``jit_step_n(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per executed HLO operation, named by its whole instruction text
(``%paged_attention.188 = bf16[...] custom-call(...)`` is the Pallas decode
kernel, ``%while.7 = ...`` the loop that CONTAINS the sub-steps' operations);
and ``/host:CPU``, one line per thread, holding the profiler's Python frames
(``$service.py:428 _generate_stream_impl``) and runtime spans
(``np.asarray(jax.Array)``). The pump's ``StepTraceAnnotation`` would show as
``decode_tick`` events, but the program only writes it when OpenTelemetry is
installed, and it is not here. On the CPU (the rehearsal) there is no device
plane: executed operations appear on host threads with an ``hlo_module``
stat, and are taken for the device's.

Reduction:

* busy: the union of the intervals in which an operation ran on a device,
  averaged over the devices; window: first to last event of the trace;
* programs: executions, total and median device time per program name;
  for a program whose executions hold a loop of sub-steps, the sub-steps
  are counted from the kernel events inside each execution (the block's
  ``substep_kernel`` pattern, ``calls_per_substep`` kernel calls to a
  sub-step), over the executions that lie whole inside the trace; and for
  each kernel the block names (``kernels``: name → pattern) its calls, their
  total and their median time inside the program's executions;
* breakdown: the device operations that took most time, grouped by
  operation name without its number (loops that contain other operations
  left out), and the longest idle gaps, each labelled with the innermost
  host span that covered most of it.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host events that say nothing about what the host was doing
HOST_NOISE = ("ThreadpoolListener", "$threading.py", "$queue.py", "$selectors.py",
              "$base_events.py", "$<unknown>", "$sys ", "$thread.py", "$runners.py",
              "$profiler.py", "$events.py", "$contextlib.py")
# operations that only contain other operations' time
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(text: str) -> str:
    """``%fusion.1337 = (bf16[...]) fusion(...)`` → ``fusion.1337``."""
    return text.split(" = ", 1)[0].lstrip("%")[:80]


def op_group(name: str) -> str:
    """``slice_bitcast_fusion.92.remat`` → ``slice_bitcast_fusion``."""
    return re.sub(r"(\.\d+|\.remat\d*|\.clone)+$", "", name)


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def load_events(xplane: Path) -> dict:
    """→ {"devices": {plane: {"ops": [(name, start, dur)], "modules": [...]}},
    "host": [(name, start, dur, line)]} with times in nanoseconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane))
    devices: dict[str, dict] = {}
    host: list[tuple] = []
    cpu_ops: list[tuple] = []
    cpu_modules: dict[tuple, list] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            slot = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                key = "ops" if line.name == OPS_LINE else "modules"
                short = op_name if key == "ops" else (lambda text: text)
                slot[key] += [(short(ev.name), float(ev.start_ns), float(ev.duration_ns))
                              for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    stats = dict(ev.stats)
                    start, dur = float(ev.start_ns), float(ev.duration_ns)
                    if "hlo_module" in stats:  # CPU backend: an executed op
                        cpu_ops.append((op_name(ev.name), start, dur))
                        cpu_modules.setdefault(
                            (stats["hlo_module"], stats.get("run_id")), []).append((start, start + dur))
                    elif dur > 0 and not ev.name.startswith(HOST_NOISE):
                        host.append((ev.name, start, dur, line.name))
    if not devices and cpu_ops:
        modules = [(name, min(s for s, _ in spans), max(e for _, e in spans) - min(s for s, _ in spans))
                   for (name, _run), spans in cpu_modules.items()]
        devices["/host:CPU (no device plane: CPU backend)"] = {"ops": cpu_ops, "modules": modules}
    return {"devices": devices, "host": host}


def union_length(spans: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of [start, end) spans, and the gaps between
    its pieces."""
    total, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(spans):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def program_name(event_name: str) -> str:
    """``jit_step_n(1234567)`` → ``jit_step_n``."""
    return event_name.split("(", 1)[0]


def _median(values: list[float]) -> float:
    ordered = sorted(values)
    mid = len(ordered) // 2
    return ordered[mid] if len(ordered) % 2 else (ordered[mid - 1] + ordered[mid]) / 2


def label_gap(gap: tuple[float, float], host: list[tuple]) -> str:
    """The innermost host span covering at least half of the gap (the
    shortest such event says most precisely what the host was doing); if
    none does, the span overlapping it longest."""
    gs, ge = gap
    inner, inner_dur = None, float("inf")
    best, best_cover = "host: nothing recorded", 0.0
    for name, start, dur, _line in host:
        cover = min(ge, start + dur) - max(gs, start)
        if cover <= 0:
            continue
        if cover >= 0.5 * (ge - gs) and dur < inner_dur:
            inner, inner_dur = name, dur
        if cover > best_cover:
            best, best_cover = name, cover
    return inner or best


def kernel_block(config: dict) -> dict:
    """A configuration's ``trace`` block with its defaults: ``substep_kernel``
    (the pattern of the kernel that counts sub-steps), ``calls_per_substep``
    (that kernel's calls to one sub-step: one a layer unless the file says
    otherwise — a model whose layers are not all of one kind says so) and
    ``kernels`` (name → pattern: those reported one by one)."""
    block = config.get("trace", {})
    return {"substep_kernel": block.get("substep_kernel", ""),
            "calls_per_substep": int(block.get("calls_per_substep",
                                               config.get("num_hidden_layers", 0))),
            "kernels": dict(block.get("kernels", {}))}


def reduce_events(events: dict, block: dict | None = None) -> dict:
    block = block or {}
    per_step, kernel = int(block.get("calls_per_substep", 0)), block.get("substep_kernel", "")
    devices, host = events["devices"], events["host"]
    all_spans = [(s, s + d) for dev in devices.values() for _n, s, d in dev["ops"]]
    if not all_spans:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "programs": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    t_lo = min(s for s, _ in all_spans)
    t_hi = max(e for _, e in all_spans)
    busy, gaps = [], []
    for dev in devices.values():
        total, dev_gaps = union_length([(s, s + d) for _n, s, d in dev["ops"]])
        busy.append(total)
        gaps += dev_gaps
    first = next(iter(devices.values()))

    programs: dict[str, dict] = {}

    def calls_of(pattern: str) -> tuple[list, list]:
        calls = sorted((s, d) for n, s, d in first["ops"] if re.search(pattern, n))
        return [s for s, _d in calls], [d for _s, d in calls]

    kernel_starts = calls_of(kernel)[0] if kernel else []
    named = {kname: calls_of(pattern) for kname, pattern in block.get("kernels", {}).items()}
    for name, start, dur in first["modules"]:
        prog = programs.setdefault(program_name(name),
                                   {"durations_ms": [], "stepped": [], "kernel_ns": {}})
        prog["durations_ms"].append(dur / 1e6)
        whole = start > t_lo and start + dur < t_hi
        if kernel_starts and per_step and whole:
            lo, hi = bisect.bisect_left(kernel_starts, start), bisect.bisect_left(kernel_starts, start + dur)
            if hi > lo and (hi - lo) % per_step == 0:
                prog["stepped"].append((dur / 1e6, (hi - lo) // per_step))
        for kname, (starts, durs) in named.items():
            lo, hi = bisect.bisect_left(starts, start), bisect.bisect_left(starts, start + dur)
            prog["kernel_ns"].setdefault(kname, []).extend(durs[lo:hi])
    for prog in programs.values():
        kernel_ns = prog.pop("kernel_ns")
        durations = prog.pop("durations_ms")
        prog["count"] = len(durations)
        prog["total_ms"] = sum(durations)
        prog["p50_ms"] = _median(durations)
        stepped = prog.pop("stepped")
        if stepped:
            prog["sub_steps"] = sum(n for _d, n in stepped)
            prog["sub_steps_ms"] = sum(d for d, _n in stepped)
        kernels = {kname: {"calls": len(ns), "total_ms": sum(ns) / 1e6, "p50_us": _median(ns) / 1e3}
                   for kname, ns in kernel_ns.items() if ns}
        if kernels:
            prog["kernels"] = kernels

    op_totals: dict[str, float] = {}
    for name, _s, dur in first["ops"]:
        if not CONTAINERS.match(name):
            op_totals[op_group(name)] = op_totals.get(op_group(name), 0.0) + dur
    top_ops = sorted(op_totals.items(), key=lambda kv: -kv[1])[:10]
    gap_totals: dict[str, float] = {}
    for gap in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        label = label_gap(gap, host)
        gap_totals[label] = gap_totals.get(label, 0.0) + (gap[1] - gap[0])
    top_gaps = sorted(gap_totals.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": len(devices),
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (t_hi - t_lo) / 1e9,
        "programs": programs,
        "breakdown": {
            "device_ops": [[n, d / 1e9] for n, d in top_ops],
            "idle_gaps": [[n, d / 1e9] for n, d in top_gaps],
        },
    }


def cut_events(events: dict, start_ms: float, length_ms: float, min_op_us: float) -> dict:
    """A slice of a trace small enough to commit as a test fixture: the
    events that START inside [start, start + length) after the first device
    operation, clipped at its end, device operations and host spans shorter
    than ``min_op_us`` dropped, times rebased to the slice."""
    first = next(iter(events["devices"].values()))
    t_lo = min(s for _n, s, _d in first["ops"]) + start_ms * 1e6
    t_hi = t_lo + length_ms * 1e6
    keep = lambda s: t_lo <= s < t_hi  # noqa: E731
    clip = lambda s, d: min(d, t_hi - s)  # noqa: E731
    devices = {
        plane: {"ops": [(n, s - t_lo, clip(s, d)) for n, s, d in dev["ops"]
                        if keep(s) and d >= min_op_us * 1e3],
                "modules": [(n, s - t_lo, clip(s, d)) for n, s, d in dev["modules"] if keep(s)]}
        for plane, dev in events["devices"].items()}
    host = [(n, s - t_lo, clip(s, d), line) for n, s, d, line in events["host"]
            if keep(s) and d >= min_op_us * 1e3]
    return {"devices": devices, "host": host}


def reduce_xplane(path: Path, block: dict | None = None) -> dict:
    return reduce_events(load_events(find_xplane(path)), block)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("path", type=Path)
    parser.add_argument("--block", type=json.loads, default={},
                        help='a configuration\'s trace block as JSON: {"substep_kernel": PATTERN, '
                             '"calls_per_substep": N, "kernels": {NAME: PATTERN}}')
    parser.add_argument("--dump", type=int, default=0,
                        help="print the first N events of every line instead (looking by hand)")
    parser.add_argument("--cut", default="",
                        help="START_MS,LENGTH_MS,MIN_OP_US: print that slice's events as JSON (a fixture)")
    args = parser.parse_args()
    if args.cut:
        start, length, min_us = (float(x) for x in args.cut.split(","))
        print(json.dumps(cut_events(load_events(find_xplane(args.path)), start, length, min_us)))
        return 0
    if args.dump:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(find_xplane(args.path)))
        for plane in data.planes:
            print("PLANE", plane.name)
            for line in plane.lines:
                events = list(line.events)
                print("  LINE", line.name, len(events))
                for ev in events[: args.dump]:
                    print("     ", ev.name[:90], ev.start_ns, ev.duration_ns, dict(ev.stats))
        return 0
    print(json.dumps(reduce_xplane(args.path, args.block)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
