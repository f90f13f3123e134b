"""The decode attention kernel alone, timed on the device it finds.

``python -m sentio_tpu.eval.paged_attn_timing`` times ONE call of the decode
kernel as the engine builds it (``make_paged_attn_impl``; bf16 pages, or
``--int8``; one layer of a 16-layer pool) at the serving geometries of the
benchmark's two cells — or, with ``--tp N``, at one device's share of their
heads under a tp=N mesh (the kernel runs per device inside ``shard_map``) — with
three fills of the same page table: every slot empty (``lens`` 0, tables 0),
what the cells hold, and every table full. The kernel's cost should follow
what the rows hold: the three points say whether it does.

Two clocks a point: the host's around a jitted loop of chained calls
(``block_until_ready``), and the device's own from a profiler trace of that
loop (the median ``paged_attention`` op). One JSON line a point, then one
summary line; every line names the device, and a time from a CPU run is a
rehearsal of the control flow, never a device number (``--tiny`` sizes it
for that).
"""

from __future__ import annotations

import argparse
import json
import statistics
import tempfile
import time
from pathlib import Path

# (slots, pages a row, query heads, kv heads, head dim, page): the cells'
# engines as benchmark/configs/*.json serve them
GEOMETRIES = {
    "mistral": dict(slots=16, nb=18, h=32, hkv=8, d=128, page=128),
    "yi": dict(slots=32, nb=10, h=32, hkv=4, d=128, page=128),
}
# rows holding a request and the blocks each holds, as the cells' ticks do
# (PERF.md §5: 1.9 rows of 1.1k tokens; 11.1 rows of 0.8k)
CELL_FILL = {"mistral": (2, 9), "yi": (11, 7)}
TINY = dict(slots=4, nb=3, h=4, hkv=2, d=16, page=8)


def fills(name: str, g: dict) -> dict:
    """fill → (page_table [slots, nb], lens [slots]) as numpy arrays."""
    import numpy as np

    slots, nb, page = g["slots"], g["nb"], g["page"]
    own = 1 + np.arange(slots * nb, dtype=np.int32).reshape(slots, nb)
    rows, blocks = CELL_FILL.get(name, (1, 2))
    rows, blocks = min(rows, slots), min(blocks, nb)
    held = np.zeros((slots, nb), np.int32)
    held[:rows, :blocks] = own[:rows, :blocks]
    held_lens = np.zeros(slots, np.int32)
    held_lens[:rows] = blocks * page - page // 2  # mid the last held block
    return {
        "empty": (np.zeros((slots, nb), np.int32), np.zeros(slots, np.int32)),
        "cell": (held, held_lens),
        "full": (own, np.full(slots, nb * page - 1, np.int32)),
    }


def _kernel_us(trace_dir: Path) -> dict:
    """Median / mean / calls of the device ops named ``paged_attention*`` in
    the newest trace under ``trace_dir`` (µs); {} where the trace has no
    device plane (a CPU run)."""
    from jax.profiler import ProfileData

    found = sorted(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        return {}
    durations = []
    for plane in ProfileData.from_file(str(found[-1])).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            durations += [ev.duration_ns / 1e3 for ev in line.events
                          if ev.name.lstrip("%").startswith("paged_attention")]
    if not durations:
        return {}
    return {"kernel_calls": len(durations),
            "kernel_median_us": statistics.median(durations),
            "kernel_mean_us": statistics.fmean(durations)}


def time_point(name: str, g: dict, layers: int, calls: int, repeats: int,
               trace_root: Path | None, int8: bool = False) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentio_tpu.kernels.paged_attention import make_paged_attn_impl
    from sentio_tpu.runtime.paged import _paged_attn_xla, quantize_kv

    slots, nb, h, hkv, d, page = (g[k] for k in ("slots", "nb", "h", "hkv", "d", "page"))
    key = jax.random.PRNGKey(0)
    shape = (layers, 1 + slots * nb, page, hkv, d)
    k_pages = jax.random.normal(key, shape, jnp.bfloat16)
    v_pages = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.bfloat16)
    if int8:  # the pool as init_pool keeps it: int8 pages, scales page-minor
        k_pages, v_pages = ({"q": xq, "s": xs.swapaxes(-1, -2)}
                            for xq, xs in map(quantize_kv, (k_pages, v_pages)))
    q0 = jax.random.normal(jax.random.fold_in(key, 2), (slots, 1, h, d), jnp.bfloat16)
    kernel = make_paged_attn_impl()  # as the engine builds it: by backend

    @jax.jit
    def chained(q, k_pages, v_pages, table, lens):
        def body(i, q):
            return kernel(q, k_pages, v_pages, i % layers, table, lens, h // hkv)

        return jax.lax.fori_loop(0, calls, body, q)

    lines = []
    for fill, (table, lens) in fills(name, g).items():
        table, lens = jnp.asarray(table), jnp.asarray(lens)
        try:
            out = chained(q0, k_pages, v_pages, table, lens).block_until_ready()
        except ValueError as refused:  # a geometry the chip's DMA cannot bring
            return [{"geometry": name, "refused": str(refused)}]
        one = kernel(q0, k_pages, v_pages, layers - 1, table, lens, h // hkv)
        ref = _paged_attn_xla(q0, k_pages, v_pages, layers - 1, table, lens, h // hkv)
        host = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            chained(q0, k_pages, v_pages, table, lens).block_until_ready()
            host.append((time.perf_counter() - t0) / calls * 1e6)
        line = {
            "geometry": name, "fill": fill, "calls": calls,
            "blocks_held": int(np.sum(np.asarray(lens) // page + 1)),
            "blocks_tabled": slots * nb,
            "host_us_per_call_min": min(host),
            "host_us_per_call_median": statistics.median(host),
            "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all()),
            "max_abs_err_vs_xla": float(jnp.abs(
                one.astype(jnp.float32) - ref.astype(jnp.float32)).max()),
        }
        if trace_root is not None:
            where = trace_root / f"{name}-{fill}"
            with jax.profiler.trace(str(where)):
                chained(q0, k_pages, v_pages, table, lens).block_until_ready()
            line.update(_kernel_us(where))
        lines.append(line)
    return lines


def main(argv=None) -> int:
    from sentio_tpu.infra.compile_cache import ensure_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes: a rehearsal of the control flow on the CPU")
    ap.add_argument("--calls", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--int8", action="store_true",
                    help="int8 pages and their scales (KV_QUANT=int8) instead of bf16")
    ap.add_argument("--tp", type=int, default=1,
                    help="time one device's share of the heads under a tp=N mesh")
    args = ap.parse_args(argv)

    ensure_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind,
              "count": jax.device_count()}
    geometries = {"tiny": TINY} if args.tiny else {
        name: {**g, "h": g["h"] // args.tp, "hkv": g["hkv"] // args.tp}
        for name, g in GEOMETRIES.items()}
    layers = 2 if args.tiny else args.layers
    table = {}
    with tempfile.TemporaryDirectory() as tmp:
        for name, g in geometries.items():
            for line in time_point(name, g, layers, args.calls, args.repeats,
                                   None if args.no_trace else Path(tmp), args.int8):
                print(json.dumps({**line, "device": device}), flush=True)
                if "refused" in line:
                    table[name] = line["refused"]
                    continue
                table.setdefault(name, {})[line["fill"]] = round(
                    line.get("kernel_median_us", line["host_us_per_call_median"]), 2)
    print(json.dumps({"ok": True, "device": device, "us_per_call": table,
                      "pages": "int8" if args.int8 else "bf16", "tp": args.tp,
                      "clock": "host" if args.no_trace or dev.platform == "cpu"
                      else "device_trace"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
