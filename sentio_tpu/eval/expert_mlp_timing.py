"""The grouped expert matmuls of ONE routed layer alone, timed on the device it finds.

``python -m sentio_tpu.eval.expert_mlp_timing`` times the megablox ``gmm``
call (``models/moe.py::expert_matmul``) at the two shapes a routed layer
holds — ``w_gate`` / ``w_up`` ``[E, D, F]`` and ``w_down`` ``[E, F, D]`` —
under two loads: a DECODE step (``slots`` rows of which ``advancing`` route
their picks, so few pairs touch some of the held experts) and a PREFILL
segment (512 tokens, every held expert touched), over candidate tiles
``(rows, tk, tn)``: the contraction whole, halved and as the old constant
cut it, every 128-lane divisor of N that makes a tile worth a DMA, the row
tile the engine picks (``--rows`` for others). A candidate the chip's
compiler refuses (VMEM) is printed as ``refused``. ``rule`` marks the tile
``models/moe.py::expert_tile`` picks, ``parent`` the ``(4096, 512)`` cut to
the matrix that every family had until PR 43.

The widths are the benchmark's three routed configurations' by default;
``--widths name:held:router:picks:dim:mlp:slots:advancing`` brings others.
The clock is the device's own (a profiler trace of a jitted loop of calls:
the median ``gmm`` op in it); on the CPU the host's, a rehearsal of the
control flow (``--tiny``, interpret mode) and never a device number. One
JSON line a point, then one summary line; every line names the device.
``gbps`` is the touched experts' matrices over the time.
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

SEGMENT = 512
# held experts, the router's experts, picks a token, hidden and expert width,
# decode slots and the rows of them that advance in the benchmark's cells
GEOMETRIES = {
    "commanda": dict(held=16, router=128, picks=8, dim=4096, mlp=4096, slots=32, advancing=10),
    "dsv2": dict(held=20, router=160, picks=6, dim=5120, mlp=1536, slots=8, advancing=2),
    "lfm2": dict(held=64, router=64, picks=4, dim=2048, mlp=1536, slots=16, advancing=10),
}
TINY = {"tiny": dict(held=4, router=8, picks=2, dim=256, mlp=384, slots=8, advancing=4)}
PARENT_TILE = (4096, 512)


def candidates(k: int, n: int, rows: int) -> list[tuple[int, int]]:
    """The ``(tk, tn)`` worth a compile at a ``[K, N]`` matrix: K whole, its
    half and the parent's cut; every 128-lane divisor of N; less those under
    a sixteenth of the matrix (too small to stream) or a quarter over the
    VMEM a kernel has (the compiler would refuse them unasked)."""
    from sentio_tpu.models.moe import _GMM_VMEM, tile_vmem

    tks = {k, min(PARENT_TILE[0], k)} | ({k // 2} if k % 256 == 0 else set())
    tns = {d for d in range(128, n + 1, 128) if n % d == 0} | {min(PARENT_TILE[1], n), n}
    return sorted((tk, tn) for tk in tks for tn in tns
                  if tk * tn * 16 >= k * n and tile_vmem(rows, tk, tn) <= 1.25 * _GMM_VMEM)


def load(g: dict, tokens: int, routing: int, seed: int):
    """Group sizes ``[held]`` of a call whose ``routing`` tokens send their
    share of picks to the held experts, evenly at random, and the row tile
    the engine picks for a program of ``tokens`` tokens."""
    import numpy as np

    from sentio_tpu.models.moe import row_tile

    pairs = round(routing * g["picks"] * g["held"] / g["router"])
    sizes = np.bincount(np.random.default_rng(seed).integers(0, g["held"], pairs), minlength=g["held"])
    return sizes.astype(np.int32), row_tile(tokens * g["picks"], g["router"])


def time_point(k: int, n: int, held: int, m: int, sizes, tile: tuple, calls: int,
               trace_dir: Path | None) -> dict:
    """One candidate: ``calls`` chained ``gmm`` calls of ``[m, k] x [held, k, n]``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from sentio_tpu.eval.prefill_attn_timing import device_us

    key = jax.random.PRNGKey(k + n)
    lhs = jax.random.normal(key, (m, k), jnp.bfloat16)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (held, k, n), jnp.bfloat16) * k ** -0.5
    sizes = jnp.asarray(sizes)
    live = (jnp.arange(m) < int(sizes.sum()))[:, None]
    interpret = jax.default_backend() != "tpu"

    @jax.jit
    def chained(lhs, rhs, sizes):   # (the matrices an argument: closed over, they would be compiled in)
        def body(_, lhs):   # each call's rows depend on the one before: the calls run in turn
            out = gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=tile, interpret=interpret)
            return lhs + jnp.where(live, out, 0)[:, :1] * 0
        return jax.lax.fori_loop(0, calls, body, lhs)

    try:
        chained(lhs, rhs, sizes).block_until_ready()
    except Exception as exc:  # noqa: BLE001 — whatever the chip's compiler refuses is the finding
        return {"status": "refused", "why": str(exc).strip().splitlines()[0][:200]}
    t0 = time.perf_counter()
    chained(lhs, rhs, sizes).block_until_ready()
    line = {"status": "ok", "host_us_per_call": (time.perf_counter() - t0) / calls * 1e6}
    if trace_dir is not None:
        with jax.profiler.trace(str(trace_dir)):
            chained(lhs, rhs, sizes).block_until_ready()
        line.update(device_us(trace_dir, calls, "gmm"))
    return line


def main(argv=None) -> int:
    from sentio_tpu.infra.compile_cache import ensure_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes: a rehearsal of the control flow on the CPU")
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--only", default="", help="comma-separated geometries")
    ap.add_argument("--loads", default="decode,prefill")
    ap.add_argument("--rows", default="", help="comma-separated row tiles beside the engine's own")
    ap.add_argument("--widths", action="append", default=[],
                    help="name:held:router:picks:dim:mlp:slots:advancing, once a geometry")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    ensure_compile_cache()
    import jax

    from sentio_tpu.models.moe import expert_tile, tile_vmem

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind, "count": jax.device_count()}
    geometries = dict(TINY if args.tiny else GEOMETRIES)
    for spec in args.widths:
        name, *numbers = spec.split(":")
        geometries[name] = dict(zip(GEOMETRIES["lfm2"], map(int, numbers), strict=True))
    if args.only:
        geometries = {name: g for name, g in geometries.items() if name in args.only.split(",")}
    segment = 32 if args.tiny else SEGMENT
    clock = "host" if args.no_trace or dev.platform == "cpu" else "device_trace"
    table = {}
    with tempfile.TemporaryDirectory() as tmp:
        for name, g in geometries.items():
            for kind in args.loads.split(","):
                tokens, routing = (g["slots"], g["advancing"]) if kind == "decode" else (segment, segment)
                sizes, own_rows = load(g, tokens, routing, args.seed)
                touched = int((sizes > 0).sum())
                for rows in sorted({own_rows, *(int(r) for r in args.rows.split(",") if r)}):
                    m = -(-tokens * g["picks"] // rows) * rows
                    for matrix, (k, n) in (("up", (g["dim"], g["mlp"])), ("down", (g["mlp"], g["dim"]))):
                        for n_point, (tk, tn) in enumerate(candidates(k, n, rows)):
                            where = None if args.no_trace else Path(tmp) / f"{name}-{kind}-{matrix}-{rows}-{n_point}"
                            line = time_point(k, n, g["held"], m, sizes, (rows, tk, tn), args.calls, where)
                            us = line.get("kernel_median_us") or line.get("host_us_per_call")
                            line.update(
                                geometry=name, load=kind, matrix=matrix, k=k, n=n, tile=[rows, tk, tn],
                                vmem_mib=round(tile_vmem(rows, tk, tn) / 2 ** 20, 2),
                                steps_per_expert=-(-k // tk) * -(-n // tn), pairs=int(sizes.sum()), touched=touched,
                                rule=(tk, tn) == expert_tile(k, n, rows),
                                parent=(tk, tn) == (min(PARENT_TILE[0], k), min(PARENT_TILE[1], n)))
                            if us:
                                line["gbps"] = touched * k * n * 2 / us / 1e3
                            print(json.dumps({**line, "device": device}), flush=True)
                            mark = "".join(c for c, on in (("*", line["rule"]), ("p", line["parent"])) if on)
                            table.setdefault(f"{name}.{kind}.{matrix} {k}x{n}", {})[f"{rows}x{tk}x{tn}{mark}"] = (
                                round(us, 1) if us else line["status"])
    print(json.dumps({"ok": True, "device": device, "clock": clock, "us_per_call": table,
                      "marks": "* the rule's tile, p the parent's"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
