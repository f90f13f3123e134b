"""The prefill attention of ONE layer alone, timed on the device it finds.

``python -m sentio_tpu.eval.prefill_attn_timing`` times one layer's attention
of a 512-token segment over priors of 2 / 8 / 16 / 32 / 40 pages at the
benchmark's widths — the dense family's (32 query heads over 8 or 4 kv heads
of 128) and the latent family's (128 heads, keys 128 + 64 wide with ONE
rotated key a position, values 128) — twice a point: the flash kernel
(``kernels/prefill_attention.py``) and the XLA form it replaces
(``layers.attention`` behind ``repeat_kv``; ``deepseek_v2.expanded_attention``).
One more point a family holds an 18-page prior in the 32-page program: the
kernel's walk ends at the row's own prior, the XLA form pays the bucket.

The clock is the device's own (a profiler trace of a jitted loop of calls:
the device's busy time a call, and the median ``prefill_attention`` op in
it); on the CPU the host's, a rehearsal of the control flow (``--tiny``) and
never a device number. One JSON line a point, then one summary line; every
line names the device. ``peak_share`` is the attention's own arithmetic —
``2 * T * keys seen * H * (Dqk + Dv)`` with the causal half of the segment's
own block — over the time, against the bf16 peak of the device kind.
"""

from __future__ import annotations

import argparse
import json
import statistics
import tempfile
import time
from pathlib import Path

PAGE, SEGMENT = 128, 512
PRIORS = (2, 8, 16, 32, 40)          # pages: the programs the long mix compiles
SHORT = (18, 32)                     # an 18-page prior in the 32-page program
GEOMETRIES = {
    "mistral": dict(h=32, hkv=8, d=128),
    "yi": dict(h=32, hkv=4, d=128),
    "dsv2": dict(h=128, hkv=128, d=128, r=64, dv=128, scale=0.1147),
}
TINY = {"tiny": dict(h=4, hkv=2, d=16), "tiny_latent": dict(h=4, hkv=4, d=16, r=8, dv=16, scale=0.3)}
PEAK_BF16 = {"TPU v5 lite": 197e12}   # Google Cloud, "TPU v5e"


def device_us(trace_dir: Path, calls: int, kernel_name: str = "prefill_attention") -> dict:
    """The device's busy time a call and the median ``kernel_name`` op of the
    newest trace under ``trace_dir`` (µs); {} without a device plane."""
    from jax.profiler import ProfileData

    found = sorted(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        return {}
    spans, kernel = [], []
    for plane in ProfileData.from_file(str(found[-1])).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                if ev.name.lstrip("%").startswith(kernel_name):
                    kernel.append(ev.duration_ns / 1e3)
    if not spans:
        return {}
    busy, end = 0.0, 0.0
    for lo, hi in sorted(spans):          # union of the op intervals
        busy += max(hi - max(lo, end), 0.0)
        end = max(end, hi)
    out = {"device_us_per_call": busy / 1e3 / calls}
    if kernel:
        out.update(kernel_calls=len(kernel), kernel_median_us=statistics.median(kernel))
    return out


def time_point(name: str, g: dict, pages: int, bucket: int, segment: int, page: int,
               calls: int, trace_root: Path | None, forms=("kernel", "xla")) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from sentio_tpu.kernels.prefill_attention import prefill_attention
    from sentio_tpu.models import layers as L
    from sentio_tpu.models.deepseek_v2 import expanded_attention

    h, hkv, d = g["h"], g["hkv"], g["d"]
    latent = "r" in g
    dv = g.get("dv", d)
    s = bucket * page + segment
    key = jax.random.PRNGKey(pages)
    rnd = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)  # noqa: E731
    q, k = rnd(0, (1, segment, h, d)), rnd(1, (1, s, hkv, d))
    v = jax.random.uniform(jax.random.fold_in(key, 2), (1, s, hkv, dv), jnp.bfloat16, -1.0, 1.0)
    q_pe, k_pe = (rnd(3, (1, segment, h, g["r"])), rnd(4, (1, s, g["r"]))) if latent else (None, None)
    q_start = jnp.asarray([pages * page], jnp.int32)
    scale = g.get("scale", d ** -0.5)
    interpret = jax.default_backend() != "tpu"

    def kernel(q):
        return prefill_attention(q, k, v, q_start, q_pe, k_pe, sm_scale=scale, interpret=interpret)

    def xla(q):
        pos = q_start[:, None] + jnp.arange(segment)[None, :]
        if latent:
            return expanded_attention(q, q_pe, k, k_pe, v, pos, None, scale, jnp.bfloat16).reshape(
                1, segment, h, dv)
        mask = jnp.arange(s)[None, None, None, :] <= pos[:, None, :, None]
        return L.attention(q, L.repeat_kv(k, h // hkv), L.repeat_kv(v, h // hkv), mask, jnp.bfloat16)

    seen = pages * page * segment + segment * (segment + 1) // 2      # (query, key) pairs
    flops = 2 * seen * h * (d + g.get("r", 0) + dv)
    err = float(jnp.abs(kernel(q).astype(jnp.float32) - xla(q).astype(jnp.float32)).max()) \
        if len(forms) > 1 else None
    lines = []
    for form, fn in (("kernel", kernel), ("xla", xla)):
        if form not in forms:
            continue
        @jax.jit
        def chained(q, fn=fn):
            # each call's queries depend on the one before: the calls run in turn
            return jax.lax.fori_loop(0, calls, lambda i, q: q + fn(q)[..., :d] * 0, q)

        chained(q).block_until_ready()
        t0 = time.perf_counter()
        chained(q).block_until_ready()
        line = {"geometry": name, "form": form, "prior_pages": pages, "bucket_pages": bucket,
                "keys": s, "calls": calls, "host_us_per_call": (time.perf_counter() - t0) / calls * 1e6,
                "max_abs_err_kernel_vs_xla": err, "flops": flops}
        if trace_root is not None:
            where = trace_root / f"{name}-{form}-{pages}-{bucket}"
            with jax.profiler.trace(str(where)):
                chained(q).block_until_ready()
            line.update(device_us(where, calls))
        lines.append(line)
    return lines


def main(argv=None) -> int:
    from sentio_tpu.infra.compile_cache import ensure_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes: a rehearsal of the control flow on the CPU")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--only", default="", help="comma-separated geometries")
    ap.add_argument("--forms", default="kernel,xla", help="kernel, xla or both")
    ap.add_argument("--priors", default="", help="comma-separated prior pages in place of the six points")
    args = ap.parse_args(argv)

    ensure_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind, "count": jax.device_count()}
    geometries = TINY if args.tiny else GEOMETRIES
    if args.only:
        geometries = {n: g for n, g in geometries.items() if n in args.only.split(",")}
    segment, page = (32, 8) if args.tiny else (SEGMENT, PAGE)
    points = [(2, 2), (3, 4)] if args.tiny else [(p, p) for p in PRIORS] + [SHORT]
    if args.priors:
        points = [(int(p), int(p)) for p in args.priors.split(",")]
    peak = PEAK_BF16.get(dev.device_kind)
    table = {}
    with tempfile.TemporaryDirectory() as tmp:
        for name, g in geometries.items():
            for pages, bucket in points:
                for line in time_point(name, g, pages, bucket, segment, page, args.calls,
                                       None if args.no_trace else Path(tmp), args.forms.split(",")):
                    us = line.get("device_us_per_call")
                    if us and peak:
                        line["peak_share"] = line["flops"] / (us * 1e-6) / peak
                    print(json.dumps({**line, "device": device}), flush=True)
                    table.setdefault(name, {}).setdefault(f"{pages}/{bucket}", {})[line["form"]] = round(
                        us if us else line["host_us_per_call"], 1)
    print(json.dumps({"ok": True, "device": device, "us_per_call": table,
                      "clock": "host" if args.no_trace or dev.platform == "cpu" else "device_trace"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
