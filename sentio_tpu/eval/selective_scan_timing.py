"""The Mamba-1 selective scan of ONE layer over one prefill segment, timed on the device it finds.

``python -m sentio_tpu.eval.selective_scan_timing`` times the recurrence of
``models/jamba.py`` — ``S_t = exp(D_t (x) A) * S_{t-1} + (D_t x_t) (x) B_t``,
``y_t = S_t C_t`` over ``[N, inner]`` float32 a token — for one row of 512
tokens at the published widths of ``ai21-jamba2-3b`` (inner 5120, state 16),
in the plain compiled forms a builder can choose between:

* ``block<k>``: ``models/jamba.py::selective_scan`` with ``k`` tokens an
  iteration of its loop, their steps unrolled (``block1``: a step an
  iteration; ``block32`` is what the model runs, ``SCAN_BLOCK``);
* ``kernel``: ``kernels/selective_scan.py``, one call a layer (what a serving
  TPU runs), and ``segmentk``: the whole mixer over it;
* ``assoc<k>``: ``lax.associative_scan`` over blocks of ``k`` tokens inside a
  loop over the blocks — the pairs ``(exp(D_t (x) A), (D_t x_t) (x) B_t)`` of a
  block ARE ``[k, N, inner]`` tensors, which is what this form costs;
* ``segment<k>``: the whole mixer (``mamba1_segment``: projections,
  convolution, the three inner norms, the scan in blocks of ``k``, the gate,
  ``W_out``).

``--layers`` chains that many calls inside one jitted loop, each from the
state the last left. The clock is the device's own (a profiler trace: the
device's busy time a call); on the CPU the host's, a rehearsal of the control
flow (``--tiny``) and never a device number. Every form's ``y`` and last state
are compared with ``block1``'s (``max_rel_err``). One JSON line a form, then a
summary line; every line names the device.
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

GEOMETRY = dict(rows=1, tokens=512, inner=5120, state=16, dim=2560)
TINY = dict(rows=2, tokens=64, inner=128, state=8, dim=64)


def assoc_scan(x, step, a, bmat, cmat, start, block: int):
    """The recurrence by ``lax.associative_scan`` a block: the pairs ``(decay,
    added)`` compose as ``(a2 a1, a2 b1 + b2)``; the state carried over the
    blocks by the loop. → (y [B, T, inner], the last state)."""
    import jax
    import jax.numpy as jnp

    b, t, inner = x.shape

    def blocks(v):  # [B, T, ...] → [T / block, block, B, ...]
        return jnp.moveaxis(v.reshape(b, t // block, block, *v.shape[2:]), 0, 2)

    def body(s, blk):
        dl, dx, bm, cm = blk
        decay = jnp.exp(dl[:, :, None, :] * a)                              # [k, B, N, inner]
        added = dx[:, :, None, :] * bm[..., None]
        decay, added = jax.lax.associative_scan(
            lambda lo, hi: (hi[0] * lo[0], hi[0] * lo[1] + hi[1]), (decay, added))
        states = decay * s + added
        return states[-1], jnp.sum(states * cm[..., None], axis=2)

    last, y = jax.lax.scan(body, start, (blocks(step), blocks(step * x), blocks(bmat), blocks(cmat)))
    return jnp.moveaxis(y, 2, 0).reshape(b, t, inner), last


def time_form(g: dict, form: str, layers: int, trace_dir: Path | None, seed: int) -> tuple:
    """``layers`` chained calls of one form → (the line, (y of the last call, its last state))."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from sentio_tpu.eval.prefill_attn_timing import device_us
    from sentio_tpu.kernels.selective_scan import selective_scan_kernel
    from sentio_tpu.models import jamba as M

    pallas = "pallas" if jax.default_backend() == "tpu" else "interpret"

    rows, t, inner, n = g["rows"], g["tokens"], g["inner"], g["state"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(keys[0], (rows, t, inner), jnp.float32)
    step = jnp.exp(jax.random.uniform(keys[1], (rows, t, inner), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, inner))
    bmat, cmat = (jax.random.normal(k, (rows, t, n), jnp.float32) for k in keys[2:4])
    start = jax.random.normal(keys[4], (rows, n, inner), jnp.float32)

    if form.startswith("segment"):
        cfg = dataclasses.replace(M.JambaConfig(), dim=g["dim"], mamba_d_state=n, mamba_expand=inner // g["dim"],
                                  mamba_dt_rank=max(g["dim"] // 16, 8), n_layers=1, attn_layer_period=2,
                                  attn_layer_offset=1, vocab_size=512, mlp_dim=64, n_heads=max(g["dim"] // 128, 1))
        tree = jax.tree.map(lambda w: w.astype(jnp.bfloat16) if w.ndim == 2 and w.shape[0] > 16 and w.shape[1] > 4
                            else w, M.init_jamba(keys[5], cfg)["layers_0"]["mamba"])
        u = jax.random.normal(keys[6], (rows, t, cfg.dim), jnp.bfloat16)
        conv = jnp.zeros((rows, cfg.conv_taps, inner), jnp.bfloat16)

        def one(s, block=form[7:]):
            was = M.SCAN_BLOCK, M.SCAN_FORM            # (both read when the segment is traced)
            M.SCAN_BLOCK, M.SCAN_FORM = (was[0], pallas) if block == "k" else (int(block), "xla")
            try:
                out, after, _ = M.mamba1_segment(tree, cfg, u, {"conv": conv, "ssm": s}, None)
            finally:
                M.SCAN_BLOCK, M.SCAN_FORM = was
            return out.astype(jnp.float32), after["ssm"]
    elif form == "kernel":
        def one(s):
            y, states = selective_scan_kernel(x, step, a, bmat, cmat, s, snap=M.SNAP_TOKENS,
                                              interpret=pallas == "interpret")
            return y, states[:, -1]
    elif form.startswith("assoc"):
        def one(s):
            return assoc_scan(x, step, a, bmat, cmat, s, int(form[5:]))
    else:
        def one(s):
            y, last, _ = M.selective_scan(x, step, a, bmat, cmat, s, block=int(form[5:]))
            return y, last

    def chained(s):
        def body(_, carry):
            s, _y = carry
            y, s = one(s)
            return s, y
        return jax.lax.fori_loop(0, layers, body, (s, jnp.zeros(jax.eval_shape(one, s)[0].shape, jnp.float32)))

    run = jax.jit(chained)
    out = jax.block_until_ready(run(start))
    t0 = time.perf_counter()
    jax.block_until_ready(run(start))
    line = {"host_us_per_call": (time.perf_counter() - t0) / layers * 1e6}
    if trace_dir is not None:
        with jax.profiler.trace(str(trace_dir)):
            jax.block_until_ready(run(start))
        line.update(device_us(trace_dir, layers, "selective_scan" if form in ("kernel", "segmentk") else "while"))
    return line, (out[1], out[0])


def main(argv=None) -> int:
    from sentio_tpu.infra.compile_cache import ensure_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true", help="toy shapes: a rehearsal of the control flow on the CPU")
    ap.add_argument("--layers", type=int, default=26, help="chained calls: the Mamba layers of the published model")
    ap.add_argument("--forms", default="block1,block16,block32,block128,kernel,assoc16,assoc64,segment16,segment32,segmentk")
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind, "count": jax.device_count()}
    g = TINY if args.tiny else GEOMETRY
    clock = "host" if args.no_trace or dev.platform == "cpu" else "device_trace"
    table, first = {}, None
    with tempfile.TemporaryDirectory() as tmp:
        for form in args.forms.split(","):
            where = None if args.no_trace else Path(tmp) / form
            line, out = time_form(g, form, args.layers, where, args.seed)
            us = line.get("device_us_per_call") or line["host_us_per_call"]
            line.update(form=form, **g, state_steps_per_us=g["rows"] * g["tokens"] * g["inner"] * g["state"] / us)
            if not form.startswith("segment"):
                first = first or out
                line["max_rel_err"] = max(float(jnp.max(jnp.abs(p - q)) / jnp.maximum(jnp.max(jnp.abs(q)), 1e-30))
                                          for p, q in zip(out, first))
            print(json.dumps({**line, "device": device}), flush=True)
            table[form] = round(us, 1)
    print(json.dumps({"ok": True, "device": device, "clock": clock, "geometry": g, "layers": args.layers,
                      "us_per_call": table}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
