"""The one-token Mamba-2 state update of ONE block alone, timed on the device it finds.

``python -m sentio_tpu.eval.ssm_update_timing`` times the decode update of
``state["ssm"]`` ``[Lm, B, H, P, N]`` float32 at the nemotron cell's widths
(6 x 16 x 64 x 64 x 128) with 16, 10, 1 and 0 of the 16 rows advancing, in two
forms: the kernel (``kernels/ssm_update.py``) and the XLA form it replaces
(``models/nemotron_h.py::mamba_step``'s arithmetic and the masked ``.at[j].set``
of ``runtime/paged.py::paged_decode_forward``). The calls are chained through
the donated state inside one jitted loop, a block after the other, as a
decode sub-step visits them. The clock is the device's own (a profiler trace:
the device's busy time a call, and the median ``ssm_update`` op); on the CPU
the host's, a rehearsal of the control flow (``--tiny``, interpret mode) and
never a device number. ``gbps`` is the ADVANCING rows' state, read once and
written once, over the time a call. One JSON line a point, then a summary
line; every line names the device. The kernel's new state and ``y`` are
compared with the XLA form's at every point (``max_rel_err``).
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

GEOMETRY = dict(layers=6, slots=16, heads=64, head_dim=64, state=128, groups=8)
TINY = dict(layers=2, slots=4, heads=4, head_dim=8, state=128, groups=2)


def xla_update(state, layer, advancing, decay, xdt, bmat, cmat):
    """The form the kernel replaces, as ``mamba_step`` and ``paged_decode_forward`` write it."""
    import jax.numpy as jnp

    rep = state.shape[2] // bmat.shape[1]
    bmat, cmat = (jnp.repeat(m, rep, axis=1) for m in (bmat, cmat))
    ssm = state[layer] * decay[..., None, None] + xdt[..., None] * bmat[:, :, None, :]
    y = jnp.where(advancing[:, None, None], jnp.einsum("bhpn,bhn->bhp", ssm, cmat), 0.0)   # (the kernel's zero)
    return state.at[layer].set(jnp.where(advancing[:, None, None, None], ssm, state[layer])), y


def time_point(g: dict, advancing: int, form: str, calls: int, trace_dir: Path | None, seed: int) -> tuple:
    """``calls`` chained updates, block after block → (the line, the state and y sum after them)."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.eval.prefill_attn_timing import device_us
    from sentio_tpu.kernels.ssm_update import make_ssm_update_impl

    layers, rows, heads, p, n, groups = (g[k] for k in ("layers", "slots", "heads", "head_dim", "state", "groups"))
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    decay = jax.random.uniform(keys[1], (rows, heads), jnp.float32, 0.5, 1.0)
    xdt = jax.random.normal(keys[2], (rows, heads, p), jnp.float32)
    bmat, cmat = (jax.random.normal(k, (rows, groups, n), jnp.float32) for k in keys[3:])
    # the advancing rows spread over the slots, as a closed cell's halted rows are
    mask = jnp.zeros((rows,), bool).at[(jnp.arange(advancing) * rows) // max(advancing, 1)].set(advancing > 0)
    update = make_ssm_update_impl() if form == "kernel" else xla_update

    def chained(state, mask, decay, xdt, bmat, cmat):
        def body(i, carry):
            state, acc = carry
            state, y = update(state, i % layers, mask, decay, xdt, bmat, cmat)
            return state, acc + y
        return jax.lax.fori_loop(0, calls, body, (state, jnp.zeros((rows, heads, p), jnp.float32)))

    run = jax.jit(chained, donate_argnums=0)

    def fresh():
        return jax.random.normal(keys[0], (layers, rows, heads, p, n), jnp.float32)

    out = jax.block_until_ready(run(fresh(), mask, decay, xdt, bmat, cmat))
    state = fresh()
    t0 = time.perf_counter()
    jax.block_until_ready(run(state, mask, decay, xdt, bmat, cmat))
    line = {"host_us_per_call": (time.perf_counter() - t0) / calls * 1e6}
    if trace_dir is not None:
        state = fresh()
        with jax.profiler.trace(str(trace_dir)):
            jax.block_until_ready(run(state, mask, decay, xdt, bmat, cmat))
        line.update(device_us(trace_dir, calls, "ssm_update"))
    return line, out


def main(argv=None) -> int:
    from sentio_tpu.infra.compile_cache import ensure_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true", help="toy shapes: a rehearsal of the control flow on the CPU")
    ap.add_argument("--calls", type=int, default=48)
    ap.add_argument("--advancing", default="", help="comma-separated counts of advancing rows")
    ap.add_argument("--forms", default="kernel,xla")
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    ensure_compile_cache()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind, "count": jax.device_count()}
    g = TINY if args.tiny else GEOMETRY
    counts = [int(c) for c in args.advancing.split(",") if c] or sorted({g["slots"], g["slots"] * 5 // 8, 1, 0},
                                                                       reverse=True)
    row_bytes = 2 * 4 * g["heads"] * g["head_dim"] * g["state"]
    clock = "host" if args.no_trace or dev.platform == "cpu" else "device_trace"
    table = {}
    with tempfile.TemporaryDirectory() as tmp:
        for advancing in counts:
            outs = {}
            for form in args.forms.split(","):
                where = None if args.no_trace else Path(tmp) / f"{form}-{advancing}"
                line, outs[form] = time_point(g, advancing, form, args.calls, where, args.seed)
                us = line.get("device_us_per_call") or line["host_us_per_call"]
                line.update(form=form, advancing=advancing, slots=g["slots"], gbps=advancing * row_bytes / us / 1e3)
                if form == "kernel" and "xla" in outs or form == "xla" and "kernel" in outs:
                    line["max_rel_err"] = max(
                        float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))
                        for a, b in zip(outs["kernel"], outs["xla"]))
                print(json.dumps({**line, "device": device}), flush=True)
                table.setdefault(form, {})[str(advancing)] = round(us, 1)
    print(json.dumps({"ok": True, "device": device, "clock": clock, "geometry": g, "us_per_call": table}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
