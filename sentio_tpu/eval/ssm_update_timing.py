"""The one-token state update of a state-space family's Mamba layers alone, timed on the device it finds.

``python -m sentio_tpu.eval.ssm_update_timing [--recurrence mamba2|mamba1]``
times the decode update of ``state["ssm"]`` in two forms: the kernel
(``kernels/ssm_update.py``) and the XLA form it replaces (the model's own
arithmetic — ``models/nemotron_h.py::mamba_step``'s, ``models/jamba.py::
mamba1_step``'s — and the masked ``.at[j].set`` of ``runtime/paged.py::
paged_decode_forward``):

* ``mamba2`` (the default): ``[Lm, B, H, P, N]`` float32 at the nemotron
  cell's widths (6 x 16 x 64 x 64 x 128), 16, 10, 1 and 0 of the 16 rows
  advancing;
* ``mamba1``: ``[Lm, B, N, inner]`` float32 at the jamba cell's (26 x 8 x 16 x
  5120), 8, 4, 2, 1 and 0 of the 8 rows advancing.

The calls are chained through the donated state inside one jitted loop, a
layer after the other, as a decode sub-step visits them (``--calls``, by
default eight sub-steps' worth; the layer is TRACED: with the layers unrolled
as constants the compiler copied the whole state around every XLA-form call of
this harness — 150 µs a Mamba-1 call, 748 a Mamba-2 one, my chip runs, PR 49 —
which no serving step does). The clock is the device's own (a profiler trace:
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
import math
import tempfile
import time
from pathlib import Path

GEOMETRY = dict(layers=6, slots=16, heads=64, head_dim=64, state=128, groups=8)
TINY = dict(layers=2, slots=4, heads=4, head_dim=8, state=128, groups=2)
GEOMETRY1 = dict(layers=26, slots=8, state=16, inner=5120)
TINY1 = dict(layers=3, slots=4, state=8, inner=256)


def xla_update(state, layer, advancing, decay, xdt, bmat, cmat):
    """The form the Mamba-2 kernel replaces, as ``mamba_step`` and ``paged_decode_forward`` write it."""
    import jax.numpy as jnp

    rep = state.shape[2] // bmat.shape[1]
    bmat, cmat = (jnp.repeat(m, rep, axis=1) for m in (bmat, cmat))
    ssm = state[layer] * decay[..., None, None] + xdt[..., None] * bmat[:, :, None, :]
    y = jnp.where(advancing[:, None, None], jnp.einsum("bhpn,bhn->bhp", ssm, cmat), 0.0)   # (the kernel's zero)
    return state.at[layer].set(jnp.where(advancing[:, None, None, None], ssm, state[layer])), y


def xla_selective_update(state, layer, advancing, dt, dx, bmat, cmat, a_log):
    """The form the Mamba-1 kernel replaces, as ``mamba1_step`` and ``paged_decode_forward`` write it."""
    import jax.numpy as jnp

    ssm = jnp.exp(dt[:, None, :] * -jnp.exp(a_log)) * state[layer] + dx[:, None, :] * bmat[:, :, None]
    y = jnp.where(advancing[:, None], jnp.sum(ssm * cmat[:, :, None], axis=1), 0.0)        # (the kernel's zero)
    return state.at[layer].set(jnp.where(advancing[:, None, None], ssm, state[layer])), y


def mamba2_terms(g: dict, key) -> tuple:
    """(decay, x dt, B, C) of one call, every layer's alike."""
    import jax
    import jax.numpy as jnp

    rows, heads, p, n, groups = (g[k] for k in ("slots", "heads", "head_dim", "state", "groups"))
    keys = jax.random.split(key, 4)
    return (jax.random.uniform(keys[0], (rows, heads), jnp.float32, 0.5, 1.0),
            jax.random.normal(keys[1], (rows, heads, p), jnp.float32),
            *(jax.random.normal(k, (rows, groups, n), jnp.float32) for k in keys[2:]))


def mamba1_terms(g: dict, key) -> tuple:
    """(D, D x, B, C, a_log) of one call; ``a_log`` as published
    (``models/jamba.py::init_jamba``): every layer starts from the same one."""
    import jax
    import jax.numpy as jnp

    rows, n, inner = (g[k] for k in ("slots", "state", "inner"))
    keys = jax.random.split(key, 4)
    return (jax.random.uniform(keys[0], (rows, inner), jnp.float32, 0.001, 0.1),
            jax.random.normal(keys[1], (rows, inner), jnp.float32),
            *(jax.random.normal(k, (rows, n), jnp.float32) for k in keys[2:]),
            jnp.tile(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (1, inner)))


# a recurrence: its geometries, the state's shape of one, a call's terms, the XLA form, the rows that advance by default
RECURRENCES = {
    "mamba2": dict(full=GEOMETRY, tiny=TINY, terms=mamba2_terms, xla=xla_update,
                   shape=lambda g: (g["layers"], g["slots"], g["heads"], g["head_dim"], g["state"]),
                   counts=lambda slots: {slots, slots * 5 // 8, 1, 0}),
    "mamba1": dict(full=GEOMETRY1, tiny=TINY1, terms=mamba1_terms, xla=xla_selective_update,
                   shape=lambda g: (g["layers"], g["slots"], g["state"], g["inner"]),
                   counts=lambda slots: {slots, slots // 2, 2, 1, 0}),
}


def time_point(rec: dict, g: dict, advancing: int, form: str, calls: int, trace_dir: Path | None, seed: int) -> tuple:
    """``calls`` chained updates, layer after layer → (the line, the state and y sum after them)."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.eval.prefill_attn_timing import device_us
    from sentio_tpu.kernels.ssm_update import make_ssm_update_impl

    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape, terms = rec["shape"](g), rec["terms"](g, keys[1])
    layers, rows = shape[:2]
    # the advancing rows spread over the slots, as a closed cell's halted rows are
    mask = jnp.zeros((rows,), bool).at[(jnp.arange(advancing) * rows) // max(advancing, 1)].set(advancing > 0)
    update = make_ssm_update_impl() if form == "kernel" else rec["xla"]

    def chained(state, mask, terms):
        def body(i, carry):
            state, acc = carry
            state, y = update(state, i % layers, mask, *terms)
            return state, acc + y

        _, y = jax.eval_shape(lambda s: update(s, 0, mask, *terms), state)
        return jax.lax.fori_loop(0, calls, body, (state, jnp.zeros(y.shape, jnp.float32)))

    run = jax.jit(chained, donate_argnums=0)

    def fresh():
        return jax.random.normal(keys[0], shape, jnp.float32)

    out = jax.block_until_ready(run(fresh(), mask, terms))
    state = fresh()
    t0 = time.perf_counter()
    jax.block_until_ready(run(state, mask, terms))
    line = {"host_us_per_call": (time.perf_counter() - t0) / calls * 1e6}
    if trace_dir is not None:
        state = fresh()
        with jax.profiler.trace(str(trace_dir)):
            jax.block_until_ready(run(state, mask, terms))
        line.update(device_us(trace_dir, calls, "ssm_update"))
    return line, out


def main(argv=None) -> int:
    from sentio_tpu.infra.compile_cache import ensure_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--recurrence", choices=sorted(RECURRENCES), default="mamba2")
    ap.add_argument("--tiny", action="store_true", help="toy shapes: a rehearsal of the control flow on the CPU")
    ap.add_argument("--calls", type=int, default=0, help="chained calls a point (default: eight sub-steps' worth)")
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
    rec = RECURRENCES[args.recurrence]
    g = rec["tiny" if args.tiny else "full"]
    slots = g["slots"]
    counts = [int(c) for c in args.advancing.split(",") if c] or sorted(rec["counts"](slots), reverse=True)
    calls = args.calls or 8 * g["layers"]
    row_bytes = 2 * 4 * math.prod(rec["shape"](g)[2:])
    clock = "host" if args.no_trace or dev.platform == "cpu" else "device_trace"
    table = {}
    with tempfile.TemporaryDirectory() as tmp:
        for advancing in counts:
            outs = {}
            for form in args.forms.split(","):
                where = None if args.no_trace else Path(tmp) / f"{form}-{advancing}"
                line, outs[form] = time_point(rec, g, advancing, form, calls, where, args.seed)
                us = line.get("device_us_per_call") or line["host_us_per_call"]
                line.update(form=form, advancing=advancing, slots=slots, gbps=advancing * row_bytes / us / 1e3)
                if form == "kernel" and "xla" in outs or form == "xla" and "kernel" in outs:
                    line["max_rel_err"] = max(
                        float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))
                        for a, b in zip(outs["kernel"], outs["xla"]))
                print(json.dumps({**line, "device": device}), flush=True)
                table.setdefault(form, {})[str(advancing)] = round(us, 1)
    print(json.dumps({"ok": True, "device": device, "clock": clock, "recurrence": args.recurrence, "geometry": g,
                      "us_per_call": table}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
