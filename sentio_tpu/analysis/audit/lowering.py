"""Abstract lowering + property extraction for one compile variant.

``jax.jit(...).lower(*example_args, **statics)`` traces the function over
abstract values — no FLOP executes, no buffer is donated — and yields the
StableHLO module XLA would compile. Three properties gate the manifest:

* **donation aliasing** — jax matches each donated input leaf to an output
  of identical shape/dtype(/sharding) during lowering; a matched leaf gets
  a ``tf.aliasing_output`` argument attribute in the module. Counting those
  attributes against the donated leaf count is the honest "is the pool
  REALLY updated in place" check (paged.py's comment-only contract until
  now). A dropped donation (shape drift, output reorder, dtype mismatch)
  simply loses its attribute — platform-independently, so CPU tier-1 can
  gate TPU-relevant donation behavior.
* **static HBM footprint** — argument/result byte totals computed from the
  avals (pure shape math, deterministic everywhere). Pool growth or an
  accidentally materialized copy shows up here.
* **sharding signatures** — arguments carrying a ``NamedSharding`` lower
  with ``sdy.sharding`` attributes; the sorted multiset of those strings
  is the replication-creep gate for mesh variants.

FLOPs / bytes-accessed from ``Lowered.cost_analysis()`` are recorded as
``info`` only — useful for eyeballing a diff, excluded from gating (they
are an XLA implementation detail, not a contract).
"""

from __future__ import annotations

import re
from typing import Any

__all__ = ["audit_variant", "lower_variant", "count_aliased", "tree_bytes"]

_ALIAS_RE = re.compile(r"tf\.aliasing_output")
# jax 0.9 lowers with the Shardy partitioner: argument shardings are
# ``sdy.sharding = #sdy.sharding<@mesh, [{}, {"tp"}]>`` attributes
_SHARDING_RE = re.compile(r'sdy\.sharding = #sdy\.sharding<@\w+, (\[[^>]*\])>')


def tree_bytes(tree: Any) -> int:
    """Total bytes of every array-like leaf (shape x dtype, no device IO)."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    return total


def count_aliased(stablehlo_text: str) -> int:
    """Donated input leaves jax actually aliased to an output."""
    main = _main_signature(stablehlo_text)
    return len(_ALIAS_RE.findall(main))


def _main_signature(stablehlo_text: str) -> str:
    """The @main func signature (arg attributes live there; searching the
    whole module would also match nested private funcs). MLIR prints the
    signature — including inline ``{tf.aliasing_output = ...}`` attribute
    dicts — on one line ending with the body-opening brace."""
    marker = "func.func public @main("
    start = stablehlo_text.find(marker)
    if start < 0:
        return stablehlo_text
    end = stablehlo_text.find("\n", start)
    return stablehlo_text[start : end if end > 0 else len(stablehlo_text)]


def donated_leaf_count(donate_argnums: tuple[int, ...], args: tuple) -> int:
    """How many flat array leaves the declared donation covers."""
    import jax

    total = 0
    for i in donate_argnums:
        if i < len(args):
            total += len(jax.tree_util.tree_leaves(args[i]))
    return total


def lower_variant(fn: Any, args: tuple, static_kwargs: dict):
    """AOT-lower one variant. ``fn`` may be a FamilyFn (``.lower`` forwards
    to the jitted inner) or a bare jitted function."""
    return fn.lower(*args, **static_kwargs)


def audit_variant(
    fn: Any,
    donate_argnums: tuple[int, ...],
    args: tuple,
    static_kwargs: dict,
    collect_shardings: bool = False,
) -> dict:
    """Lower one variant and extract its gated properties.

    Returns a manifest-entry dict: ``donated_leaves`` (declared),
    ``aliased`` (what lowering kept), ``arg_bytes``/``out_bytes`` (static
    footprint), optional ``arg_shardings`` (sorted sdy strings, mesh
    variants only), and non-gated ``info`` (flops / bytes accessed).
    """
    lowered = lower_variant(fn, args, static_kwargs)
    text = lowered.as_text()
    entry: dict = {
        "donated_leaves": donated_leaf_count(donate_argnums, args),
        "aliased": count_aliased(text),
        "arg_bytes": tree_bytes(args),
        "out_bytes": _out_bytes(lowered, fn, args, static_kwargs),
    }
    if collect_shardings:
        entry["arg_shardings"] = sorted(
            _SHARDING_RE.findall(_main_signature(text))
        )
    info: dict = {}
    try:
        cost = lowered.cost_analysis() or {}
        for key in ("flops", "bytes accessed"):
            if key in cost:
                info[key.replace(" ", "_")] = float(cost[key])
    except Exception:  # noqa: BLE001 — cost analysis is backend-optional
        pass
    if info:
        entry["info"] = info
    return entry


def _out_bytes(lowered: Any, fn: Any, args: tuple, static_kwargs: dict) -> int:
    """Output footprint from the lowering's own out avals when the jax
    version exposes them; otherwise one extra abstract trace."""
    import jax

    out_info = getattr(lowered, "out_info", None)
    if out_info is not None:
        return tree_bytes(out_info)
    # fall back to the bare jitted fn (NOT the FamilyFn wrapper — an
    # eval_shape must never feed the compile counters)
    inner = getattr(fn, "_fn", fn)
    return tree_bytes(jax.eval_shape(inner, *args, **static_kwargs))
