"""``jit_family``: the registry decorator behind the compile-manifest audit.

Every serving-critical jit site declares itself once::

    @jit_family("paged.step_n", static_argnames=("steps",),
                donate_argnums=(5, 6))
    def step_n(params, tok, ...):
        ...

The decorator applies ``jax.jit`` itself, so the static/donated argnums it
records are BY CONSTRUCTION the ones XLA sees — there is no second copy to
drift. The returned :class:`FamilyFn` is a thin callable wrapper that:

* forwards calls (and ``.lower`` / ``.clear_cache`` / ``._cache_size`` /
  every other attribute) to the underlying jitted function;
* after each call, compares the jit cache size against the last observed
  value — growth means XLA compiled a new variant — and reports the event
  to :mod:`sentio_tpu.analysis.audit.fence` with the family name and the
  abstract signature of the offending call.

``sentio lint``'s retrace rules recognize ``@jit_family(...)`` exactly like
``@partial(jax.jit, ...)`` (analysis/retrace.py), so moving a site onto the
registry never loses static-arg boundedness coverage.

The registry is process-global and last-wins per name: engines rebuild
their jitted closures per instance (``_build_fns``), and the audit only
needs (a) the full set of family NAMES that exist — its coverage check
fails when a new ``jit_family`` site appears without an audit spec — and
(b) the declared static/donate contract per name.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Optional

__all__ = ["jit_family", "FamilyFn", "JitFamily", "families", "get_family"]


@dataclass
class JitFamily:
    """One registered jit family: the declared compile contract plus the
    most recently constructed jitted instance."""

    name: str
    static_argnames: tuple[str, ...]
    donate_argnums: tuple[int, ...]
    fn: "FamilyFn"


_REGISTRY: dict[str, JitFamily] = {}  # guarded-by: _registry_lock
_registry_lock = threading.Lock()


def families() -> dict[str, JitFamily]:
    """Snapshot of every family registered so far in this process."""
    with _registry_lock:
        return dict(_REGISTRY)


def get_family(name: str) -> Optional[JitFamily]:
    with _registry_lock:
        return _REGISTRY.get(name)


def abstract_signature(args: tuple, kwargs: dict) -> str:
    """Compact dtype[shape] rendering of a call's dynamic arguments — what a
    fence error / compile event reports as "the shape that recompiled"."""
    import jax

    def leaf(x: Any) -> str:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return f"{dtype}[{','.join(str(d) for d in shape)}]"
        return repr(x)[:32]

    leaves = [leaf(x) for x in jax.tree_util.tree_leaves((args, kwargs))]
    return "(" + ", ".join(leaves) + ")"


class FamilyFn:
    """Callable wrapper over one jitted function instance. Call overhead is
    one ``_cache_size()`` C++ call per dispatch — noise next to the
    dispatch itself."""

    def __init__(self, family: str, fn: Any) -> None:
        self.family = family
        self._fn = fn
        # JAX's own name of what it compiles here: the label the timed
        # compile account keeps this family under
        self.program = getattr(fn, "__name__", None)
        self._cache_size_fn = getattr(fn, "_cache_size", None)
        self._seen = 0
        # armed-fence bypass for THIS instance only: a supervised replica
        # rebuild sets it while warming its fresh engine (whose FamilyFns
        # are all cold), then clears it — compiles on other instances keep
        # tripping the fence throughout
        self.fence_exempt = False

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        if self._cache_size_fn is not None:
            n = self._cache_size_fn()
            if n > self._seen:
                delta = n - self._seen
                self._seen = n
                from sentio_tpu.analysis.audit import fence

                # may raise CompileFenceError when the fence is armed — the
                # compile already happened; the error is the report
                fence.note_compile(
                    self.family, abstract_signature(args, kwargs), delta,
                    exempt=self.fence_exempt, program=self.program,
                )
        return out

    def __getattr__(self, name: str):
        # .lower / .eval_shape / .clear_cache / ._cache_size ... — AOT
        # lowering through this path never touches the compile counters
        return getattr(self._fn, name)

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"FamilyFn({self.family!r}, {self._fn!r})"


def jit_family(
    name: str,
    *,
    static_argnames: tuple[str, ...] = (),
    donate_argnums: tuple[int, ...] = (),
    donate_argnames: tuple[str, ...] = (),
    register: bool = True,
):
    """Decorator: ``jax.jit`` + registry entry + compile accounting.

    ``donate_argnames``: keyword arguments donated beside the positional
    ``donate_argnums`` — state only some families pass (None, an empty
    pytree, for the others), so no positional signature moves for it.

    ``register=False`` builds the counting wrapper without touching the
    process-global registry — for test fixtures that must not make the
    audit's coverage check order-dependent.
    """

    def deco(fn):
        import jax

        jitted = jax.jit(
            fn,
            static_argnames=tuple(static_argnames),
            donate_argnums=tuple(donate_argnums),
            **({"donate_argnames": tuple(donate_argnames)} if donate_argnames else {}),
        )
        wrapped = FamilyFn(name, jitted)
        if wrapped.program:
            from sentio_tpu.analysis.audit import fence

            fence.register_program(wrapped.program)
        if register:
            with _registry_lock:
                _REGISTRY[name] = JitFamily(
                    name=name,
                    static_argnames=tuple(static_argnames),
                    donate_argnums=tuple(donate_argnums),
                    fn=wrapped,
                )
        return wrapped

    return deco
