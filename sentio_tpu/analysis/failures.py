"""Failure-surface analysis: typed-error propagation, wire-contract audit,
frame-protocol exhaustiveness (whole-program).

Every chaos drill asserts "0 untyped errors" — but only for the paths the
drill exercises. This pass proves the property statically, reusing the
thread-role call graph from :mod:`.threads`:

* **untyped-boundary-escape** — raise sites are propagated up the call
  graph (filtered by enclosing ``try``/``except`` clauses, subclass-aware)
  to the declared *serving boundaries*: HTTP handlers (auto-discovered
  from ``app.router.add_get/add_post(...)`` registrations), and every
  thread spawned under a serving role (pump, supervisor, dispatcher, RPC
  handlers, accepter, status/telemetry loops, detached verify, drain,
  rebuild, health probes). A raise reachable at a boundary that is not a
  ``SentioError`` subclass is a finding anchored at the ORIGIN raise site.
  HTTP boundaries additionally allow ``SchemaError`` and aiohttp
  ``HTTP*`` exceptions (the middleware maps both deliberately).

* **typed-error-untyped-rethrow** — an ``except <SentioError subclass>``
  handler that raises a non-typed exception strips ``code`` /
  ``retry_after_s`` / ``soft_fail_exempt`` off the error on its way to
  the wire.

* **broad-except-swallow** — an ``except Exception`` whose body neither
  re-raises, nor logs, nor counts, and whose except line carries no
  written justification (``# noqa: BLE001 — <why>``) swallows failures
  invisibly. :mod:`.hygiene` owns the ``BaseException`` / bare-``except``
  variants; this rule covers exactly ``except Exception``.

* **codec-roundtrip** — the RPC exception codec
  (``runtime/worker.py:_encode_exc``/``_decode_exc``) resolves classes by
  name from ``sentio_tpu.infra.exceptions`` and re-raises through
  ``cls(message)``-shaped construction. A ``SentioError`` subclass
  defined in any other module silently degrades to ``RuntimeError``
  across the wire; one whose ``__init__`` requires extra positional
  arguments breaks the re-raise path. Both are findings at the class
  definition.

* **frame-kind-unhandled** / **frame-protocol** — transport channels are
  declared in source with ``# frame-emit: <channel> [via=pipe,socket]``
  (on a ``def`` or ``class`` line; class-level covers every method) and
  ``# frame-dispatch: <channel> via=pipe,socket``. Emitted frame kinds
  are extracted from ``send``-shaped calls (string literals or
  module-level constants); dispatched kinds from ``kind == ...`` /
  ``method in (...)`` comparisons. Every kind a channel can emit must
  have a dispatcher branch on every transport path (``via``) the channel
  serves — a frame kind added on one side only is a static finding, not
  a runtime ``FrameProtocolError``.

Like the thread model, the analysis is an under-approximation:
unresolvable dynamic raises/calls produce no findings, a transparent
re-raise (``except Exception: ...; raise``) is treated as catching (its
conditional re-raise cannot be modeled precisely), so every finding
corresponds to a concrete raise-to-boundary path. Missing edges cost
recall, never precision.
"""

from __future__ import annotations

import ast
import builtins
import re
from dataclasses import dataclass, field
from typing import Optional

from sentio_tpu.analysis.findings import Finding, SourceFile
from sentio_tpu.analysis.threads import FuncInfo, FuncKey, Program

__all__ = [
    "check_failures",
    "build_failure_graph",
    "collect_fault_points",
    "collect_armed_points",
    "FAILURE_RULE_IDS",
]

RULE_ESCAPE = "untyped-boundary-escape"
RULE_RETHROW = "typed-error-untyped-rethrow"
RULE_SWALLOW = "broad-except-swallow"
RULE_CODEC = "codec-roundtrip"
RULE_FRAME = "frame-kind-unhandled"
RULE_PROTO = "frame-protocol"

FAILURE_RULE_IDS = (RULE_ESCAPE, RULE_RETHROW, RULE_SWALLOW, RULE_CODEC,
                    RULE_FRAME, RULE_PROTO)

#: the typed-error root: everything transitively derived from it carries
#: the wire surface (code / status / details / retryable)
TYPED_ROOT = "SentioError"

#: the one module the RPC codec resolves exception classes from
CODEC_MODULE = "sentio_tpu/infra/exceptions.py"

#: spawn roles whose thread death IS a serving failure: an escape that
#: kills one of these silently degrades live traffic. Roles like warmup /
#: eval-worker / mock-api are bench-and-build scaffolding with their own
#: error handling and are deliberately out of scope.
SERVING_ROLES = frozenset({
    "pump", "supervisor", "dispatcher", "rpc", "accepter", "status",
    "telemetry", "detached-verify", "drain", "rebuild", "health-probe",
    "autoscaler",
})

#: boundaries that are not thread spawns or HTTP routes: the worker RPC
#: recv loop and the worker process entry points (qualname match, path
#: must end with the given suffix)
EXTRA_BOUNDARIES: tuple[tuple[str, str, str], ...] = (
    ("runtime/worker.py", "_WorkerServer.run", "worker RPC recv loop"),
    ("runtime/worker.py", "worker_main", "worker process entry"),
    ("runtime/worker.py", "worker_main_socket", "worker process entry"),
    ("runtime/worker.py", "worker_serve", "advertised-worker accept loop"),
)

#: aiohttp route registration methods (handler = last positional arg)
_ROUTE_ADDERS = frozenset({
    "add_get", "add_post", "add_put", "add_delete", "add_patch",
    "add_route",
})

#: BaseException-derived control flow `except Exception` cannot catch —
#: and which is never an untyped *failure* at a boundary (cancellation
#: and generator teardown are protocol, not errors)
_BASE_ONLY = frozenset({
    "KeyboardInterrupt", "SystemExit", "GeneratorExit", "CancelledError",
})
_NON_FAILURES = _BASE_ONLY | frozenset({"StopIteration", "StopAsyncIteration"})

#: builtin exception single-inheritance chains (everything else reaches
#: Exception implicitly, which the catch-all markers cover)
_BUILTIN_PARENTS = {
    "BrokenPipeError": "ConnectionError",
    "ConnectionResetError": "ConnectionError",
    "ConnectionAbortedError": "ConnectionError",
    "ConnectionRefusedError": "ConnectionError",
    "ConnectionError": "OSError",
    "FileNotFoundError": "OSError",
    "FileExistsError": "OSError",
    "PermissionError": "OSError",
    "InterruptedError": "OSError",
    "BlockingIOError": "OSError",
    "ChildProcessError": "OSError",
    "ProcessLookupError": "OSError",
    "NotADirectoryError": "OSError",
    "IsADirectoryError": "OSError",
    "TimeoutError": "OSError",
    "IOError": "OSError",
    "IndexError": "LookupError",
    "KeyError": "LookupError",
    "ZeroDivisionError": "ArithmeticError",
    "OverflowError": "ArithmeticError",
    "FloatingPointError": "ArithmeticError",
    "UnicodeDecodeError": "UnicodeError",
    "UnicodeEncodeError": "UnicodeError",
    "UnicodeTranslateError": "UnicodeError",
    "UnicodeError": "ValueError",
    "IndentationError": "SyntaxError",
    "RecursionError": "RuntimeError",
    "NotImplementedError": "RuntimeError",
    "ModuleNotFoundError": "ImportError",
}

_BUILTIN_EXCS = frozenset(
    n for n in dir(builtins)
    if isinstance(getattr(builtins, n), type)
    and issubclass(getattr(builtins, n), BaseException)
)

#: calls inside an `except Exception` body that count as handling it:
#: logging, traceback printing, or a metrics count
_SWALLOW_OK_CALLS = frozenset({
    "debug", "info", "warning", "error", "exception", "critical", "log",
    "print_exc", "format_exc", "print", "inc", "observe", "increment",
    "record", "record_worker_death", "note_stale_frame",
})

_NOQA_JUSTIFIED_RE = re.compile(r"#\s*noqa:\s*BLE001\b.*—\s*\S")

_FRAME_EMIT_RE = re.compile(
    r"#\s*frame-emit:\s*([\w-]+)(?:\s+via=([\w,]+))?")
_FRAME_DISPATCH_RE = re.compile(
    r"#\s*frame-dispatch:\s*([\w-]+)\s+via=([\w,]+)")
_FRAME_ANY_RE = re.compile(r"#\s*frame-(emit|dispatch):")

#: variables a dispatcher switches on — comparisons against anything else
#: are not dispatch branches
_DISPATCH_VARS = frozenset({"kind", "method"})

#: call shapes that put a frame on the wire; the kind position differs:
#: f(req_id, KIND, payload) vs f((req_id, KIND, payload)) vs _call(KIND, ..)
_SEND_ATTRS = frozenset({"send", "_send", "_send_frame"})


# ------------------------------------------------------------- typed universe


def _class_parents(prog: Program) -> dict[str, str]:
    """First resolvable base name per program class (single chains — the
    exception taxonomy is single-inheritance)."""
    parents: dict[str, str] = {}
    for name, defs in prog.classes.items():
        for _rel, node in defs:
            for base in node.bases:
                base_name = base.id if isinstance(base, ast.Name) else (
                    base.attr if isinstance(base, ast.Attribute) else None)
                if base_name:
                    parents.setdefault(name, base_name)
                    break
            if name in parents:
                break
    return parents


def _typed_universe(prog: Program, parents: dict[str, str]) -> set[str]:
    typed = {TYPED_ROOT}
    changed = True
    while changed:
        changed = False
        for name in prog.classes:
            if name not in typed and parents.get(name) in typed:
                typed.add(name)
                changed = True
    return typed


def _ancestor_chain(name: str, parents: dict[str, str]) -> list[str]:
    chain = []
    seen = set()
    n: Optional[str] = name
    while n and n not in seen:
        chain.append(n)
        seen.add(n)
        n = parents.get(n) or _BUILTIN_PARENTS.get(n)
    return chain


def _caught_by(exc_name: str, catches: frozenset,
               parents: dict[str, str]) -> bool:
    if "**" in catches:
        return True
    if "*" in catches and exc_name not in _BASE_ONLY:
        return True
    return any(a in catches for a in _ancestor_chain(exc_name, parents))


# ------------------------------------------------- per-function raise/call map


@dataclass
class _ExcSummary:
    #: (exception class name, raise lineno, enclosing catch filters)
    raises: list[tuple[str, int, tuple[frozenset, ...]]] = field(
        default_factory=list)
    #: (callee, call lineno, enclosing catch filters)
    calls: list[tuple[FuncKey, int, tuple[frozenset, ...]]] = field(
        default_factory=list)


def _handler_catch_names(handler: ast.ExceptHandler) -> list[str]:
    t = handler.type
    if t is None:
        return ["**"]
    names: list[str] = []
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    for e in elts:
        n = e.id if isinstance(e, ast.Name) else (
            e.attr if isinstance(e, ast.Attribute) else None)
        if n == "Exception":
            names.append("*")
        elif n == "BaseException":
            names.append("**")
        elif n:
            names.append(n)
    return names


def _handler_reraises(handler: ast.ExceptHandler) -> bool:
    """A bare ``raise`` anywhere in the handler body (nested defs
    excluded) makes the handler transparent for the escape analysis."""
    for node in _walk_body(handler.body):
        if isinstance(node, ast.Raise) and node.exc is None:
            return True
    return False


def _walk_body(stmts) -> list[ast.AST]:
    out: list[ast.AST] = []
    stack = list(stmts)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        out.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return out


def _raise_class_name(node: ast.Raise, known: frozenset) -> Optional[str]:
    exc = node.exc
    if exc is None:
        return None  # bare re-raise: modeled by handler transparency
    if isinstance(exc, ast.Call):
        exc = exc.func
    name = exc.id if isinstance(exc, ast.Name) else (
        exc.attr if isinstance(exc, ast.Attribute) else None)
    # `raise exc` re-raising a bound variable resolves to a non-class
    # name; only names that are program classes or builtin exceptions are
    # concrete raise sites
    if name in known:
        return name
    return None


def _summarize(prog: Program, info: FuncInfo,
               known_classes: frozenset) -> _ExcSummary:
    summary = _ExcSummary()
    call_ids = {id(c) for c in info.calls}
    raw_calls: list[tuple[ast.Call, int, tuple[frozenset, ...]]] = []

    def visit(stmts, filters: tuple[frozenset, ...]) -> None:
        for node in stmts:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if isinstance(node, ast.Raise):
                name = _raise_class_name(node, known_classes)
                if name is not None:
                    summary.raises.append((name, node.lineno, filters))
                for child in ast.iter_child_nodes(node):
                    visit([child], filters)
                continue
            if isinstance(node, (ast.Try, getattr(ast, "TryStar", ast.Try))):
                names: list[str] = []
                for h in node.handlers:
                    if not _handler_reraises(h):
                        names.extend(_handler_catch_names(h))
                body_filters = (filters + (frozenset(names),)
                                if names else filters)
                visit(node.body, body_filters)
                for h in node.handlers:
                    visit(h.body, filters)
                visit(node.orelse, filters)
                visit(node.finalbody, filters)
                continue
            if isinstance(node, ast.Call) and id(node) in call_ids:
                raw_calls.append((node, node.lineno, filters))
            for child in ast.iter_child_nodes(node):
                visit([child], filters)

    visit(list(ast.iter_child_nodes(info.node)), ())

    # resolve raw call nodes against the already-built call graph: every
    # edge out of this function is matched to the call sites sharing its
    # terminal name, so each site carries its own try/except filters
    edges = prog.edges.get(info.key, set())
    if edges:
        by_name: dict[str, list[FuncKey]] = {}
        for callee in edges:
            by_name.setdefault(callee[1].rsplit(".", 1)[-1], []).append(callee)
        for raw, lineno, filters in raw_calls:
            fn = raw.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            for callee in by_name.get(name or "", ()):
                summary.calls.append((callee, lineno, filters))
    return summary


def _escape_sets(
    prog: Program,
    summaries: dict[FuncKey, _ExcSummary],
    parents: dict[str, str],
) -> dict[FuncKey, dict[str, tuple[str, int]]]:
    """Fixpoint: escapes(f) = local uncaught raises ∪ callee escapes not
    caught at the call site. Values map exception name → first origin
    ``(path, line)`` so findings anchor at the raise that started it."""
    escapes: dict[FuncKey, dict[str, tuple[str, int]]] = {}
    for key, s in summaries.items():
        local: dict[str, tuple[str, int]] = {}
        for name, lineno, filters in s.raises:
            if any(_caught_by(name, f, parents) for f in filters):
                continue
            local.setdefault(name, (key[0], lineno))
        escapes[key] = local
    changed = True
    rounds = 0
    while changed and rounds < 100:
        changed = False
        rounds += 1
        for key, s in summaries.items():
            mine = escapes[key]
            for callee, _lineno, filters in s.calls:
                for name, origin in escapes.get(callee, {}).items():
                    if name in mine:
                        continue
                    if any(_caught_by(name, f, parents) for f in filters):
                        continue
                    mine[name] = origin
                    changed = True
    return escapes


# ------------------------------------------------------------------ boundaries


@dataclass
class _Boundary:
    key: FuncKey
    kind: str            # human description ("pump thread", "http handler")
    allow_http: bool = False


def _discover_boundaries(prog: Program) -> list[_Boundary]:
    out: list[_Boundary] = []
    seen: set[FuncKey] = set()

    def add(key: FuncKey, kind: str, allow_http: bool = False) -> None:
        if key in prog.functions and key not in seen:
            seen.add(key)
            out.append(_Boundary(key=key, kind=kind, allow_http=allow_http))

    for spawn in prog.spawns:
        if spawn.role in SERVING_ROLES and spawn.target_key is not None:
            add(spawn.target_key, f"{spawn.role} thread")

    for tree, src in prog.files:
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _ROUTE_ADDERS
                    and node.args):
                continue
            handler = node.args[-1]
            if isinstance(handler, ast.Name):
                add((src.rel, handler.id), "http handler", allow_http=True)

    for suffix, qual, kind in EXTRA_BOUNDARIES:
        for key in prog.functions:
            if key[1] == qual and key[0].endswith(suffix):
                add(key, kind)
    return out


def _check_escapes(prog: Program, typed: set[str],
                   escapes: dict[FuncKey, dict[str, tuple[str, int]]],
                   boundaries: list[_Boundary]) -> list[Finding]:
    src_by_rel = {s.rel: s for _t, s in prog.files}
    # (origin path, origin line, exc name) -> [boundary descriptions]
    grouped: dict[tuple[str, int, str], list[str]] = {}
    for b in boundaries:
        for name, (opath, oline) in escapes.get(b.key, {}).items():
            if name in typed or name in _NON_FAILURES:
                continue
            if b.allow_http and (name.startswith("HTTP")
                                 or name == "SchemaError"):
                continue
            desc = f"{b.key[1]} ({b.kind})"
            grouped.setdefault((opath, oline, name), []).append(desc)
    findings: list[Finding] = []
    for (opath, oline, name), descs in sorted(grouped.items()):
        src = src_by_rel.get(opath)
        if src is None:
            continue
        shown = ", ".join(sorted(set(descs))[:3])
        more = len(set(descs)) - len(sorted(set(descs))[:3])
        if more > 0:
            shown += f" (+{more} more)"
        f = src.finding(
            RULE_ESCAPE, oline,
            f"raise {name} can reach serving boundary {shown} untyped — "
            f"wrap it in a SentioError subclass (typed status + "
            f"retry_after_s survive the wire) or catch it before the "
            f"boundary",
        )
        if f is not None:
            findings.append(f)
    return findings


# ----------------------------------------------- rethrow / swallow (per file)


def _check_handlers(prog: Program, typed: set[str]) -> list[Finding]:
    findings: list[Finding] = []
    known = frozenset(prog.classes) | _BUILTIN_EXCS
    for tree, src in prog.files:
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            names = set(_handler_catch_names(node))
            catches_typed = bool(names & typed)
            body = _walk_body(node.body)
            if catches_typed:
                for stmt in body:
                    if not isinstance(stmt, ast.Raise) or stmt.exc is None:
                        continue
                    rname = _raise_class_name(stmt, known)
                    if rname is None or rname in typed:
                        continue
                    if rname in _NON_FAILURES or rname.startswith("HTTP"):
                        continue
                    f = src.finding(
                        RULE_RETHROW, stmt.lineno,
                        f"except {'/'.join(sorted(n for n in names if n not in ('*', '**')))} "
                        f"re-raises {rname}: the typed error's code / "
                        f"retry_after_s / soft_fail_exempt are lost on the "
                        f"way to the wire — re-raise the original or wrap "
                        f"it in a SentioError subclass",
                    )
                    if f is not None:
                        findings.append(f)
            if names == {"*"}:  # exactly `except Exception`
                handled = False
                for stmt in body:
                    if isinstance(stmt, ast.Raise):
                        handled = True
                        break
                    # counting the failure IS handling it (errors += 1)
                    if isinstance(stmt, ast.AugAssign):
                        handled = True
                        break
                    if isinstance(stmt, ast.Call):
                        fn = stmt.func
                        cname = fn.attr if isinstance(fn, ast.Attribute) \
                            else (fn.id if isinstance(fn, ast.Name) else "")
                        if cname in _SWALLOW_OK_CALLS:
                            handled = True
                            break
                # a handler that binds the exception and actually reads it
                # (records str(exc), maps it to a response, ...) consumed
                # the failure — only DROPPING the value is a swallow
                if not handled and node.name:
                    for sub in ast.walk(ast.Module(body=node.body,
                                                   type_ignores=[])):
                        if isinstance(sub, ast.Name) and sub.id == node.name:
                            handled = True
                            break
                if handled:
                    continue
                line = src.line_text(node.lineno)
                if _NOQA_JUSTIFIED_RE.search(line):
                    continue
                f = src.finding(
                    RULE_SWALLOW, node.lineno,
                    "except Exception swallows the failure without "
                    "re-raising typed, logging, or counting — handle it, "
                    "or justify the swallow in place "
                    "(# noqa: BLE001 — <why>)",
                )
                if f is not None:
                    findings.append(f)
    return findings


# ------------------------------------------------------------ codec roundtrip


def _check_codec(prog: Program, typed: set[str]) -> list[Finding]:
    src_by_rel = {s.rel: s for _t, s in prog.files}
    findings: list[Finding] = []
    for name in sorted(typed):
        if name == TYPED_ROOT:
            continue
        for rel, node in prog.classes.get(name, ()):
            src = src_by_rel.get(rel)
            if src is None:
                continue
            problems: list[str] = []
            if not rel.endswith(CODEC_MODULE):
                problems.append(
                    "defined outside sentio_tpu/infra/exceptions.py — "
                    "_decode_exc resolves subclasses by name from that "
                    "module only, so this type degrades to RuntimeError "
                    "across the RPC wire")
            init = prog.functions.get((rel, f"{name}.__init__"))
            if init is not None:
                bad = _ctor_incompatibility(init.node)
                if bad:
                    problems.append(bad)
            if problems:
                f = src.finding(
                    RULE_CODEC, node.lineno,
                    f"SentioError subclass {name} cannot round-trip the "
                    f"RPC exception codec: " + "; ".join(problems),
                )
                if f is not None:
                    findings.append(f)
    return findings


def _ctor_incompatibility(node: ast.AST) -> Optional[str]:
    """The codec's re-raise path (and the exhaustiveness gate) construct
    ``cls(message, **wire_kwargs)`` — more than one required positional
    parameter, or a required keyword-only one, breaks that."""
    args = node.args
    pos = list(args.posonlyargs) + list(args.args)
    required = len(pos) - len(args.defaults)
    if pos and pos[0].arg in ("self", "cls"):
        required -= 1
    if required > 1:
        return ("__init__ requires extra positional arguments beyond the "
                "message — the codec re-raise path constructs "
                "cls(message)")
    for kw, default in zip(args.kwonlyargs, args.kw_defaults):
        if default is None and kw.arg not in ("details", "kwargs"):
            return (f"__init__ requires keyword-only argument "
                    f"{kw.arg!r} — the codec re-raise path constructs "
                    f"cls(message)")
    return None


# ---------------------------------------------------- frame-kind exhaustiveness


@dataclass
class _Emitter:
    channel: str
    vias: Optional[frozenset]
    info: FuncInfo


@dataclass
class _Dispatcher:
    channel: str
    vias: frozenset
    info: FuncInfo
    kinds: set = field(default_factory=set)


def _annotation_lines(src: SourceFile, node: ast.AST) -> list[str]:
    return [src.line_text(node.lineno - 1), src.line_text(node.lineno)]


def _module_str_consts(tree: ast.Module) -> dict[str, str]:
    consts: dict[str, str] = {}
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Constant) \
                and isinstance(stmt.value.value, str):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    consts[t.id] = stmt.value.value
    return consts


def _kind_consts(expr: ast.expr, consts: dict[str, str]) -> list[str]:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return [expr.value]
    if isinstance(expr, ast.Name) and expr.id in consts:
        return [consts[expr.id]]
    if isinstance(expr, ast.IfExp):
        return (_kind_consts(expr.body, consts)
                + _kind_consts(expr.orelse, consts))
    if isinstance(expr, ast.Tuple):
        out = []
        for e in expr.elts:
            out.extend(_kind_consts(e, consts))
        return out
    return []


def _emitted_kinds(info: FuncInfo,
                   consts: dict[str, str]) -> list[tuple[str, int]]:
    """Frame kinds this function can put on the wire, with line numbers.
    Shapes: ``f(req_id, KIND, payload)`` (3+ positional args on a send
    attr), ``f((req_id, KIND, payload))`` (single 3-tuple arg), and
    ``self._call(KIND, ...)``."""
    out: list[tuple[str, int]] = []
    for call in info.calls:
        fn = call.func
        attr = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if attr == "_call" and call.args:
            for k in _kind_consts(call.args[0], consts):
                out.append((k, call.lineno))
        elif attr in _SEND_ATTRS:
            if len(call.args) >= 3:
                for k in _kind_consts(call.args[1], consts):
                    out.append((k, call.lineno))
            elif (len(call.args) == 1 and isinstance(call.args[0], ast.Tuple)
                    and len(call.args[0].elts) == 3):
                for k in _kind_consts(call.args[0].elts[1], consts):
                    out.append((k, call.lineno))
    return out


def _dispatched_kinds(info: FuncInfo, consts: dict[str, str]) -> set:
    kinds: set = set()
    for node in _walk_body(info.node.body):
        if not isinstance(node, ast.Compare) or not node.ops:
            continue
        if not (isinstance(node.left, ast.Name)
                and node.left.id in _DISPATCH_VARS):
            continue
        if not isinstance(node.ops[0], (ast.Eq, ast.NotEq, ast.In, ast.NotIn)):
            continue
        for k in _kind_consts(node.comparators[0], consts):
            kinds.add(k)
    return kinds


def _check_frames(prog: Program) -> list[Finding]:
    findings: list[Finding] = []
    consts_by_rel = {src.rel: _module_str_consts(tree)
                     for tree, src in prog.files}
    emitters: list[_Emitter] = []
    dispatchers: list[_Dispatcher] = []

    # class-level annotations fan out to every method (qualname prefix)
    class_emit: dict[tuple[str, str], tuple[str, Optional[frozenset]]] = {}
    for cname, defs in prog.classes.items():
        for rel, node in defs:
            src = next((s for _t, s in prog.files if s.rel == rel), None)
            if src is None:
                continue
            for line in _annotation_lines(src, node):
                m = _FRAME_EMIT_RE.search(line)
                if m:
                    vias = (frozenset(m.group(2).split(","))
                            if m.group(2) else None)
                    class_emit[(rel, cname)] = (m.group(1), vias)

    for key, info in prog.functions.items():
        src = info.src
        func_emits = False
        for line in _annotation_lines(src, info.node):
            matched = False
            m = _FRAME_EMIT_RE.search(line)
            if m:
                vias = (frozenset(m.group(2).split(","))
                        if m.group(2) else None)
                emitters.append(_Emitter(m.group(1), vias, info))
                func_emits = matched = True
            md = _FRAME_DISPATCH_RE.search(line)
            if md:
                d = _Dispatcher(md.group(1),
                                frozenset(md.group(2).split(",")), info)
                d.kinds = _dispatched_kinds(
                    info, consts_by_rel.get(info.module, {}))
                dispatchers.append(d)
                matched = True
            if _FRAME_ANY_RE.search(line) and not matched:
                f = src.finding(
                    RULE_PROTO, info.node.lineno,
                    "malformed frame annotation — expected "
                    "'# frame-emit: <channel> [via=a,b]' or "
                    "'# frame-dispatch: <channel> via=a,b'",
                )
                if f is not None:
                    findings.append(f)
        if not func_emits:
            top_cls = key[1].split(".", 1)[0]
            ce = class_emit.get((info.module, top_cls))
            if ce is not None and "." in key[1]:
                emitters.append(_Emitter(ce[0], ce[1], info))

    by_channel_disp: dict[str, list[_Dispatcher]] = {}
    for d in dispatchers:
        by_channel_disp.setdefault(d.channel, []).append(d)

    # emitted kinds per channel, deduped to the first emit site
    emitted: dict[tuple[str, str], tuple[FuncInfo, int, Optional[frozenset]]] = {}
    for e in emitters:
        consts = consts_by_rel.get(e.info.module, {})
        for kind, lineno in _emitted_kinds(e.info, consts):
            cur = emitted.get((e.channel, kind))
            site = (e.info, lineno, e.vias)
            if cur is None or (e.info.module, lineno) < (cur[0].module, cur[1]):
                emitted[(e.channel, kind)] = site

    for (channel, kind), (info, lineno, evias) in sorted(
            emitted.items(), key=lambda kv: (kv[0], kv[1][0].module, kv[1][1])):
        disp = by_channel_disp.get(channel, [])
        if not disp:
            f = info.src.finding(
                RULE_FRAME, lineno,
                f"channel {channel!r} emits frame kind {kind!r} but has no "
                f"# frame-dispatch annotation anywhere in the program",
            )
            if f is not None:
                findings.append(f)
            continue
        channel_vias = frozenset().union(*(d.vias for d in disp))
        vias = evias if evias is not None else channel_vias
        for via in sorted(vias - channel_vias):
            f = info.src.finding(
                RULE_PROTO, lineno,
                f"frame kind {kind!r} declares via={via} but no dispatcher "
                f"on channel {channel!r} serves that path",
            )
            if f is not None:
                findings.append(f)
        missing = sorted(
            via for via in (vias & channel_vias)
            if not any(via in d.vias and kind in d.kinds for d in disp)
        )
        if missing:
            served_by = ", ".join(sorted(d.info.key[1] for d in disp))
            f = info.src.finding(
                RULE_FRAME, lineno,
                f"frame kind {kind!r} (channel {channel!r}) has no "
                f"dispatcher branch on the {'/'.join(missing)} receive "
                f"path — a one-sided frame kind is a runtime "
                f"FrameProtocolError waiting to happen (dispatchers: "
                f"{served_by})",
            )
            if f is not None:
                findings.append(f)
    return findings


# -------------------------------------------------------------------- the rule


def check_failures(prog: Program) -> list[Finding]:
    parents = _class_parents(prog)
    typed = _typed_universe(prog, parents)
    known = frozenset(prog.classes) | _BUILTIN_EXCS
    summaries = {key: _summarize(prog, info, known)
                 for key, info in prog.functions.items()}
    escapes = _escape_sets(prog, summaries, parents)
    boundaries = _discover_boundaries(prog)
    findings = _check_escapes(prog, typed, escapes, boundaries)
    findings.extend(_check_handlers(prog, typed))
    findings.extend(_check_codec(prog, typed))
    findings.extend(_check_frames(prog))
    return findings


# -------------------------------------------------------- boundary graph dump


def build_failure_graph(prog: Program) -> dict:
    """JSON view of the failure surface (``sentio lint --boundary-graph``):
    every serving boundary with the exception names that can escape to it
    (typed and untyped, with origins), plus the frame channels."""
    parents = _class_parents(prog)
    typed = _typed_universe(prog, parents)
    known = frozenset(prog.classes) | _BUILTIN_EXCS
    summaries = {key: _summarize(prog, info, known)
                 for key, info in prog.functions.items()}
    escapes = _escape_sets(prog, summaries, parents)
    boundaries = _discover_boundaries(prog)

    out_boundaries = []
    for b in sorted(boundaries, key=lambda b: (b.key[0], b.key[1])):
        info = prog.functions[b.key]
        esc = {}
        for name, (opath, oline) in sorted(escapes.get(b.key, {}).items()):
            esc[name] = {
                "origin": f"{opath}:{oline}",
                "typed": name in typed,
            }
        out_boundaries.append({
            "qualname": b.key[1],
            "path": b.key[0],
            "line": info.node.lineno,
            "kind": b.kind,
            "escapes": esc,
        })

    consts_by_rel = {src.rel: _module_str_consts(tree)
                     for tree, src in prog.files}
    channels: dict[str, dict] = {}
    for key, info in prog.functions.items():
        for line in _annotation_lines(info.src, info.node):
            md = _FRAME_DISPATCH_RE.search(line)
            if md:
                ch = channels.setdefault(
                    md.group(1), {"emits": {}, "dispatchers": []})
                ch["dispatchers"].append({
                    "qualname": key[1],
                    "path": key[0],
                    "vias": sorted(md.group(2).split(",")),
                    "handles": sorted(_dispatched_kinds(
                        info, consts_by_rel.get(info.module, {}))),
                })
    # reuse the emitter fan-out from the checker by re-walking annotations
    class_emit: dict[tuple[str, str], str] = {}
    for cname, defs in prog.classes.items():
        for rel, node in defs:
            src = next((s for _t, s in prog.files if s.rel == rel), None)
            if src is None:
                continue
            for line in _annotation_lines(src, node):
                m = _FRAME_EMIT_RE.search(line)
                if m:
                    class_emit[(rel, cname)] = m.group(1)
    for key, info in prog.functions.items():
        channel = None
        for line in _annotation_lines(info.src, info.node):
            m = _FRAME_EMIT_RE.search(line)
            if m:
                channel = m.group(1)
        if channel is None and "." in key[1]:
            channel = class_emit.get((info.module, key[1].split(".", 1)[0]))
        if channel is None:
            continue
        ch = channels.setdefault(channel, {"emits": {}, "dispatchers": []})
        consts = consts_by_rel.get(info.module, {})
        for kind, lineno in _emitted_kinds(info, consts):
            ch["emits"].setdefault(kind, []).append(f"{key[0]}:{lineno}")
    for ch in channels.values():
        ch["emits"] = {k: sorted(v) for k, v in sorted(ch["emits"].items())}
        ch["dispatchers"].sort(key=lambda d: (d["path"], d["qualname"]))

    return {
        "typed": sorted(typed),
        "boundaries": out_boundaries,
        "channels": dict(sorted(channels.items())),
    }


# ------------------------------------------------------- fault-point crossref


def collect_fault_points(
    files: list[tuple[ast.Module, SourceFile]],
) -> dict[str, list[str]]:
    """Every ``faults.hit("<name>")`` / ``hit_frame`` injection point in
    the tree → plant sites. ``SocketTransport._hit("send"/"recv")`` plants
    the dynamic ``transport.<op>[.<scope>]`` family — recorded under its
    static ``transport.<op>`` base name."""
    points: dict[str, list[str]] = {}
    for tree, src in files:
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            fn = node.func
            attr = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                continue
            if attr in ("hit", "hit_frame"):
                points.setdefault(arg.value, []).append(
                    f"{src.rel}:{node.lineno}")
            elif attr == "_hit":
                points.setdefault(f"transport.{arg.value}", []).append(
                    f"{src.rel}:{node.lineno}")
    return {k: sorted(v) for k, v in sorted(points.items())}


def collect_armed_points(
    files: list[tuple[ast.Module, SourceFile]],
) -> dict[str, list[str]]:
    """Every fault point a test arms: ``faults.arm(...)``,
    ``faults.inject(...)`` context managers, and worker-RPC
    ``inject_fault(...)`` calls. Scoped arms (``transport.recv.r0``)
    count toward their ``transport.recv`` base point."""
    armed: dict[str, list[str]] = {}
    for tree, src in files:
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            fn = node.func
            attr = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            if attr not in ("arm", "inject", "inject_fault"):
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                continue
            name = arg.value
            # scoped arms ("transport.recv.r0") credit their base point
            if name.count(".") >= 2:
                name = ".".join(name.split(".")[:2])
            armed.setdefault(name, []).append(
                f"{src.rel}:{node.lineno}")
    return {k: sorted(v) for k, v in sorted(armed.items())}


def fault_point_inventory() -> dict:
    """The committed chaos-coverage map (``analysis/fault_points.json``):
    every injection point planted in the package, and the test files that
    arm it. File-level (line numbers churn too fast to commit); the
    tier-1 cross-reference test regenerates and compares."""
    import json as _json  # noqa: F401 — re-exported for the __main__ dump

    from sentio_tpu.analysis.runner import PACKAGE_ROOT, REPO_ROOT, parse_paths

    pkg, _errs = parse_paths([PACKAGE_ROOT])
    tests, _errs = parse_paths([REPO_ROOT / "tests"])
    points = collect_fault_points(pkg)
    armed = collect_armed_points(tests)
    return {
        "points": {k: sorted({s.rsplit(":", 1)[0] for s in v})
                   for k, v in points.items()},
        "armed_by": {k: sorted({s.rsplit(":", 1)[0] for s in v})
                     for k, v in armed.items() if k in points},
    }


if __name__ == "__main__":  # pragma: no cover — `python -m ...failures`
    import json

    print(json.dumps(fault_point_inventory(), indent=1))
