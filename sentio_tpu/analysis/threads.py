"""Thread-role model + cross-thread race rule (whole-program).

The serving tier is deeply multithreaded (pump, supervisor, socket
dispatcher, registry accepter, telemetry/status loops, detached verify,
warmup/drain workers ...) but :mod:`.locks` checks lock discipline
*lexically* and *file-locally*: it can say "this access holds the lock"
but not "which threads can get here at all". This module upgrades the
model from lexical to call-graph-aware:

* **thread-role registry** (``thread-role`` rule) — every
  ``threading.Thread(...)`` construction must carry a ``name=`` that
  resolves to a role in the bounded :data:`ROLE_REGISTRY` (pattern match
  on the statically-resolvable part of the name, or an explicit
  ``# thread-role: <role>`` comment on the construction for dynamic
  names). An unnamed or unregistered spawn is a finding: anonymous
  threads are invisible to every downstream concurrency rule.

* **intra-package call graph** — every ``def`` in the linted program is
  a node; edges come from ``self.method()`` calls (with single-level
  base-class resolution), bare-name calls through the lexical scope
  chain (closures included — warmup/drain workers are closures), calls
  through ``from pkg.mod import fn`` / ``import pkg.mod as alias``
  imports, and ``obj.method()`` calls whose method name is defined by
  exactly one class in the program (and is not a generic verb). Passing
  a function as a *value* (``target=self._run``) is NOT a call edge —
  that reference is what creates a role, below.

* **role reachability** — from each spawn's ``target`` the call graph
  yields the set of functions that role can execute. Everything
  reachable from the public surface (non-underscore functions/methods
  and dunders) additionally carries the pseudo-role ``caller``: the
  main thread, API handlers, and test drivers all enter there.

* **cross-thread race rule** (``cross-thread-race``) — a ``self.<attr>``
  mutated (assigned, aug-assigned, subscript-stored, or hit with a
  mutating container method) outside ``__init__`` from functions whose
  role sets union to ≥ 2 roles, with no ``guarded-by`` annotation, is a
  finding: two threads can write it and no lock is even *declared*. An
  attribute annotated with a :data:`~.locks.THREAD_LOCKS` owner
  (``engine-thread`` / ``pump-thread``) that is *accessed at all* from a
  role outside the owner set is likewise a finding — thread-ownership
  is only sound if foreign roles provably cannot reach the attribute.

The model is deliberately an under-approximation (unresolvable dynamic
calls produce no edges), so every finding corresponds to a concrete
spawn-to-access path; missing edges cost recall, never precision.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Optional

from sentio_tpu.analysis.findings import Finding, SourceFile
from sentio_tpu.analysis.locks import GuardedClass, collect_guarded

__all__ = [
    "ROLE_REGISTRY",
    "CALLER_ROLE",
    "Program",
    "build_program",
    "check_thread_model",
]

RULE_ROLE = "thread-role"
RULE_RACE = "cross-thread-race"

#: Pseudo-role carried by everything reachable from the public surface:
#: the main thread, HTTP handlers, and test/bench drivers.
CALLER_ROLE = "caller"

#: The bounded role registry: role -> thread-name patterns (``*`` matches
#: any run of characters). A spawn whose ``name=`` matches no pattern and
#: carries no ``# thread-role:`` annotation is a ``thread-role`` finding.
ROLE_REGISTRY: dict[str, tuple[str, ...]] = {
    "pump": ("paged-decode-pump",),
    "supervisor": ("replica-supervisor",),
    "dispatcher": ("replica-worker-rx-*",),
    "rpc": ("worker-rpc-*",),
    "accepter": ("worker-registry-accept", "worker-registry-handshake",
                 "worker-serve-conn"),
    "autoscaler": ("fleet-autoscaler",),
    "telemetry": ("worker-telemetry",),
    "status": ("worker-status",),
    "detached-verify": ("graph-detached-*",),
    "warmup": ("replica-warmup-*", "paged-warmup-*"),
    "drain": ("replica-drain-*",),
    "batcher": ("thread-batcher", "*-batcher"),
    "health-probe": ("qdrant-health-*", "replica-worker-ping-*"),
    "rebuild": ("replica-rebuild-*",),
    "eval-worker": ("eval-worker-*",),
    "cache-fill": ("embedder-cache-fill",),
    "stamper": ("device-stamper",),
    "mock-api": ("mock-model-api",),
}

#: Thread-ownership annotations (locks.THREAD_LOCKS) -> roles allowed to
#: touch the attribute. ``caller`` is always allowed: tests and bench
#: drive the engine from the main thread, and the runtime sanitizer's
#: ThreadGuard enforces the single-driver handoff dynamically.
THREAD_OWNER_ROLES: dict[str, frozenset[str]] = {
    "engine-thread": frozenset({"pump", CALLER_ROLE}),
    "pump-thread": frozenset({"pump", CALLER_ROLE}),
}

_THREAD_ROLE_RE = re.compile(r"#\s*thread-role:\s*([\w-]+)")

# obj.method() calls resolve through the program-wide method index only
# when the name is unambiguous AND not one of these generic verbs — a
# `.close()` matching some unrelated class would wire fantasy edges.
_GENERIC_METHODS = frozenset({
    "get", "put", "set", "add", "pop", "close", "open", "start", "stop",
    "run", "join", "wait", "send", "recv", "read", "write", "append",
    "clear", "update", "items", "keys", "values", "acquire", "release",
    "submit", "step", "generate", "encode", "decode", "flush", "reset",
    "copy", "next", "result", "cancel", "done", "info", "warning",
    "error", "debug", "exception", "search", "match", "group", "strip",
    "split", "lower", "upper", "format", "remove", "insert", "extend",
    "count", "index", "sort", "setdefault", "discard", "notify",
    "notify_all", "is_alive", "is_set", "empty", "name",
    "cleanup", "setup", "shutdown", "terminate", "kill", "connect",
    "disconnect", "listen", "accept", "handle", "apply", "fetch", "load",
    "save", "dump", "emit", "poll", "push", "pull", "peek", "ping",
    "stat", "stats", "item", "mean", "sum", "max", "min", "all", "any",
    "tolist", "astype", "serve_forever", "invoke", "render", "build",
})

# container-mutating method names: `self.attr.append(x)` counts as a
# mutation of `attr` for the race rule
_MUTATORS = frozenset({
    "append", "extend", "insert", "pop", "popleft", "appendleft",
    "remove", "clear", "add", "discard", "update", "setdefault",
    "__setitem__", "__delitem__", "sort", "reverse", "rotate",
})


# --------------------------------------------------------------- model types


FuncKey = tuple[str, str]  # (repo-relative path, dotted qualname)


@dataclass
class FuncInfo:
    key: FuncKey
    name: str
    module: str                       # repo-relative path
    class_name: Optional[str]         # innermost enclosing class
    node: ast.AST
    src: SourceFile
    visible: dict[str, FuncKey]       # lexically visible callables
    # self.<attr> accesses in the IMMEDIATE body (nested defs excluded —
    # they are their own FuncInfo, sharing class_name through the closure)
    writes: dict[str, list[int]] = field(default_factory=dict)
    reads: dict[str, list[int]] = field(default_factory=dict)
    calls: list[ast.Call] = field(default_factory=list)
    withs: list[ast.With] = field(default_factory=list)


@dataclass
class ThreadSpawn:
    src: SourceFile
    lineno: int
    in_class: Optional[str]
    name_pattern: Optional[str]   # resolved name ('*' for dynamic parts)
    role: Optional[str]
    annotation: Optional[str]     # explicit # thread-role: value
    target_key: Optional[FuncKey]
    unnamed: bool = False


@dataclass
class Program:
    """Whole-program view shared by the thread-role and lock-order rules."""

    files: list[tuple[ast.Module, SourceFile]]
    functions: dict[FuncKey, FuncInfo] = field(default_factory=dict)
    edges: dict[FuncKey, set[FuncKey]] = field(default_factory=dict)
    spawns: list[ThreadSpawn] = field(default_factory=list)
    # (module rel, class name) -> guarded annotations for that class
    guarded: dict[tuple[str, str], GuardedClass] = field(default_factory=dict)
    # class name -> [(module rel, ClassDef)] across the program
    classes: dict[str, list[tuple[str, ast.ClassDef]]] = field(default_factory=dict)
    # function role sets (filled by _assign_roles)
    func_roles: dict[FuncKey, set[str]] = field(default_factory=dict)
    # module-level lock names per module (for lockorder): name -> lock id
    module_locks: dict[str, dict[str, str]] = field(default_factory=dict)
    # direct-method name -> keys of every class method with that name
    method_index: dict[str, list[FuncKey]] = field(default_factory=dict)

    def roles_of(self, key: FuncKey) -> set[str]:
        return self.func_roles.get(key, set())


# ------------------------------------------------------------ name matching


def _pattern_to_regex(pattern: str) -> re.Pattern:
    return re.compile(
        "".join(".*" if ch == "*" else re.escape(ch) for ch in pattern) + r"\Z"
    )


_ROLE_PATTERNS = [
    (role, _pattern_to_regex(p))
    for role, pats in ROLE_REGISTRY.items()
    for p in pats
]


def resolve_role(name_pattern: str) -> Optional[str]:
    """Match a (possibly wildcarded) thread name against the registry.
    ``*`` in the candidate stands for a runtime-formatted segment; it is
    encoded as a char the registry's own wildcards match."""
    probe = name_pattern.replace("*", "\x00")  # '.*' matches the marker
    for role, rx in _ROLE_PATTERNS:
        if rx.match(probe):
            return role
    return None


def _static_name(expr: ast.expr) -> Optional[str]:
    """Resolve a thread ``name=`` expression to a wildcard pattern, or
    None when nothing about it is static."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    if isinstance(expr, ast.JoinedStr):
        parts = []
        for v in expr.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            else:
                parts.append("*")
        pat = "".join(parts)
        return pat if pat.strip("*") else None
    return None


def _thread_role_annotation(src: SourceFile, node: ast.AST) -> Optional[str]:
    for line in range(node.lineno, getattr(node, "end_lineno", node.lineno) + 1):
        m = _THREAD_ROLE_RE.search(src.line_text(line))
        if m:
            return m.group(1)
    return None


# ------------------------------------------------------------ program build


def _module_dotted(rel: str) -> Optional[str]:
    if not rel.endswith(".py"):
        return None
    parts = rel[:-3].split("/")
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class _ModuleIndex:
    """Per-module symbol tables used during edge resolution."""

    def __init__(self) -> None:
        self.funcs: dict[str, FuncKey] = {}          # module-level defs
        self.import_funcs: dict[str, tuple[str, str]] = {}  # name -> (dotted mod, attr)
        self.import_mods: dict[str, str] = {}        # alias -> dotted module
        self.locks: dict[str, str] = {}              # module-level lock names


def _is_lock_factory(call: ast.expr) -> bool:
    if not isinstance(call, ast.Call):
        return False
    fn = call.func
    name = fn.attr if isinstance(fn, ast.Attribute) else (
        fn.id if isinstance(fn, ast.Name) else "")
    return name in ("Lock", "RLock", "Condition", "make_lock")


def build_program(files: list[tuple[ast.Module, SourceFile]]) -> Program:
    prog = Program(files=files)
    mod_index: dict[str, _ModuleIndex] = {}
    dotted_to_rel: dict[str, str] = {}
    for _tree, src in files:
        dotted = _module_dotted(src.rel)
        if dotted:
            dotted_to_rel[dotted] = src.rel

    # ---- pass 1: symbols, functions, classes, guarded annotations
    for tree, src in files:
        idx = _ModuleIndex()
        mod_index[src.rel] = idx
        for cls_name, gc in collect_guarded(tree, src).items():
            prog.guarded[(src.rel, cls_name)] = gc
        for stmt in tree.body:
            if isinstance(stmt, ast.Import):
                for alias in stmt.names:
                    idx.import_mods[alias.asname or alias.name.split(".")[0]] = (
                        alias.name
                    )
            elif isinstance(stmt, ast.ImportFrom) and stmt.module:
                for alias in stmt.names:
                    idx.import_funcs[alias.asname or alias.name] = (
                        stmt.module, alias.name
                    )
            elif isinstance(stmt, ast.Assign) and _is_lock_factory(stmt.value):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        stem = src.rel.rsplit("/", 1)[-1][:-3]
                        idx.locks[t.id] = f"{stem}.{t.id}"
        prog.module_locks[src.rel] = idx.locks

        def register(node: ast.AST, qual: list[str], cls: Optional[str],
                     visible: dict[str, FuncKey]) -> None:
            for child in (node.body if hasattr(node, "body") else []):
                if isinstance(child, ast.ClassDef):
                    prog.classes.setdefault(child.name, []).append(
                        (src.rel, child))
                    register(child, qual + [child.name], child.name, dict(visible))
                elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    key = (src.rel, ".".join(qual + [child.name]))
                    # nested defs are visible to siblings defined later AND
                    # earlier (runtime order rarely matters for our reach)
                    visible[child.name] = key
                    if not qual:
                        idx.funcs[child.name] = key
                    info = FuncInfo(
                        key=key, name=child.name, module=src.rel,
                        class_name=cls, node=child, src=src,
                        visible=dict(visible),
                    )
                    prog.functions[key] = info
                    register(child, qual + [child.name], cls, info.visible)
                elif isinstance(child, (ast.If, ast.Try)):
                    register(child, qual, cls, visible)

        register(tree, [], None, {})

    # two-phase sibling visibility: a def earlier in a scope must see defs
    # later in the same scope (mutual recursion) — rebuild visible maps by
    # merging every sibling registered under the same parent scope
    by_scope: dict[tuple[str, str], dict[str, FuncKey]] = {}
    for key, info in prog.functions.items():
        scope = (info.module, key[1].rsplit(".", 1)[0] if "." in key[1] else "")
        by_scope.setdefault(scope, {})[info.name] = key
    for key, info in prog.functions.items():
        scope = (info.module, key[1].rsplit(".", 1)[0] if "." in key[1] else "")
        info.visible.update(by_scope.get(scope, {}))

    for key, f in prog.functions.items():
        if f.class_name and key[1] == f"{f.class_name}.{f.name}":
            prog.method_index.setdefault(f.name, []).append(key)

    # ---- pass 2: per-function bodies — accesses, calls, withs, spawns
    for tree, src in files:
        for key, info in prog.functions.items():
            if info.module != src.rel:
                continue
            _scan_body(prog, info)

    # ---- pass 3: call edges + spawn targets
    for key, info in prog.functions.items():
        out = prog.edges.setdefault(key, set())
        for call in info.calls:
            callee = _resolve_call(prog, mod_index, dotted_to_rel, info,
                                   call.func)
            if callee is not None:
                out.add(callee)
            spawn = _extract_spawn(prog, mod_index, dotted_to_rel, info, call)
            if spawn is not None:
                prog.spawns.append(spawn)

    _assign_roles(prog)
    return prog


def _scan_body(prog: Program, info: FuncInfo) -> None:
    """Collect self-attribute accesses / calls / withs from the immediate
    body of one function (nested defs excluded)."""

    def visit(node: ast.AST) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return  # separate FuncInfo / opaque
        if isinstance(node, ast.Call):
            info.calls.append(node)
            fn = node.func
            # self.attr.append(...) — a container mutation of attr
            if (isinstance(fn, ast.Attribute) and fn.attr in _MUTATORS
                    and isinstance(fn.value, ast.Attribute)
                    and isinstance(fn.value.value, ast.Name)
                    and fn.value.value.id == "self"):
                info.writes.setdefault(fn.value.attr, []).append(fn.lineno)
        if isinstance(node, (ast.With, ast.AsyncWith)):
            info.withs.append(node)
        if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            if isinstance(node.ctx, (ast.Store, ast.Del)):
                info.writes.setdefault(node.attr, []).append(node.lineno)
            else:
                info.reads.setdefault(node.attr, []).append(node.lineno)
        # self.attr[k] = v mutates attr even though attr is a Load
        if isinstance(node, ast.Subscript) and isinstance(node.ctx, (ast.Store, ast.Del)):
            tgt = node.value
            if (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"):
                info.writes.setdefault(tgt.attr, []).append(node.lineno)
        for child in ast.iter_child_nodes(node):
            visit(child)

    node = info.node
    for child in ast.iter_child_nodes(node):
        visit(child)


def _method_on_class(prog: Program, module: str, cls_name: str,
                     meth: str, depth: int = 0) -> Optional[FuncKey]:
    """Resolve a method on a class, walking base classes by name (single
    inheritance chains, bounded depth)."""
    if depth > 4:
        return None
    candidates = prog.classes.get(cls_name, [])
    # prefer the class defined in the calling module (shadowed names)
    candidates = sorted(candidates, key=lambda rn: rn[0] != module)
    for rel, node in candidates:
        key = (rel, f"{node.name}.{meth}")
        if key in prog.functions:
            return key
        for base in node.bases:
            base_name = base.id if isinstance(base, ast.Name) else (
                base.attr if isinstance(base, ast.Attribute) else None)
            if base_name and base_name in prog.classes:
                found = _method_on_class(prog, rel, base_name, meth, depth + 1)
                if found:
                    return found
    return None


def _resolve_call(prog: Program, mod_index: dict[str, _ModuleIndex],
                  dotted_to_rel: dict[str, str], info: FuncInfo,
                  fn: ast.expr) -> Optional[FuncKey]:
    idx = mod_index[info.module]
    if isinstance(fn, ast.Name):
        # lexical chain: closures/siblings, then module defs, then imports
        if fn.id in info.visible:
            return info.visible[fn.id]
        if fn.id in idx.funcs:
            return idx.funcs[fn.id]
        if fn.id in idx.import_funcs:
            dotted, attr = idx.import_funcs[fn.id]
            rel = dotted_to_rel.get(dotted)
            if rel:
                key = (rel, attr)
                if key in prog.functions:
                    return key
        return None
    if isinstance(fn, ast.Attribute):
        base = fn.value
        if isinstance(base, ast.Name):
            if base.id in ("self", "cls") and info.class_name:
                return _method_on_class(prog, info.module, info.class_name,
                                        fn.attr)
            if base.id in idx.import_mods:
                rel = dotted_to_rel.get(idx.import_mods[base.id])
                if rel:
                    key = (rel, fn.attr)
                    if key in prog.functions:
                        return key
                return None
            if base.id in prog.classes:
                return _method_on_class(prog, info.module, base.id, fn.attr)
        # obj.method(): unique-name resolution, generic verbs excluded
        if fn.attr in _GENERIC_METHODS or fn.attr.startswith("__"):
            return None
        owners = prog.method_index.get(fn.attr, [])
        if len(owners) == 1:
            return owners[0]
        return None
    return None


def _is_thread_ctor(fn: ast.expr) -> bool:
    if isinstance(fn, ast.Attribute) and fn.attr == "Thread":
        return isinstance(fn.value, ast.Name) and fn.value.id == "threading"
    return isinstance(fn, ast.Name) and fn.id == "Thread"


def _extract_spawn(prog: Program, mod_index: dict[str, _ModuleIndex],
                   dotted_to_rel: dict[str, str], info: FuncInfo,
                   call: ast.Call) -> Optional[ThreadSpawn]:
    if not _is_thread_ctor(call.func):
        return None
    name_expr = None
    target_expr = None
    for kw in call.keywords:
        if kw.arg == "name":
            name_expr = kw.value
        elif kw.arg == "target":
            target_expr = kw.value
    annotation = _thread_role_annotation(info.src, call)
    name_pattern = _static_name(name_expr) if name_expr is not None else None
    role = annotation or (resolve_role(name_pattern) if name_pattern else None)
    target_key = None
    if target_expr is not None:
        target_key = _resolve_call(prog, mod_index, dotted_to_rel, info,
                                   target_expr)
    return ThreadSpawn(
        src=info.src, lineno=call.lineno, in_class=info.class_name,
        name_pattern=name_pattern, role=role, annotation=annotation,
        target_key=target_key, unnamed=name_expr is None,
    )


def _assign_roles(prog: Program) -> None:
    """BFS role reachability from spawn targets + the public surface."""

    def reach(starts: set[FuncKey]) -> set[FuncKey]:
        seen = set(starts)
        stack = list(starts)
        while stack:
            k = stack.pop()
            for nxt in prog.edges.get(k, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append(nxt)
        return seen

    role_starts: dict[str, set[FuncKey]] = {}
    for spawn in prog.spawns:
        if spawn.role and spawn.target_key:
            role_starts.setdefault(spawn.role, set()).add(spawn.target_key)

    public = {
        k for k, f in prog.functions.items()
        if not f.name.startswith("_")
        or (f.name.startswith("__") and f.name.endswith("__"))
    }
    role_starts[CALLER_ROLE] = public

    for role, starts in role_starts.items():
        for k in reach(starts):
            prog.func_roles.setdefault(k, set()).add(role)


# ----------------------------------------------------------------- the rule


def check_thread_model(prog: Program) -> list[Finding]:
    findings: list[Finding] = []

    # --- rule 1: every spawn is named and registered
    for spawn in prog.spawns:
        if spawn.unnamed:
            f = spawn.src.finding(
                RULE_ROLE, spawn.lineno,
                "threading.Thread(...) without name= — anonymous threads "
                "are invisible to the role registry and every downstream "
                "concurrency rule; name it and register a role",
            )
        elif spawn.annotation and spawn.annotation not in ROLE_REGISTRY:
            f = spawn.src.finding(
                RULE_ROLE, spawn.lineno,
                f"# thread-role: {spawn.annotation} names a role outside "
                f"the bounded registry ({', '.join(sorted(ROLE_REGISTRY))})",
            )
        elif spawn.role is None:
            shown = spawn.name_pattern or "<dynamic>"
            f = spawn.src.finding(
                RULE_ROLE, spawn.lineno,
                f"thread name {shown!r} matches no pattern in the role "
                f"registry — add it to analysis/threads.py ROLE_REGISTRY "
                f"or annotate the spawn with # thread-role: <role>",
            )
        else:
            continue
        if f is not None:
            findings.append(f)

    # --- rule 2: cross-thread races on class attributes
    # group per (module, class): writes/reads by attr with role sets
    per_class: dict[tuple[str, str], dict[str, list[tuple[FuncInfo, int, bool]]]] = {}
    for info in prog.functions.values():
        if not info.class_name:
            continue
        if info.name in ("__init__", "__post_init__"):
            continue
        cls_key = (info.module, info.class_name)
        table = per_class.setdefault(cls_key, {})
        for attr, lines in info.writes.items():
            for ln in lines:
                table.setdefault(attr, []).append((info, ln, True))
        for attr, lines in info.reads.items():
            for ln in lines:
                table.setdefault(attr, []).append((info, ln, False))

    for (module, cls_name), table in sorted(per_class.items()):
        gc = prog.guarded.get((module, cls_name), GuardedClass(cls_name))
        src = next(
            (s for _t, s in prog.files if s.rel == module), None)
        if src is None:
            continue
        for attr, accesses in sorted(table.items()):
            if attr in gc.guarded:
                continue  # mutex-annotated: locks.py owns this attribute
            if attr in gc.thread_owned:
                owner = _owner_annotation(prog, module, cls_name, attr)
                allowed = THREAD_OWNER_ROLES.get(
                    owner or "", frozenset({CALLER_ROLE}))
                foreign = sorted({
                    r
                    for info, _ln, _w in accesses
                    for r in prog.roles_of(info.key)
                    if r not in allowed
                })
                if foreign:
                    first = min(
                        (ln for info, ln, _w in accesses
                         if prog.roles_of(info.key) - allowed),
                    )
                    f = src.finding(
                        RULE_RACE, first,
                        f"{cls_name}.{attr} is thread-owned "
                        f"(guarded-by: {owner}) but reachable from foreign "
                        f"role(s) {', '.join(foreign)} — thread ownership "
                        f"only holds if no other role can get here",
                    )
                    if f is not None:
                        findings.append(f)
                continue
            # unannotated: mutated from >= 2 roles?
            write_roles: set[str] = set()
            for info, _ln, is_write in accesses:
                if is_write:
                    write_roles |= prog.roles_of(info.key)
            if len(write_roles) >= 2:
                first = min(ln for _i, ln, w in accesses if w)
                f = src.finding(
                    RULE_RACE, first,
                    f"{cls_name}.{attr} mutated from roles "
                    f"{', '.join(sorted(write_roles))} with no guarded-by "
                    f"annotation — two threads can write it and no lock is "
                    f"declared; annotate it (and hold the lock) or confine "
                    f"it to one role",
                )
                if f is not None:
                    findings.append(f)
    return findings


def _owner_annotation(prog: Program, module: str, cls_name: str,
                      attr: str) -> Optional[str]:
    """Recover WHICH thread-lock annotation an attr carries (collect_guarded
    collapses them into one set)."""
    src = next((s for _t, s in prog.files if s.rel == module), None)
    if src is None:
        return None
    rx = re.compile(
        rf"self\.{re.escape(attr)}\s*[:=].*#\s*guarded-by:\s*([\w-]+)")
    for line in src.lines:
        m = rx.search(line)
        if m:
            return m.group(1)
    return None
