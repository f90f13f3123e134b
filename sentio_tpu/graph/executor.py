"""Minimal typed DAG executor — the framework's LangGraph replacement.

The reference assembles its pipeline as a LangGraph ``StateGraph`` with
conditional edges (/root/reference/src/core/graph/factory.py:94-188). We need
the same shape — named nodes over a shared state, static and conditional
edges, sync + async invocation — but with zero external deps and with stage
boundaries that double as host/TPU dispatch points (a node is free to await a
batched device call). Nodes return *partial* state updates; the executor
merges them, records per-node wall time, and never lets a node exception kill
the pipeline unless the node opts out of soft-fail.

Trace context: when ``metadata["query_id"]`` is set (the serving layer's
request id), the executor publishes the finished run's per-node timings and
path into the flight recorder (infra/flight.py), joining the graph stage
timeline with the decode engine's tick events under one id.
"""

from __future__ import annotations

import asyncio
import inspect
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable, Mapping, Optional, Union

from sentio_tpu.infra.exceptions import GraphError
from sentio_tpu.infra.tracing import span

logger = logging.getLogger(__name__)

END = "__end__"

# live detached-node threads (async verify): bench/eval/tests join them via
# wait_detached() before tearing the decode service down under their feet
_detached_lock = threading.Lock()
_detached_threads: list[threading.Thread] = []  # guarded-by: _detached_lock


def wait_detached(timeout_s: float = 30.0) -> bool:
    """Join every live detached-node thread (best effort, bounded by the
    shared ``timeout_s`` wall clock). Returns whether all finished. The
    serving path never calls this — detached nodes are fire-and-forget
    there — but anything that closes the decode service right after a
    graph run (bench sweeps, eval, tests) must, or the trailing verify
    decode races the shutdown."""
    deadline = time.perf_counter() + max(timeout_s, 0.0)
    while True:
        with _detached_lock:
            _detached_threads[:] = [t for t in _detached_threads if t.is_alive()]
            live = list(_detached_threads)
        if not live:
            return True
        if time.perf_counter() >= deadline:
            return False
        live[0].join(timeout=min(max(deadline - time.perf_counter(), 0.0), 0.5))

NodeFn = Callable[[dict], Union[Mapping[str, Any], Awaitable[Mapping[str, Any]], None]]
RouterFn = Callable[[dict], str]


@dataclass
class _Node:
    name: str
    fn: NodeFn
    soft_fail: bool = True
    # detached nodes run OFF the critical path: the executor snapshots the
    # state, launches the node on a daemon thread, stamps
    # metadata[f"{name}_pending"] = True, and follows the edge immediately.
    # The node's return value is discarded — a detached node communicates
    # through side effects (the async verify node writes its verdict to the
    # flight recorder, where /debug/flight/{id} serves it)
    detached: bool = False


@dataclass
class CompiledGraph:
    """An immutable, runnable pipeline. Build via :class:`GraphBuilder`."""

    nodes: dict[str, _Node]
    edges: dict[str, Union[str, RouterFn]]
    entry: str
    max_steps: int = 64

    async def ainvoke(self, state: dict, config: Optional[dict] = None) -> dict:
        state = dict(state)
        meta = dict(state.get("metadata", {}))
        if config:
            meta.setdefault("graph_config", dict(config))
        state["metadata"] = meta

        current = self.entry
        steps = 0
        path: list[str] = []
        while current != END:
            if current not in self.nodes:
                raise GraphError(f"unknown node {current!r} (path so far: {path})")
            steps += 1
            if steps > self.max_steps:
                raise GraphError(f"step limit {self.max_steps} exceeded; path: {path}")
            node = self.nodes[current]
            path.append(current)
            if node.detached:
                # off-critical-path stage (async verify): snapshot the state
                # so the thread never races later merges, launch, move on.
                # The answer does not wait for the audit — this edge is what
                # turns verify's ~500 ms from blocking latency into overlap.
                snapshot = dict(state)
                snapshot["metadata"] = dict(state.get("metadata", {}))
                thread = threading.Thread(
                    target=_run_detached, args=(node, snapshot),
                    name=f"graph-detached-{node.name}", daemon=True,
                )
                with _detached_lock:
                    _detached_threads[:] = [
                        t for t in _detached_threads if t.is_alive()
                    ]
                    _detached_threads.append(thread)
                thread.start()
                state = _merge(
                    state, {"metadata": {f"{node.name}_pending": True}}
                )
                edge = self.edges.get(current, END)
                current = edge(state) if callable(edge) else edge
                continue
            t0 = time.perf_counter()
            try:
                # span per node, carrying the trace id and (once the
                # generate node stamped it) the serving replica: the
                # request stages written inside the node hang under it
                query_id = meta.get("query_id")
                with span(
                    f"graph.{node.name}",
                    request_id=str(query_id) if query_id else None,
                    replica_id=int(state["metadata"].get("replica_id", -1)),
                ):
                    update = node.fn(state)
                    if inspect.isawaitable(update):
                        update = await update
            except Exception as exc:  # noqa: BLE001 — soft-fail ladder by design
                # typed shed/deadline errors opt OUT of soft-fail: turning a
                # 429/503/504 into a degraded 200 would hide overload from
                # the caller, whose retry-elsewhere is the correct response
                if not node.soft_fail or getattr(exc, "soft_fail_exempt", False):
                    raise
                logger.exception("node %s failed softly", node.name)
                update = {"metadata": {f"{node.name}_error": str(exc)}}
            dt_ms = (time.perf_counter() - t0) * 1000.0
            state = _merge(state, update)
            timings = dict(state["metadata"].get("node_timings_ms", {}))
            timings[node.name] = round(timings.get(node.name, 0.0) + dt_ms, 3)
            state["metadata"]["node_timings_ms"] = timings

            edge = self.edges.get(current, END)
            current = edge(state) if callable(edge) else edge
        state["metadata"]["graph_path"] = path
        request_id = state["metadata"].get("query_id")
        if request_id:
            try:
                from sentio_tpu.infra.flight import get_flight_recorder

                get_flight_recorder().add_node_timings(
                    str(request_id),
                    state["metadata"].get("node_timings_ms", {}),
                    graph_path=path,
                )
            except Exception:  # noqa: BLE001 — telemetry must not fail runs
                logger.debug("flight recording failed", exc_info=True)
        return state

    def invoke(self, state: dict, config: Optional[dict] = None) -> dict:
        """Sync entry point. Safe to call when no event loop is running."""
        return asyncio.run(self.ainvoke(state, config))


def _run_detached(node: _Node, state: dict) -> None:
    """Drive one detached node to completion on its own thread (its own
    event loop — the spawning loop is long gone by the time a slow audit
    decode finishes). Exceptions are logged, never propagated: the caller
    already has its answer."""
    meta = state.get("metadata", {})
    query_id = meta.get("query_id")
    try:
        with span(
            f"graph.{node.name}", detached=True,
            request_id=str(query_id) if query_id else None,
            replica_id=int(meta.get("replica_id", -1)),
        ):
            update = node.fn(state)
            if inspect.isawaitable(update):
                asyncio.run(_await_detached(update))
    except Exception:  # noqa: BLE001 — off-path stage must not crash anything
        logger.exception("detached node %s failed", node.name)


async def _await_detached(awaitable) -> None:
    await awaitable


def _merge(state: dict, update: Optional[Mapping[str, Any]]) -> dict:
    if not update:
        return state
    new = dict(state)
    for key, value in update.items():
        if key == "metadata" and isinstance(value, Mapping):
            meta = dict(new.get("metadata", {}))
            meta.update(value)
            new["metadata"] = meta
        else:
            new[key] = value
    return new


@dataclass
class GraphBuilder:
    """Fluent builder mirroring the reference's StateGraph assembly surface:
    ``add_node`` / ``add_edge`` / ``add_conditional_edge`` / ``set_entry``."""

    _nodes: dict[str, _Node] = field(default_factory=dict)
    _edges: dict[str, Union[str, RouterFn]] = field(default_factory=dict)
    _entry: Optional[str] = None
    max_steps: int = 64

    def add_node(self, name: str, fn: NodeFn, soft_fail: bool = True,
                 detached: bool = False) -> "GraphBuilder":
        if name == END:
            raise GraphError(f"{END!r} is reserved")
        if name in self._nodes:
            raise GraphError(f"duplicate node {name!r}")
        self._nodes[name] = _Node(name, fn, soft_fail, detached)
        return self

    def add_edge(self, src: str, dst: str) -> "GraphBuilder":
        self._edges[src] = dst
        return self

    def add_conditional_edge(self, src: str, router: RouterFn) -> "GraphBuilder":
        self._edges[src] = router
        return self

    def set_entry(self, name: str) -> "GraphBuilder":
        self._entry = name
        return self

    def compile(self) -> CompiledGraph:
        if not self._entry:
            raise GraphError("no entry point set")
        if self._entry not in self._nodes:
            raise GraphError(f"entry {self._entry!r} is not a node")
        for src, edge in self._edges.items():
            if src not in self._nodes:
                raise GraphError(f"edge from unknown node {src!r}")
            if isinstance(edge, str) and edge != END and edge not in self._nodes:
                raise GraphError(f"edge to unknown node {edge!r}")
        return CompiledGraph(
            nodes=dict(self._nodes),
            edges=dict(self._edges),
            entry=self._entry,
            max_steps=self.max_steps,
        )
