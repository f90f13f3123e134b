"""Deadline-based request coalescing for TPU dispatch.

The reference's concurrency story is a connection pool to external services
(/root/reference/src/core/vector_store/async_qdrant_store.py:50-266). On TPU
the equivalent primitive is a *batcher*: concurrent requests (embed / rerank /
generate) are coalesced into one padded device batch so the MXU sees large
matmuls, with a deadline bound (default ~8 ms) so p50 latency doesn't pay for
occupancy. One compiled program per bucketed batch size; the batcher rounds
up to the bucket and the model side masks padding.
"""

from __future__ import annotations

import asyncio
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable, Generic, Optional, Sequence, TypeVar

T = TypeVar("T")
R = TypeVar("R")

ProcessFn = Callable[[list[T]], Awaitable[Sequence[R]]]


class BatcherClosed(Exception):
    pass


class BatcherTimeout(Exception):
    pass


@dataclass
class BatcherStats:
    batches: int = 0
    items: int = 0
    errors: int = 0
    occupancy_sum: float = 0.0
    wait_ms_sum: float = 0.0

    def snapshot(self) -> dict[str, float]:
        return {
            "batches": self.batches,
            "items": self.items,
            "errors": self.errors,
            "avg_occupancy": round(self.occupancy_sum / self.batches, 3) if self.batches else 0.0,
            "avg_wait_ms": round(self.wait_ms_sum / self.items, 3) if self.items else 0.0,
        }


@dataclass
class _Pending(Generic[T, R]):
    item: T
    future: "asyncio.Future[R]"
    enqueued_at: float = field(default_factory=time.perf_counter)


class Batcher(Generic[T, R]):
    """Coalesces awaited ``submit`` calls into batched ``process_fn`` calls.

    ``process_fn`` receives a list of items (1 <= n <= max_size) and must
    return one result per item, in order. A failing batch fails only the
    futures in that batch — the batcher itself stays up (circuit breaking
    happens a layer above, like the reference's resilience ladder).
    """

    def __init__(
        self,
        process_fn: ProcessFn,
        max_size: int = 8,
        deadline_ms: float = 8.0,
        name: str = "batcher",
    ) -> None:
        if max_size < 1:
            raise ValueError("max_size must be >= 1")
        self.process_fn = process_fn
        self.max_size = max_size
        self.deadline_s = max(deadline_ms, 0.0) / 1000.0
        self.name = name
        self.stats = BatcherStats()
        self._queue: asyncio.Queue[Optional[_Pending[T, R]]] = asyncio.Queue()
        self._worker: Optional[asyncio.Task] = None
        self._closed = False

    # ---------------------------------------------------------------- public

    async def submit(self, item: T) -> R:
        if self._closed:
            raise BatcherClosed(f"{self.name} is closed")
        self._ensure_worker()
        pending: _Pending[T, R] = _Pending(item, asyncio.get_running_loop().create_future())
        await self._queue.put(pending)
        return await pending.future

    async def close(self) -> None:
        self._closed = True
        if self._worker is not None:
            await self._queue.put(None)
            await self._worker
            self._worker = None

    # --------------------------------------------------------------- worker

    def _ensure_worker(self) -> None:
        if self._worker is None or self._worker.done():
            self._worker = asyncio.get_running_loop().create_task(self._run())

    async def _run(self) -> None:
        while True:
            head = await self._queue.get()
            if head is None:
                return
            batch = [head]
            deadline = time.perf_counter() + self.deadline_s
            while len(batch) < self.max_size:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    nxt = await asyncio.wait_for(self._queue.get(), timeout=remaining)
                except asyncio.TimeoutError:
                    break
                if nxt is None:
                    await self._dispatch(batch)
                    return
                batch.append(nxt)
            await self._dispatch(batch)

    async def _dispatch(self, batch: list[_Pending[T, R]]) -> None:
        now = time.perf_counter()
        self.stats.batches += 1
        self.stats.items += len(batch)
        self.stats.occupancy_sum += len(batch) / self.max_size
        self.stats.wait_ms_sum += sum((now - p.enqueued_at) * 1000.0 for p in batch)
        try:
            results = await self.process_fn([p.item for p in batch])
            if len(results) != len(batch):
                raise RuntimeError(
                    f"{self.name}: process_fn returned {len(results)} results "
                    f"for {len(batch)} items"
                )
            for pending, result in zip(batch, results):
                if not pending.future.done():
                    pending.future.set_result(result)
        except Exception as exc:  # noqa: BLE001 — fail the batch, not the batcher
            self.stats.errors += 1
            for pending in batch:
                if not pending.future.done():
                    pending.future.set_exception(exc)


class _SyncPending(Generic[T, R]):
    __slots__ = ("item", "event", "result", "error", "enqueued_at")

    def __init__(self, item: T) -> None:
        self.item = item
        self.event = threading.Event()
        self.result: Optional[R] = None
        self.error: Optional[BaseException] = None
        self.enqueued_at = time.perf_counter()


class ThreadBatcher(Generic[T, R]):
    """Cross-THREAD deadline coalescer — the sync sibling of :class:`Batcher`.

    The serving pipeline runs synchronously on the server's request threads
    (one per request, ``DependencyContainer.request_threads``), so coalescing
    concurrent query embeddings / rerank scores into one padded device batch
    must happen below the event loop. ``submit`` blocks the calling thread
    until its result is ready; a single daemon dispatcher thread collects
    items for up to ``deadline_ms`` (or ``max_size``) and invokes the sync
    ``process_fn`` once per batch. Same contract as Batcher: one result per
    item, in order; a failing batch fails only its own callers.
    """

    def __init__(
        self,
        process_fn: Callable[[list[T]], Sequence[R]],
        max_size: int = 8,
        deadline_ms: float = 8.0,
        name: str = "thread-batcher",
        timeout_s: float = 120.0,
    ) -> None:
        if max_size < 1:
            raise ValueError("max_size must be >= 1")
        self.process_fn = process_fn
        self.max_size = max_size
        self.deadline_s = max(deadline_ms, 0.0) / 1000.0
        self.timeout_s = timeout_s
        self.name = name
        self.stats = BatcherStats()
        self._queue: deque[_SyncPending[T, R]] = deque()  # guarded-by: _cond
        self._cond = threading.Condition()
        self._worker: Optional[threading.Thread] = None
        self._closed = False

    def submit(self, item: T) -> R:
        pending: _SyncPending[T, R] = _SyncPending(item)
        with self._cond:
            if self._closed:
                raise BatcherClosed(f"{self.name} is closed")
            self._queue.append(pending)
            self._ensure_worker()
            self._cond.notify_all()
        # bounded wait: a wedged process_fn (device stall, hung compile) must
        # surface as an error the resilience ladder can degrade on, not
        # deadlock every serving worker thread forever
        if not pending.event.wait(self.timeout_s):
            # mark abandoned so the dispatcher drops it instead of burning a
            # device batch on a result nobody is waiting for
            pending.error = BatcherTimeout(
                f"{self.name}: batch did not complete within {self.timeout_s:.0f}s"
            )
            pending.event.set()
            raise pending.error
        if pending.error is not None:
            raise pending.error
        return pending.result  # type: ignore[return-value]

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._worker is not None:
            self._worker.join(timeout=5.0)
            self._worker = None

    def _ensure_worker(self) -> None:  # _cond held
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(  # thread-role: batcher
                target=self._run, name=self.name, daemon=True
            )
            self._worker.start()

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if not self._queue and self._closed:
                    return
                deadline = time.perf_counter() + self.deadline_s
                while len(self._queue) < self.max_size and not self._closed:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
                batch = []
                while self._queue and len(batch) < self.max_size:
                    pending = self._queue.popleft()
                    if not pending.event.is_set():  # skip timed-out waiters
                        batch.append(pending)
            if batch:
                self._dispatch(batch)

    def _dispatch(self, batch: list[_SyncPending[T, R]]) -> None:
        now = time.perf_counter()
        self.stats.batches += 1
        self.stats.items += len(batch)
        self.stats.occupancy_sum += len(batch) / self.max_size
        self.stats.wait_ms_sum += sum((now - p.enqueued_at) * 1000.0 for p in batch)
        try:
            results = self.process_fn([p.item for p in batch])
            if len(results) != len(batch):
                raise RuntimeError(
                    f"{self.name}: process_fn returned {len(results)} results "
                    f"for {len(batch)} items"
                )
            for pending, result in zip(batch, results):
                pending.result = result
                pending.event.set()
        except BaseException as exc:  # noqa: BLE001 — fail the batch, not the batcher
            self.stats.errors += 1
            for pending in batch:
                if not pending.event.is_set():
                    pending.error = exc
                    pending.event.set()
            # exiting exceptions must still exit: waiters are failed above,
            # but swallowing KeyboardInterrupt/SystemExit here would keep a
            # dying interpreter's worker thread spinning
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                raise


def bucket_size(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n (compile once per bucket, pad to it). When n
    exceeds every bucket the result is n itself — callers pad by
    ``bucket - n`` and that difference must never go negative; an exact-size
    compile is correct, just uncached."""
    for b in sorted(buckets):
        if n <= b:
            return b
    return n


def floor_bucket(n: int, buckets: Sequence[int]) -> int:
    """Largest bucket <= n (min(buckets) if none fit) — for quantities that
    must round DOWN, like decode step counts bounded by cache headroom."""
    best = min(buckets)
    for b in sorted(buckets):
        if b <= n:
            best = b
    return best
