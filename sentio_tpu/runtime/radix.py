"""Radix prefix cache: automatic multi-prefix KV reuse over the paged pool.

RadixAttention-style (SGLang, Zheng et al. 2024) prefix sharing layered on
the PagedAttention page pool (Kwon et al. 2023): a token-id radix tree whose
edges own runs of **full KV pages**. Admission does a longest-prefix match,
reuses the matched pages read-only, and prefills only the unmatched suffix;
every admitted prompt's full-page span is inserted back, so the tree learns
the workload's shared heads (system prompt, retrieved context, the
generate-prompt head the verify prompt embeds) with no registration step.

Design constraints that shape the structure:

* **page granularity everywhere** — pages are the pool's unit of sharing,
  so edges hold whole pages and nodes split only at page boundaries; a
  divergence inside a page means that page simply isn't shared. Children
  are keyed by their edge's FIRST PAGE of tokens (a tuple), since two
  siblings may agree on a first token but diverge later in the page.
* **refcount pinning** — a live slot locks the node chain covering the
  pages its table references; eviction only ever touches refcount-0
  leaves, so a shared page can never be freed (and reallocated, and
  scribbled over) while any in-flight sequence still attends to it.
* **LRU under pressure** — when the engine needs pages it evicts unpinned
  leaves oldest-touch-first (a touch is a match walking through the node),
  cascading upward as parents become leaves.

Single-threaded by contract, like the engine that owns it: only the pump
thread calls in. The tree never talks to the device — it tracks integer
page ids; the engine orders actual KV writes via its dispatch sequence.

**Prior-prefix admissions** (resume-by-replay, runtime/replica.py): a
resumed stream re-admits with its delivered tokens appended after the
prompt, so the token sequences this tree matches and inserts are NOT
always pure prompts — they may embed generated continuations. Nothing in
the tree distinguishes the two (tokens are tokens), which is exactly what
makes the replay cheap: when the dead stream's prompt pages were already
cached here, the resume admission matches them and prefills only the
delivered suffix; the insert afterwards caches prompt+delivered, so a
SECOND resume of the same stream (a flapping replica) is a full-prefix
hit. Eviction, pinning, and page accounting are oblivious to the origin
of the tokens — the conservation invariants hold unchanged.

**Snapshots** (a family whose layers carry a STATE beside the pages,
``models/nemotron_h.py``): K and V of a cached page serve any prompt that
shares the page, but a state layer needs every token before the point a
prompt starts from — so a prefix can only be served up to a page boundary at
which the STATE was kept too. A state is far larger than the page it ends
(fifty times, at that model's widths), so pages do not own one by right: the
engine holds a bounded pool of ``snapshots`` slots on the device and this tree
says which page boundary owns which slot (``RadixNode.snaps``).
:meth:`match_state` cuts a match back to the deepest matched boundary that has
a snapshot; the engine recomputes the rest and attaches snapshots where its
prefill passed (:meth:`snap_alloc`, :meth:`snap_attach`). A snapshot leaves on
its own LRU without its pages (the next match is cut back further, and writes
it again), and with its page when the page is evicted. A slot that is about
to START from a snapshot pins it until its prefill is dispatched: dispatches
run in order on the device, so a later overwrite of the slot cannot pass the
read.
"""

from __future__ import annotations

import heapq
import itertools
from typing import Optional, Sequence

__all__ = ["RadixNode", "RadixPrefixCache"]


class RadixNode:
    """One edge of the tree: ``tokens`` (length a multiple of page_size)
    backed by ``pages`` (one id per page_size tokens)."""

    __slots__ = ("tokens", "pages", "children", "parent", "refcount",
                 "last_used", "snaps")

    def __init__(self, tokens: list[int], pages: list[int],
                 parent: Optional["RadixNode"]) -> None:
        self.tokens = tokens
        self.pages = pages
        self.children: dict[tuple, "RadixNode"] = {}
        self.parent = parent
        self.refcount = 0
        self.last_used = 0
        # page boundary inside this edge (1 = after its first page) → the
        # snapshot slot that holds the state AT that boundary
        self.snaps: dict[int, int] = {}

    def __repr__(self) -> str:  # debugging aid only
        return (f"RadixNode(tokens={len(self.tokens)}, pages={self.pages}, "
                f"rc={self.refcount}, children={len(self.children)})")


class RadixPrefixCache:
    """Token-id radix tree over page-aligned KV page runs.

    The cache OWNS the pages held by its nodes: the engine transfers
    ownership on :meth:`insert` (donated pages are no longer freed at slot
    retirement) and gets them back via :meth:`evict`, which returns freed
    ids to the allocator.
    """

    def __init__(self, page_size: int, allocator, snapshots: int = 0) -> None:
        self.page_size = page_size
        self.allocator = allocator
        self.root = RadixNode([], [], None)  # guarded-by: engine-thread
        self.pages_held = 0  # guarded-by: engine-thread
        self.node_count = 0  # guarded-by: engine-thread
        self.evicted_pages = 0  # guarded-by: engine-thread
        self._clock = itertools.count(1)
        # the snapshot pool's slots (module docstring): free ones, and of each
        # one handed out [node or None while its state is being written,
        # boundary in the node's edge, last use, pins]
        self.snapshots = snapshots
        self._snap_free = list(range(snapshots - 1, -1, -1))  # guarded-by: engine-thread
        self._snap_meta: dict[int, list] = {}  # guarded-by: engine-thread
        self.snapshots_evicted = 0  # guarded-by: engine-thread
        self._snap_evicted_taken = 0  # guarded-by: engine-thread

    # ------------------------------------------------------------------ reads

    @property
    def empty(self) -> bool:
        return not self.root.children

    def match(self, tokens: Sequence[int]) -> tuple[int, list[int], Optional[RadixNode]]:
        """Longest page-aligned prefix of ``tokens`` present in the tree →
        ``(n_matched, pages, deepest_node)``. Only whole pages match; a
        partial match inside an edge returns that edge's node (pinning it
        protects the matched page prefix). Touches the walked path for LRU.
        """
        page = self.page_size
        now = next(self._clock)
        node = self.root
        pages: list[int] = []
        pos = 0
        while pos + page <= len(tokens):
            key = tuple(tokens[pos : pos + page])
            child = node.children.get(key)
            if child is None:
                break
            # count full pages of the edge matching from ``pos``
            j = 1  # first page matched via the key
            edge_pages = len(child.pages)
            while j < edge_pages:
                lo = pos + j * page
                if lo + page > len(tokens) or \
                        child.tokens[j * page : (j + 1) * page] != list(tokens[lo : lo + page]):
                    break
                j += 1
            pages.extend(child.pages[:j])
            pos += j * page
            child.last_used = now
            if j < edge_pages:
                return pos, pages, child
            node = child
        # touch ancestors so a deep hit refreshes its whole path
        walk = node
        while walk is not None:
            walk.last_used = now
            walk = walk.parent
        return pos, pages, (node if node is not self.root else None)

    def peek_prefix(self, tokens: Sequence[int]) -> int:
        """Length (in tokens) of the longest page-aligned prefix of
        ``tokens`` this tree holds, WITHOUT taking refcounts or touching
        LRU clocks — the read-only routing probe the multi-replica router
        calls to pick the replica already holding a session's KV.

        Unlike every other method, this one MAY be called from a thread
        that is not the engine driver: it only reads (dict ``.get``, list
        slices — each GIL-atomic), never mutates, and its result is an
        advisory hint, not a correctness input. A concurrent insert/split/
        evict on the driver thread can at worst make the count stale by a
        few pages, which costs a slightly suboptimal routing choice."""
        page = self.page_size
        node = self.root
        pos = 0
        while pos + page <= len(tokens):
            child = node.children.get(tuple(tokens[pos : pos + page]))
            if child is None:
                break
            j = 1
            edge_pages = len(child.pages)
            while j < edge_pages:
                lo = pos + j * page
                if lo + page > len(tokens) or \
                        child.tokens[j * page : (j + 1) * page] != list(tokens[lo : lo + page]):
                    break
                j += 1
            pos += j * page
            if j < edge_pages:
                break
            node = child
        return pos

    # -------------------------------------------------------------- snapshots

    def _chain(self, node: Optional[RadixNode]) -> list[tuple[RadixNode, int]]:
        """``node`` and its ancestors, root side first, each with the token
        offset its edge starts at."""
        chain = []
        while node is not None and node is not self.root:
            chain.append(node)
            node = node.parent
        out, start = [], 0
        for link in reversed(chain):
            out.append((link, start))
            start += len(link.tokens)
        return out

    def match_state(self, tokens: Sequence[int], limit: int):
        """:meth:`match`, CUT BACK to the deepest matched page boundary, no
        deeper than ``limit`` tokens, that owns a snapshot → ``(n_matched,
        pages, node, snapshot, n_pages_matched)``: the first three as
        :meth:`match` gives them but for the cut prefix (``node`` the one whose
        edge holds the boundary; ``0, [], None`` where no boundary has a
        snapshot), the snapshot's slot (None), and the tokens the pages alone
        would have served. The snapshot's LRU clock is touched."""
        matched, pages, node = self.match(tokens)
        page = self.page_size
        matched = min(matched, limit)
        for link, start in reversed(self._chain(node)):
            for j in range(min(len(link.pages), (matched - start) // page), 0, -1):
                snap = link.snaps.get(j)
                if snap is not None:
                    self._snap_meta[snap][2] = next(self._clock)
                    cut = start + j * page
                    return cut, pages[: cut // page], link, snap, matched
        return 0, [], None, None, matched

    def snap_alloc(self) -> Optional[int]:
        """A snapshot slot to write: a free one, else the least recently used
        that is attached and not pinned (it leaves its boundary; its pages
        stay). None where every slot is pinned or still being written."""
        if self._snap_free:
            snap = self._snap_free.pop()
        else:
            idle = [(meta[2], snap) for snap, meta in self._snap_meta.items()
                    if meta[0] is not None and not meta[3]]
            if not idle:
                return None
            snap = min(idle)[1]
            node, at = self._snap_meta[snap][:2]
            del node.snaps[at]
            self.snapshots_evicted += 1
        self._snap_meta[snap] = [None, 0, next(self._clock), 0]
        return snap

    def snap_attach(self, tokens: Sequence[int], boundary: int, snap: int) -> bool:
        """Slot ``snap`` holds the state after ``tokens[:boundary]`` (whole
        pages, in the tree): the boundary owns it from now. False — and the
        slot is free again — where the boundary already owns one or the
        tokens are not in the tree."""
        page = self.page_size
        node, pos = self.root, 0
        while pos < boundary:
            child = node.children.get(tuple(tokens[pos: pos + page]))
            if child is None:
                break
            span = min(len(child.tokens), boundary - pos)
            if child.tokens[:span] != list(tokens[pos: pos + span]):
                break
            if span == boundary - pos:  # the boundary lies in (or ends) this edge
                if span // page in child.snaps:
                    break
                child.snaps[span // page] = snap
                self._snap_meta[snap][:2] = [child, span // page]
                return True
            node, pos = child, pos + span
        self.snap_free(snap)
        return False

    def snap_free(self, snap: int) -> None:
        """Give back a slot that is attached nowhere (``snap_alloc``'s, whose
        write never came to a boundary)."""
        del self._snap_meta[snap]
        self._snap_free.append(snap)

    def snap_pin(self, snap: int, by: int = 1) -> None:
        """A slot about to start from ``snap`` holds it (``by`` -1: lets go)."""
        self._snap_meta[snap][3] += by

    def _drop_snaps(self, node: RadixNode) -> None:
        """``node`` leaves the tree: its snapshots' slots are free again."""
        for snap in node.snaps.values():
            self.snap_free(snap)
        node.snaps = {}

    @property
    def snapshots_held(self) -> int:
        return len(self._snap_meta)

    def take_snapshots_evicted(self) -> int:
        """Snapshots :meth:`snap_alloc` took from their boundaries since the
        last call (the engine's counter books them a tick at a time)."""
        taken, self._snap_evicted_taken = self.snapshots_evicted - self._snap_evicted_taken, self.snapshots_evicted
        return taken

    # ----------------------------------------------------------------- writes

    def insert(self, tokens: Sequence[int], start: int, pages: Sequence[int],
               ) -> tuple[Optional[RadixNode], list[int]]:
        """Insert ``tokens`` (page-aligned length) whose span ``[start:)``
        is backed by ``pages`` (the inserting slot's own, freshly prefilled
        pages; ``start`` is page-aligned — the span the slot matched at
        admission). Returns ``(deepest_node, donated)`` where ``donated``
        are the pages whose ownership moved to the tree; pages covering
        spans some earlier insert already cached stay with the caller.
        """
        page = self.page_size
        assert len(tokens) % page == 0 and start % page == 0
        now = next(self._clock)
        node = self.root
        pos = 0
        donated: list[int] = []
        while pos < len(tokens):
            key = tuple(tokens[pos : pos + page])
            child = node.children.get(key)
            if child is None:
                if pos < start:
                    # the matched span must still be present: admission
                    # pinned it, and pins block eviction
                    raise RuntimeError(
                        f"radix insert: matched span [{pos}:{start}) vanished"
                    )
                new_pages = list(pages[(pos - start) // page :])
                tail = RadixNode(list(tokens[pos:]), new_pages, node)
                tail.last_used = now
                node.children[key] = tail
                donated.extend(new_pages)
                self.pages_held += len(new_pages)
                self.node_count += 1
                node = tail
                pos = len(tokens)
                break
            # walk the edge page by page
            j = 1
            edge_pages = len(child.pages)
            while j < edge_pages:
                lo = pos + j * page
                if lo + page > len(tokens) or \
                        child.tokens[j * page : (j + 1) * page] != list(tokens[lo : lo + page]):
                    break
                j += 1
            child.last_used = now
            if j < edge_pages:
                split = self._split(child, j)
                pos += j * page
                if pos >= len(tokens):
                    node = split
                    break
                node = split
                continue  # diverged mid-edge: next loop attaches the tail
            node = child
            pos += edge_pages * page
        return (node if node is not self.root else None), donated

    def _split(self, node: RadixNode, j: int) -> RadixNode:
        """Split ``node``'s edge after ``j`` pages; returns the new upper
        node (which keeps the parent link, refcount, and children key)."""
        page = self.page_size
        upper = RadixNode(node.tokens[: j * page], node.pages[:j], node.parent)
        upper.last_used = node.last_used
        # a pin on the lower node pins its whole chain; the upper node
        # inherits the count so chain pins stay consistent after the split
        upper.refcount = node.refcount
        # a boundary's snapshot goes with the half its page lies in
        kept, node.snaps = node.snaps, {}
        for at, snap in kept.items():
            owner, where = (upper, at) if at <= j else (node, at - j)
            self._snap_meta[snap][:2] = [owner, where]
            owner.snaps[where] = snap
        key = tuple(node.tokens[:page])
        node.parent.children[key] = upper
        node.tokens = node.tokens[j * page :]
        node.pages = node.pages[j:]
        node.parent = upper
        upper.children[tuple(node.tokens[:page])] = node
        self.node_count += 1
        return upper

    # ------------------------------------------------------------- pin/unpin

    def lock(self, node: Optional[RadixNode]) -> None:
        """Pin ``node`` and every ancestor (a slot's page table references
        the whole chain down to its match point)."""
        while node is not None and node is not self.root:
            node.refcount += 1
            node = node.parent

    def unlock(self, node: Optional[RadixNode]) -> None:
        while node is not None and node is not self.root:
            node.refcount -= 1
            assert node.refcount >= 0, "radix refcount underflow"
            node = node.parent

    # -------------------------------------------------------------- eviction

    def evict(self, n_pages: int) -> int:
        """Free up to ``n_pages`` pages from unpinned leaves, LRU first,
        cascading to parents as they become leaves. Returns pages freed
        (returned to the allocator). One tree traversal total: candidates
        collect into a ``last_used`` min-heap and parents push as their
        last child evicts — not a fresh full-tree scan per victim, which
        would cost O(nodes x victims) on the admission path exactly when
        the pool is most contended."""
        heap: list[tuple[int, int, RadixNode]] = []
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            elif node.refcount == 0:
                heap.append((node.last_used, id(node), node))
        heapq.heapify(heap)
        freed = 0
        while freed < n_pages and heap:
            _, _, victim = heapq.heappop(heap)
            parent = victim.parent
            self._drop_snaps(victim)
            self.allocator.free(victim.pages)
            freed += len(victim.pages)
            self.pages_held -= len(victim.pages)
            self.evicted_pages += len(victim.pages)
            self.node_count -= 1
            del parent.children[tuple(victim.tokens[: self.page_size])]
            if parent is not self.root and not parent.children \
                    and parent.refcount == 0:
                heapq.heappush(heap, (parent.last_used, id(parent), parent))
        return freed

    def clear(self) -> None:
        """Drop every node, returning all held pages to the allocator.
        Callers must ensure no live page table references the tree."""
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            self._drop_snaps(node)
            self.allocator.free(node.pages)
        self.root = RadixNode([], [], None)
        self.pages_held = 0
        self.node_count = 0

    # ------------------------------------------------------------------ stats

    def stats(self) -> dict:
        return {
            "pages": self.pages_held,
            "nodes": self.node_count,
            "evicted_pages": self.evicted_pages,
            **({"snapshots": self.snapshots, "snapshots_held": self.snapshots_held,
                "snapshots_evicted": self.snapshots_evicted} if self.snapshots else {}),
        }
