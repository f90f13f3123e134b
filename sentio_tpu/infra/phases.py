"""Tick-phase time attribution: where a pump-loop millisecond goes.

The flight recorder (infra/flight.py) times ticks as opaque wholes; this
module gives every pump iteration a named-phase decomposition so host work
is separable from device compute — the measurement ROADMAP item 1's
multi-process argument needs (host-fraction x N replicas is the direct GIL
ceiling). Phases are plain ``perf_counter`` deltas: no spans, no context
objects on the hot path beyond one tiny ``_PhaseSpan``, nothing when a
section simply stamps two clocks.

The phase set is FIXED and BOUNDED (``TICK_PHASES``): per-tick ``phase_ms``
dicts on flight tick records and the ``sentio_tpu_tick_phase_seconds``
histogram label space can never grow by a typo'd key (metrics cardinality
guard — unknown keys are dropped at the recording seam).

Phase glossary (one pump iteration, in canonical order):

``inbox_drain``
    Service-side mutex section at the loop top: heartbeat stamp, cancelled/
    expired sweeps, engine ``submit`` for every inbox ticket.
``admission_build``
    Host-side admission work inside ``engine.step()``: tokenization, radix
    matching, page allocation, padded numpy array assembly — everything in
    ``_admit``/``_advance_prefill`` EXCEPT the jit dispatch calls.
``prefill_dispatch``
    Host call time of the prefill/scatter jit dispatches (async on device;
    this is what the dispatch costs the PUMP THREAD — the GIL-held part).
``decode_dispatch``
    Host call time of the fused decode dispatch (``step_n``/spec tick) plus
    its merge/budget prep — again host-side cost of an async dispatch.
``device_wait``
    Time blocked on device results: the harvest's packed-token fetch
    (``np.asarray`` on a not-yet-ready array) and any blocking first-token
    fold. With ``pipeline_depth=2`` the dispatch overlaps the previous
    fetch, so the wait measured in iteration N is for the tick dispatched
    at N-1 — it is charged to the iteration that HARVESTS it, which is
    where the wall clock actually went (per-iteration conservation holds).
``deliver``
    Service-side mutex section after the tick: TTFT stamping, stream-queue
    pushes, result/event completion.
``other``
    Everything else measured inside the iteration (sanitizer invariant
    walks, telemetry recording) — kept explicit so per-tick conservation
    (``sum(phase_ms) == pump_ms``) holds by construction, not by tolerance.

``idle`` is not a tick phase: it is the duty-cycle complement (wall time
with no pump iteration running — pump down, or gaps between bursts).

Every region timed through :meth:`PhaseTimer.phase` also runs under a
profiler annotation named ``tick.<phase>``: inside an armed
``/debug/profile`` window the device trace's idle gaps are then named by
what the pump was doing, on the device's own clock.

**Request stages** are the same idea one level up: where a REQUEST's time
goes between its receipt and its last token. ``REQUEST_STAGES`` is fixed and
bounded like ``TICK_PHASES``; each stage is written where its work happens
(infra/tracing.py spans) and observed once per request in
``sentio_tpu_request_stage_seconds{stage}``:

``pool_wait``
    ``/chat`` received → the request's pipeline starts on one of the
    server's request threads. They are as many as the generation service
    admits (``serve/dependencies.py::RequestThreads``), so this is the hop
    and no queue: a caller beyond the slots waits in ``inbox_wait`` and
    ``slot_wait``.
``embed``
    The dense retrieval leg: query embedding dispatched → its top-k on the
    host (on the fused path the query vector never visits the host; the one
    blocking fetch is the index's).
``sparse_fuse``
    Dense leg back → fused, scored candidates: what is left of BM25, which
    ran beside the dense leg, then fusion and scorer plugins.
``rerank``
    Cross-encoder call → scores on the host.
``select``
    Dedup and the context token budget.
``inbox_wait``
    Ticket created → the pump's inbox drain hands it to ``engine.submit``.
``slot_wait``
    ``engine.submit`` → admitted into a slot.
``prefill``
    Admitted → first token host-visible.
``other``
    The residual: receipt → first token minus the eight above (prompt
    building, thread hops, JSON). Explicit, so the stages sum to the
    request's server-side time to first token by construction
    (:func:`tile_ttft`), as the tick phases sum to ``pump_ms``.
``decode``
    First token → finished.
``verify``
    The audit: its own admission's inbox_wait/slot_wait/prefill/decode are
    its children in the request's span tree and are not observed again.
``stream_lag``
    Per stream event: the pump put tokens on the ticket's queue → the HTTP
    handler wrote them to the socket.

**Device programs** are the third account, and the only one of the DEVICE's
time: ``DEVICE_PROGRAMS`` is fixed and bounded like the two above, written
by the completion stamps of infra/tracing.py (``DeviceStamper``) and read as
``sentio_tpu_device_program_seconds_total{program}``, the tick ring's
``device_ms`` and ``device.<program>`` annotations. A phase says what the
pump was doing and a stage where a request stood; a program says what the
device was running meanwhile. Idle is in no program.
"""

from __future__ import annotations

import time

__all__ = [
    "REQUEST_STAGES",
    "ROW_STEP_KINDS",
    "KV_PAGE_KINDS",
    "MOE_KINDS",
    "CONV_STATE_KINDS",
    "SSM_ROW_UPDATE_KINDS",
    "SSM_STATE_KINDS",
    "PREFILL_LATENT_KINDS",
    "PREFILL_TURN_KINDS",
    "LANE_ADMISSION_KINDS",
    "DEVICE_PROGRAMS",
    "STARTUP_PHASES",
    "COMPILE_PARTS",
    "CACHE_OUTCOMES",
    "INGEST_STAGES",
    "BM25_UPDATE_KINDS",
    "ENCODER_PROGRAMS",
    "ENCODER_FORWARD_PARTS",
    "TTFT_STAGES",
    "tile_ttft",
    "TICK_PHASES",
    "ENGINE_PHASES",
    "HOST_PHASES",
    "DUTY_STATES",
    "PhaseTimer",
    "duty_fractions",
    "phases_to_ms",
    "sum_phase_totals",
]

# the one bounded key set — flight `phase_ms`, the tick-phase histogram's
# `phase` label, and the conservation test all pin against this tuple
TICK_PHASES = (
    "inbox_drain",
    "admission_build",
    "prefill_dispatch",
    "decode_dispatch",
    "device_wait",
    "deliver",
    "other",
)

# the subset engine.step() itself attributes (the service adds the rest)
ENGINE_PHASES = (
    "admission_build",
    "prefill_dispatch",
    "decode_dispatch",
    "device_wait",
    "other",
)

# duty-cycle rollup: every phase that burns the host thread (and, with N
# replicas in one process, contends for the one GIL) vs. blocked-on-device
HOST_PHASES = tuple(p for p in TICK_PHASES if p != "device_wait")

DUTY_STATES = ("host", "device", "idle")

# the stages that tile receipt → first token, the residual last
TTFT_STAGES = (
    "pool_wait",
    "embed",
    "sparse_fuse",
    "rerank",
    "select",
    "inbox_wait",
    "slot_wait",
    "prefill",
    "other",
)

# the one bounded stage set — the request-stage histogram's `stage` label
REQUEST_STAGES = TTFT_STAGES + ("decode", "verify", "stream_lag")

# what a decode slot did with one sub-step the device ran (the engine counts
# slots x sub-steps per harvested tick, runtime/paged.py): `useful` folded a
# token into an answer, `halted` held a request that had finished, spent its
# budget or was still prefilling, `empty` held none
ROW_STEP_KINDS = ("useful", "halted", "empty")

# K/V page blocks of the sub-steps the device ran (runtime/paged.py counts
# them per dispatched tick, by the decode kernel's own rule): `held` the
# blocks the kernel's walk copies and computes — a row's ``lens // page + 1``,
# one for a row that holds no request or does not advance — `tabled`
# every cell of every page table, what a walk of the table would touch, and
# `behind_window` the blocks an advancing row holds in layers whose window no
# longer reaches them (the mean over layers, as `held` is; 0 with no window)
KV_PAGE_KINDS = ("held", "tabled", "behind_window")

# a routed family's expert layers (models/moe.py::expert_layer counts them on
# the device; runtime/paged.py books them when a tick is harvested): pairs of
# token and pick routed over ALL experts (`pairs_routed`) and those whose
# expert this process holds (`pairs_held`) — decode sub-steps and prefill
# programs alike; and of the held experts x layers x decode sub-steps
# (`experts_held`) those at least one pair touched (`experts_touched`)
MOE_KINDS = ("pairs_routed", "pairs_held", "experts_held", "experts_touched")

# a latent family's prefill dispatches (runtime/paged.py counts them on the
# host from each dispatch's own integers): `new` the tokens a call computed,
# `expanded` the prior tokens whose pooled latents that call turned back into
# keys and values (a chunked prompt's every segment after the first, and every
# radix hit, expands its whole prior)
PREFILL_LATENT_KINDS = ("new", "expanded")

# a family with convolution state (models/lfm2_moe.py; runtime/paged.py counts
# on the host, books when a tick is harvested): what each row of a prefill
# dispatch STARTED from — `zero` (position 0), `tail` (a cached page's stored
# tail: a radix hit), `carried` (a chunked prompt's later segment, from the
# tail its own earlier segment left) — and `pages`, the page tails written (by
# prefill for the pages it filled, by decode when a page filled)
CONV_STATE_KINDS = ("zero", "tail", "carried", "pages")
CONV_START_KINDS = CONV_STATE_KINDS[:3]

# a family with Mamba layers (models/nemotron_h.py), whose state is a matrix a
# head: what each row of a prefill dispatch STARTED from — `zero` (position 0),
# `snapshot` (a radix hit, cut back to a page boundary whose state the cache
# kept) or `carried` (a chunked prompt's later segment, from its slot) —, the
# snapshots `written` (a prefill dispatch filled a slot of the bounded pool)
# and `evicted` (a slot taken from its boundary for another; its pages stay),
# `cut_back_tokens`: tokens the pages matched and the model computed again
# for want of a snapshot, and of the decode ticks' slots x sub-steps x Mamba
# blocks one-token updates of a slot's state the `row_updates` the device did
# and the `row_skips` it did not (kernels/ssm_update.py moves no byte of a row
# that does not advance; the XLA form updates every row and skips none)
SSM_STATE_KINDS = ("zero", "snapshot", "carried", "written", "evicted", "cut_back_tokens", "row_updates", "row_skips")
SSM_START_KINDS = SSM_STATE_KINDS[:3]
SSM_SNAPSHOT_EVENTS = SSM_STATE_KINDS[3:5]
SSM_ROW_UPDATE_KINDS = SSM_STATE_KINDS[6:]

# chunked prefill's turns (runtime/paged.py::_advance_prefill dispatches ONE
# segment a tick over all slots): a tick in which n slots hold a pending
# segment books one `taken` and n - 1 `waited`
PREFILL_TURN_KINDS = ("taken", "waited")

# the lane an admission took (runtime/paged.py::_admit): `free` held no
# request; `spent` held a row whose every remaining token rode the tick in
# flight, and was handed on before that tick's harvest retired the row (depth
# 2 only: at depth 1, and on a speculative engine, every admission is `free`)
LANE_ADMISSION_KINDS = ("free", "spent")

# what the DEVICE was running (infra/tracing.py's completion stamps: every
# dispatch site hands its program and one small output to the stamper, which
# books the interval the program held the device): `decode` the fused decode
# tick (``step_n``, the speculative tick), `prefill` the whole-prompt and
# prior-primed prefill programs, `admit` ``merge_admitted``, `embed` and
# `rerank` the encoders' forwards, `other` the dense index's top-k and
# whatever else dispatches. Idle is in no program
DEVICE_PROGRAMS = ("decode", "prefill", "admit", "embed", "rerank", "other")

# the programs whose forwards are split into the time they lay behind other
# device work and the time they ran (``ENCODER_FORWARD_PARTS``)
ENCODER_PROGRAMS = ("embed", "rerank")
ENCODER_FORWARD_PARTS = ("queued", "running")


# where a START's time goes, process start → listening (infra/startup.py
# tiles it from the ``startup`` flight record's spans, as SELF time: a
# component built inside another's build is its child and is not counted
# twice): `import` the interpreter and the modules up to the server's own,
# `backend` the first device enumeration (the TPU runtime's own start), one
# phase a component built through ``DependencyContainer._get`` that is worth a
# name, `weights` every checkpoint read and every placement on the device
# (``weights.read`` / ``weights.place`` spans), `pool.alloc` the page pool,
# `prefix.warm` the prefix cache's template head, `warmup` the compile
# fence's sweep where it is armed, `listen` the container built → the socket
# accepting, and `other` what no named span covers (the components that are
# no phase of their own among it), explicit so the phases sum to ``ready_s``
STARTUP_PHASES = (
    "import", "backend", "mesh", "embedder", "dense_index", "sparse_index",
    "retriever", "reranker", "decoder", "generation_service", "request_threads",
    "generator", "verifier", "graph", "ingestor", "weights", "pool.alloc",
    "prefix.warm", "warmup", "listen", "other",
)

# a compile's parts (infra/tracing.py's ``jax.monitoring`` listeners book them
# into analysis/audit/fence.py by program): `trace` the Python tracing of the
# outermost function (the functions it calls are traced inside it), `lower`
# jaxpr → MLIR module, and the backend's share as `backend_miss` (XLA
# compiled) or `backend_hit` (the persistent cache had it: key, read,
# deserialisation)
COMPILE_PARTS = ("trace", "lower", "backend_miss", "backend_hit")
CACHE_OUTCOMES = ("hit", "miss")

# an ingest call's stages (ops/ingest.py): chunking, the embedder's forward
# over the chunks, the dense index's add and the sparse index's
INGEST_STAGES = ("chunk", "embed", "dense_add", "sparse_add")
# what the sparse stage did: `add` indexed the call's chunks after those held
# (what the dense index did was append), `build` indexed the store's documents
# anew (an id written again, a delete, a store that counts otherwise)
BM25_UPDATE_KINDS = ("add", "build")


def tile_ttft(stage_s: dict, ttft_s: float) -> dict:
    """Seconds per measured stage + a request's server-side time to first
    token → the full ``TTFT_STAGES`` dict, zeros included, ``other`` taking
    what the measured stages leave. ``sum(tile.values()) == ttft_s`` by
    construction. A key outside the set raises, as ``PhaseTimer`` does: a
    typo'd stage must fail where it is written, not mint a series."""
    tile = dict.fromkeys(TTFT_STAGES, 0.0)
    for key, seconds in stage_s.items():
        if key not in tile or key == "other":
            raise KeyError(f"unknown stage {key!r} (bounded set: {TTFT_STAGES[:-1]})")
        tile[key] += seconds
    tile["other"] = ttft_s - sum(tile.values())
    return tile


class _PhaseSpan:
    """Tiny enter/exit timer — two perf_counter calls and a dict add, under
    a ``tick.<phase>`` profiler annotation."""

    __slots__ = ("_timer", "_key", "_t0", "_ann")

    def __init__(self, timer: "PhaseTimer", key: str) -> None:
        self._timer = timer
        self._key = key

    def __enter__(self) -> "_PhaseSpan":
        from jax.profiler import TraceAnnotation

        self._ann = TraceAnnotation(f"tick.{self._key}")
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._timer.add(self._key, time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)
        return False


class PhaseTimer:
    """Per-iteration phase accumulator. NOT thread-safe by design — one
    timer belongs to one pump/engine thread; cross-thread aggregation
    happens on snapshots. A region may be entered many times per tick
    (every prefill dispatch adds to ``prefill_dispatch``); keys outside
    the constructor's set are rejected so the bounded-set guarantee is
    enforced at the writer, not just the exporter."""

    __slots__ = ("acc",)

    def __init__(self, keys: tuple = TICK_PHASES) -> None:
        self.acc: dict[str, float] = dict.fromkeys(keys, 0.0)

    def reset(self) -> None:
        for key in self.acc:
            self.acc[key] = 0.0

    def add(self, key: str, seconds: float) -> None:
        # KeyError on an unknown phase is deliberate: a typo'd phase name
        # must fail the tick that introduced it, not mint a metric series
        self.acc[key] += seconds

    def phase(self, key: str) -> _PhaseSpan:
        """Context manager timing one region into ``key``."""
        if key not in self.acc:
            raise KeyError(f"unknown phase {key!r} (bounded set: {tuple(self.acc)})")
        return _PhaseSpan(self, key)

    def total(self) -> float:
        return sum(self.acc.values())

    def snapshot_ms(self) -> dict[str, float]:
        """Bounded ``phase_ms`` dict for a flight tick record (zero phases
        included — a fixed shape diffs and plots cleanly)."""
        return phases_to_ms(self.acc)


def phases_to_ms(phase_s: dict) -> dict:
    """Seconds-per-phase → the ``phase_ms`` wire shape (ms, 3 decimals).
    ONE definition — the pump's flight records and PhaseTimer.snapshot_ms
    must never drift (the chrome-trace golden fixture pins the format)."""
    return {k: round(v * 1e3, 3) for k, v in phase_s.items()}


def sum_phase_totals(rows) -> tuple:
    """Fold per-replica stats rows (each carrying cumulative
    ``phase_seconds`` + ``duty_elapsed_s``) into fleet totals:
    ``(phase_totals, duty_elapsed_s)``. ONE definition shared by
    ``ReplicaSet.stats()`` and the telemetry merge path — the fleet's
    phase arithmetic must not drift between replica modes. Rows without
    phase data (a dead worker's fallback stats) contribute nothing."""
    totals: dict[str, float] = {}
    elapsed = 0.0
    for row in rows:
        for key, val in (row.get("phase_seconds") or {}).items():
            totals[key] = totals.get(key, 0.0) + float(val)
        elapsed += float(row.get("duty_elapsed_s", 0.0))
    return totals, elapsed


def duty_fractions(phase_totals: dict, elapsed_s: float) -> dict:
    """Fold cumulative phase seconds into host/device/idle fractions of
    ``elapsed_s`` wall time, summing to exactly 1.0 (the gauge contract:
    ``sentio_tpu_pump_duty_cycle{state}`` over one replica sums to 1).
    Measurement skew (busy marginally exceeding elapsed on a coarse clock)
    clamps idle at 0 and renormalizes."""
    if elapsed_s <= 0:
        return {"host": 0.0, "device": 0.0, "idle": 1.0}
    host = sum(phase_totals.get(k, 0.0) for k in HOST_PHASES)
    device = phase_totals.get("device_wait", 0.0)
    idle = max(elapsed_s - host - device, 0.0)
    total = host + device + idle
    return {
        "host": round(host / total, 6),
        "device": round(device / total, 6),
        "idle": round(idle / total, 6),
    }
