"""Metrics: Prometheus counters/histograms/gauges with in-memory fallback,
plus TPU device gauges the reference never needed.

The request dimension of /root/reference/src/observability/metrics.py and its
text-or-JSON export, and the serving dimension this program adds: per-sequence
TTFT and TPOT, tick phases, request stages, counted row-steps, and what the
device ran by program (``sentio_tpu_device_program_seconds_total``). A series
that neither the benchmark, ``deploy/kubernetes/monitoring.yaml`` nor a
README section reads does not stay (PR 40 took out the embedding, LLM-call
and tokens-per-second series).
"""

from __future__ import annotations

import ast
import time
from contextlib import contextmanager
from typing import Any, Optional

from sentio_tpu.analysis.sanitizer import guard_locksets, make_lock

try:
    from prometheus_client import (
        CollectorRegistry,
        Counter,
        Gauge,
        Histogram,
        generate_latest,
    )

    PROMETHEUS_AVAILABLE = True
except ImportError:  # pragma: no cover - prometheus is in the image
    PROMETHEUS_AVAILABLE = False


# fleet telemetry (runtime/worker.py telemetry frames → merge_worker_series):
# distinct worker-originated series a single replica may mint on the router.
# The worker's own registry is already label-bounded (phase/family/reason
# sets are fixed tuples), so this cap only fires if a worker starts lying —
# overflow series are dropped and counted, never merged.
MAX_WORKER_SERIES_PER_REPLICA = 512


def _parse_series_key(key: str):
    """Split an :class:`InMemoryMetrics` storage key (``f"{name}{labels}"``
    with ``labels`` a tuple) back into ``(name, labels)``. Returns
    ``(None, ())`` for keys that do not round-trip — a malformed key from a
    byte-damaged frame must be dropped, not crash the merge."""
    cut = key.find("(")
    if cut < 0:
        return key, ()
    try:
        labels = ast.literal_eval(key[cut:])
    except (ValueError, SyntaxError):
        return None, ()
    if not isinstance(labels, tuple):
        labels = (labels,)
    return key[:cut], tuple(str(item) for item in labels)


@guard_locksets
class InMemoryMetrics:
    """Fallback store mirroring the counter/histogram API shape."""

    WINDOW = 1000  # retained observations per histogram key

    def __init__(self) -> None:
        self._lock = make_lock("InMemoryMetrics._lock")
        self.counters: dict[str, float] = {}  # guarded-by: _lock
        self.histograms: dict[str, list[float]] = {}  # guarded-by: _lock
        self._histo_total: dict[str, int] = {}  # guarded-by: _lock
        self._histo_sum: dict[str, float] = {}  # guarded-by: _lock
        self.gauges: dict[str, float] = {}  # guarded-by: _lock

    def inc(self, name: str, labels: tuple = (), value: float = 1.0) -> None:
        key = f"{name}{labels}"
        with self._lock:
            self.counters[key] = self.counters.get(key, 0.0) + value

    def observe(self, name: str, labels: tuple, value: float) -> None:
        key = f"{name}{labels}"
        with self._lock:
            self.histograms.setdefault(key, []).append(value)
            self._histo_total[key] = self._histo_total.get(key, 0) + 1
            self._histo_sum[key] = self._histo_sum.get(key, 0.0) + value
            if len(self.histograms[key]) > self.WINDOW:
                self.histograms[key] = self.histograms[key][-self.WINDOW:]

    def set_gauge(self, name: str, labels: tuple, value: float) -> None:
        with self._lock:
            self.gauges[f"{name}{labels}"] = value

    def snapshot(self) -> dict[str, Any]:
        """JSON-export aggregates. ``count`` and ``mean`` are TRUE lifetime
        statistics; quantiles come from the retained window (the last
        ``WINDOW`` observations) with ``dropped`` saying how many fell out,
        so exported numbers are never silently presented as full-run
        statistics (the old export reported a truncation-biased p50 under
        the full count)."""
        with self._lock:
            histos = {}
            for k, v in self.histograms.items():
                total = self._histo_total.get(k, len(v))
                s = sorted(v)
                histos[k] = {
                    "count": total,
                    "window": len(v),
                    "dropped": total - len(v),
                    "p50": s[len(s) // 2] if s else 0.0,
                    "p95": s[min(int(len(s) * 0.95), len(s) - 1)] if s else 0.0,
                    "mean": (self._histo_sum.get(k, 0.0) / total) if total else 0.0,
                }
            return {"counters": dict(self.counters), "histograms": histos, "gauges": dict(self.gauges)}


@guard_locksets
class MetricsCollector:
    """One instance per process. With prometheus_client present, metrics
    register in an isolated registry (no default-registry collisions in
    tests); the in-memory store is always maintained for JSON export."""

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = enabled
        self.memory = InMemoryMetrics()
        self.registry = None
        self._prom: dict[str, Any] = {}
        self._inflight = 0  # guarded-by: _inflight_lock
        self._inflight_lock = make_lock("MetricsCollector._inflight_lock")
        self._serving_last: dict[str, float] = {}
        # per-replica worker-telemetry merge state: cumulative baselines +
        # the (pid, epoch) fence. Lives on the COLLECTOR, not the replica
        # shim — a heal replaces the ProcessReplica object, and losing the
        # baselines there would double-count every series post-heal.
        self._worker_last: dict[int, dict] = {}  # guarded-by: _worker_lock
        self._worker_lock = make_lock("MetricsCollector._worker_lock")
        if PROMETHEUS_AVAILABLE and enabled:
            self.registry = CollectorRegistry()
            self._build_prom()

    def _build_prom(self) -> None:
        r = self.registry
        self._prom = {
            "requests": Counter(
                "sentio_requests_total", "HTTP requests", ["endpoint", "status"], registry=r
            ),
            "request_latency": Histogram(
                "sentio_request_latency_seconds", "request latency", ["endpoint"],
                buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10), registry=r,
            ),
            # TPU device dimension
            "serving_stat": Gauge(
                "sentio_tpu_serving_stat",
                "decode service point-in-time stats (occupancy, queue depth, pages)",
                ["stat"], registry=r,
            ),
            # what each in-process encoder's weights hold of the device, as
            # its class keeps them (cast once at load to the forward's dtype:
            # the README's memory table and /info's ``param_bytes`` read it)
            "encoder_param_bytes": Gauge(
                "sentio_tpu_encoder_param_bytes",
                "bytes of weights an encoder holds on the device",
                ["model"], registry=r,
            ),
            "serving_total": Counter(
                "sentio_tpu_serving_events_total",
                "decode service lifetime totals", ["event"], registry=r,
            ),
            # per-sequence serving latency, the two numbers an LLM-serving
            # SLO is actually written against (vLLM exposes the same pair):
            # TTFT = submit → first sampled token host-visible; TPOT = mean
            # seconds per output token after the first
            "ttft": Histogram(
                "sentio_tpu_ttft_seconds", "time to first token", ["path"],
                buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30),
                registry=r,
            ),
            "tpot": Histogram(
                "sentio_tpu_tpot_seconds", "time per output token", ["path"],
                buckets=(0.002, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5),
                registry=r,
            ),
            # engine pump iteration telemetry (the flight recorder's tick
            # events, aggregated): wall time per fused decode dispatch
            "tick_duration": Histogram(
                "sentio_tpu_tick_duration_seconds", "engine pump tick wall time",
                [], buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 5),
                registry=r,
            ),
            # XLA compilations observed at registered jit families
            # (analysis/audit): after warmup this should flatline — any
            # increase is a recompile regression a latency SLO will feel
            "xla_compiles": Counter(
                "sentio_tpu_xla_compiles_total",
                "XLA compilations at registered jit families", ["family"],
                registry=r,
            ),
            # admission-control outcomes: requests dropped at or after the
            # decode-service door (queue_full / draining / deadline /
            # expired / cancelled) — the overload story's headline series;
            # a nonzero rate here is the signal to scale out or shed earlier
            "shed": Counter(
                "sentio_tpu_shed_total",
                "requests shed / expired / cancelled by the decode service",
                ["reason"], registry=r,
            ),
            # the HPA scaling signal (deploy/kubernetes/hpa.yaml): CPU% is
            # meaningless for a TPU pod, queue depth is what saturates a slice
            "inflight": Gauge(
                "sentio_inflight_requests", "requests currently being served", [], registry=r
            ),
            # multi-replica serving tier (runtime/replica.py): per-tenant
            # weighted-fair-queueing outcomes and per-replica occupancy /
            # queue / page-pool gauges — the labels that say WHICH tenant
            # was shed and WHICH replica is hot. Tenant label cardinality
            # is bounded by TenantFairQueue.MAX_TRACKED.
            "tenant_admitted": Counter(
                "sentio_tpu_tenant_admitted_total",
                "requests admitted through weighted fair queueing",
                ["tenant"], registry=r,
            ),
            "tenant_shed": Counter(
                "sentio_tpu_tenant_shed_total",
                "requests shed by weighted fair queueing",
                ["tenant", "reason"], registry=r,
            ),
            "replica_stat": Gauge(
                "sentio_tpu_replica_stat",
                "per-replica decode service point-in-time stats",
                ["replica", "stat"], registry=r,
            ),
            # replica failure domains (runtime/replica.py supervisor): 1 on
            # the replica's CURRENT health state, 0 on the other three —
            # monitoring.yaml alerts on any replica out of HEALTHY > 60s
            "replica_health": Gauge(
                "sentio_tpu_replica_health",
                "replica health state machine position (1 = current state)",
                ["replica", "state"], registry=r,
            ),
            # confidence-gated verification (ops/confidence.py + the graph
            # verify node): outcome per mode — skipped_confident is the
            # gate paying off, a skip-rate anomaly alert rides this series
            "verify_total": Counter(
                "sentio_tpu_verify_total",
                "answer verifications by mode and outcome",
                ["mode", "outcome"], registry=r,
            ),
            "verify_confidence": Histogram(
                "sentio_tpu_verify_confidence",
                "confidence-gate score per scored answer",
                [], buckets=(0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.75,
                             0.8, 0.85, 0.9, 0.95, 1.0),
                registry=r,
            ),
            # stall watchdog: seconds since a replica's decode pump last
            # completed a loop iteration WITH pending work (0 = idle or
            # freshly ticked). A tick wedged inside a device dispatch
            # raises nothing — this gauge climbing toward the stall budget
            # is the only early signal; monitoring.yaml alerts on it
            "pump_heartbeat_age": Gauge(
                "sentio_tpu_pump_heartbeat_age_seconds",
                "decode pump heartbeat age under pending work",
                ["replica"], registry=r,
            ),
            # tick-phase attribution (infra/phases.py): per-replica
            # host/device/idle wall-time split (fractions sum to 1) and
            # the per-tick phase latency distributions. Host fraction
            # near 1 under load = the pump is GIL/dispatch-bound, not
            # device-bound — monitoring.yaml's SentioTpuPumpHostBound
            # alert and ROADMAP item 1's multi-process argument both
            # read this series.
            "pump_duty_cycle": Gauge(
                "sentio_tpu_pump_duty_cycle",
                "fraction of wall time the decode pump spends per state "
                "(host / device / idle; sums to 1 per replica)",
                ["replica", "state"], registry=r,
            ),
            "tick_phase": Histogram(
                "sentio_tpu_tick_phase_seconds",
                "pump-iteration time per named phase",
                ["phase"],
                buckets=(1e-5, 1e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 0.01,
                         0.025, 0.05, 0.1, 0.25, 0.5, 1, 5),
                registry=r,
            ),
            # where a request waits (infra/phases.py REQUEST_STAGES): the
            # nine stages from pool_wait to other are observed together
            # when a request's first token lands and sum to its server-side
            # TTFT; decode, verify and stream_lag when they close
            "request_stage": Histogram(
                "sentio_tpu_request_stage_seconds",
                "request time per named stage, receipt to last token",
                ["stage"],
                buckets=(5e-4, 1e-3, 2.5e-3, 5e-3, 0.01, 0.025, 0.05, 0.1,
                         0.25, 0.5, 1, 2.5, 5, 10, 30),
                registry=r,
            ),
            # what the decode slots did with the sub-steps the device ran:
            # per harvested tick, slots x sub-steps row-steps, each one
            # useful (its token was delivered), halted (a request held the
            # slot but the row had finished, spent its budget or was still
            # prefilling) or empty (no request in the slot)
            "row_steps": Counter(
                "sentio_tpu_decode_row_steps_total",
                "decode row-steps by what they produced",
                ["kind"], registry=r,
            ),
            # K/V page blocks of those sub-steps: held = what the decode
            # kernel's walk copies and computes (a row's lens // page + 1,
            # one for a row that holds nothing), tabled = every cell of
            # every page table. held / tabled is the share of a walk of the
            # table that is work; behind_window = blocks the rows hold in
            # layers whose window no longer reaches them
            "kv_pages": Counter(
                "sentio_tpu_decode_kv_pages_total",
                "K/V page blocks of the decode sub-steps: held by rows, tabled, held behind a window",
                ["kind"], registry=r,
            ),
            # a routed family's expert layers, counted on the device inside
            # the decode tick and the prefill programs (models/moe.py):
            # pairs of token and pick, routed over all experts vs held by
            # this process's share of them ...
            "moe_pairs": Counter(
                "sentio_tpu_moe_pairs_total",
                "token-expert pairs the router made, and those whose expert is held here",
                ["kind"], registry=r,
            ),
            # ... and of the held experts x layers x decode sub-steps, those
            # at least one pair touched (a decode step reads only these)
            "moe_expert_steps": Counter(
                "sentio_tpu_moe_expert_steps_total",
                "held experts x layers x decode sub-steps, and those a pair touched",
                ["kind"], registry=r,
            ),
            # a latent family's prefill dispatches (runtime/paged.py, counted
            # on the host): tokens a call computed (new) and prior tokens
            # whose pooled latents it turned back into keys and values
            # (expanded). expanded / new is what chunking over a latent
            # prior costs; 0 expanded if priors are attended absorbed
            "prefill_latent": Counter(
                "sentio_tpu_prefill_latent_tokens_total",
                "tokens a latent family's prefill computed, and prior tokens it expanded",
                ["kind"], registry=r,
            ),
            # a family with convolution state (runtime/paged.py, counted on
            # the host): what each prefill row started from — zeros, a cached
            # page's tail (a radix hit), its own earlier segment's tail ...
            "conv_starts": Counter(
                "sentio_tpu_conv_state_starts_total",
                "prefill rows of a family with convolution state, by what their state started from",
                ["kind"], registry=r,
            ),
            # ... and the page tails written (prefill: the pages it filled;
            # decode: a page that filled)
            "conv_tail_pages": Counter(
                "sentio_tpu_conv_tail_pages_total",
                "page tails of convolution state written, by prefill and by decode",
                registry=r,
            ),
            # a family with Mamba layers (runtime/paged.py, counted on the
            # host): what each prefill row started from — zeros, a snapshot
            # (a radix hit, cut back to a boundary whose state was kept), its
            # slot's own state (a chunked prompt's later segment) ...
            "ssm_starts": Counter(
                "sentio_tpu_ssm_state_starts_total",
                "prefill rows of a family with Mamba layers, by what their state started from",
                ["kind"], registry=r,
            ),
            # ... the snapshots written into the bounded pool and those taken
            # from their boundary for another ...
            "ssm_snapshots": Counter(
                "sentio_tpu_ssm_snapshots_total",
                "snapshots of Mamba state written by prefill, and evicted from their page boundary",
                ["event"], registry=r,
            ),
            # ... the one-token updates of a slot's state the decode ticks did
            # and skipped (a row that does not advance: the update kernel
            # moves no byte of it) ...
            "ssm_row_updates": Counter(
                "sentio_tpu_ssm_state_rows_total",
                "decode row-steps x Mamba blocks whose state update the device did, and skipped",
                ["kind"], registry=r,
            ),
            # ... and the tokens the pages matched that were computed again
            # because no snapshot stood at their boundary
            "prefix_cut_back": Counter(
                "sentio_tpu_prefix_cut_back_tokens_total",
                "prefix tokens matched in pages and recomputed for want of a state snapshot",
                registry=r,
            ),
            # chunked prefill's turns (runtime/paged.py::_advance_prefill:
            # one segment a tick over all slots): a tick in which n slots
            # hold a pending segment books one taken and n - 1 waited.
            # waited / (taken + waited) is the share of a segment's life
            # spent waiting for its turn
            "prefill_turns": Counter(
                "sentio_tpu_prefill_turns_total",
                "slots holding a pending prefill segment at a tick: the one dispatched, and the rest",
                ["kind"], registry=r,
            ),
            # the lane each admission took (runtime/paged.py::_admit): free
            # (it held no request) or spent (its row's last tokens rode the
            # tick in flight: handed on before that tick's harvest).
            # spent / (free + spent) is how often a request did NOT wait a
            # tick for a lane the host already knew was done
            "lane_admissions": Counter(
                "sentio_tpu_lane_admissions_total",
                "admissions by the lane they took: one that held no request, or one whose row was spent",
                ["kind"], registry=r,
            ),
            # what the DEVICE was running, by the program's own completion
            # stamps (infra/tracing.py::DeviceStamper): the seconds each
            # dispatched program held the device, from the later of its
            # dispatch and its predecessor's completion to its own
            # completion. Idle is booked nowhere, so the labels sum to the
            # device's busy time as this process saw it
            "device_program": Counter(
                "sentio_tpu_device_program_seconds_total",
                "seconds the device ran each kind of program, by completion stamps",
                ["program"], registry=r,
            ),
            # what a START cost (infra/startup.py tiles process start ->
            # listening from the ``startup`` flight record: self time a
            # phase, ``other`` the residual, so the labels sum to ``ready``'s
            # seconds), what every compile cost by program and part with
            # what the persistent cache answered (analysis/audit/fence.py's
            # timed account; infra/tracing.py's jax.monitoring listeners
            # write it), and an ingest call's stages (ops/ingest.py)
            "startup_seconds": Gauge(
                "sentio_tpu_startup_seconds",
                "process start -> listening, by phase (self time; sums to ready_s)",
                ["phase"], registry=r,
            ),
            "compile_seconds": Counter(
                "sentio_tpu_compile_seconds_total",
                "seconds of compiling by program and part "
                "(trace, lower, backend_miss, backend_hit)",
                ["program", "part"], registry=r,
            ),
            "compile_cache": Counter(
                "sentio_tpu_compile_cache_total",
                "backend compiles by program and what JAX's persistent cache answered",
                ["program", "outcome"], registry=r,
            ),
            "ingest_stage_seconds": Counter(
                "sentio_tpu_ingest_stage_seconds_total",
                "seconds of ingest calls by stage (chunk, embed, dense_add, sparse_add)",
                ["stage"], registry=r,
            ),
            "bm25_updates": Counter(
                "sentio_tpu_bm25_updates_total",
                "sparse index updates of ingest calls: add (the call's chunks "
                "alone) or build (the whole store again)",
                ["kind"], registry=r,
            ),
            # an encoder forward's two parts: dispatch -> the device took it
            # up (queued behind other programs), took it up -> done (running)
            "encoder_forward": Counter(
                "sentio_tpu_encoder_forward_seconds_total",
                "embed and rerank forwards: seconds queued behind device work, and running",
                ["part"], registry=r,
            ),
            # process-mode replica tier (runtime/worker.py): worker
            # process deaths observed by the router-side shim (SIGKILL,
            # OOM-kill, crash, broken RPC pipe). A steadily increasing
            # rate means the supervisor is respawn-looping a replica —
            # monitoring.yaml's SentioTpuReplicaWorkerDead alerts on it
            "worker_deaths": Counter(
                "sentio_tpu_replica_worker_deaths",
                "replica worker process deaths observed by the router",
                ["replica"], registry=r,
            ),
            # multi-host worker tier (runtime/transport.py + the worker
            # registry in runtime/replica.py): each (re)registration of a
            # socket worker bumps the slot's incarnation epoch — this
            # gauge IS the epoch, so a sawtooth means the slot is churning
            "worker_incarnation": Gauge(
                "sentio_tpu_worker_incarnation",
                "current incarnation epoch of each replica slot's worker "
                "(bumped at every socket (re)registration)",
                ["replica"], registry=r,
            ),
            # frames from a PREVIOUS incarnation dropped at dispatch — a
            # partition healing is the normal source (the old connection
            # drains its buffered pre-partition frames); nonzero during an
            # incident is the epoch fence doing its job, a sustained rate
            # outside incidents means a zombie connection never died
            "worker_stale_frames": Counter(
                "sentio_tpu_worker_stale_frames_total",
                "worker frames dropped for carrying a stale incarnation "
                "epoch",
                ["replica"], registry=r,
            ),
            # worker (re)connection outcomes: heal = a live partitioned
            # worker re-registered and kept its process; respawn = the
            # supervisor spawned a fresh process; reconnected = a dialed
            # remote worker accepted a fresh router connection; rejected_*
            # = the registry refused a registration. monitoring.yaml's
            # SentioTpuWorkerFlapping alerts on churn in this series.
            "worker_reconnects": Counter(
                "sentio_tpu_worker_reconnects_total",
                "socket worker reconnection outcomes",
                ["outcome"], registry=r,
            ),
            # resumable streams (runtime/replica.py): mid-flight failovers
            # of delivered-token streams. outcome=resumed is the healthy
            # path; a sustained resume RATE means a replica is flapping —
            # monitoring.yaml's SentioTpuStreamResumeStorm alerts on it
            "stream_resumes": Counter(
                "sentio_tpu_stream_resumes_total",
                "mid-flight stream resume outcomes (resumed = delivered "
                "prefix spliced onto a survivor; exhausted = resume budget "
                "spent, typed error surfaced; failed = no survivor could "
                "take the splice; opt_out = caller disabled resumption)",
                ["outcome"], registry=r,
            ),
            # fleet telemetry plane (runtime/worker.py telemetry frames):
            # worker-process metric registries shipped as monotonic deltas
            # and re-published here under {replica} — /metrics shows one
            # truthful fleet view in every replica mode. Counters (not
            # gauges): rate() stays correct across scrapes and worker
            # respawns (merge_worker_series resets baselines on pid change).
            "worker_tick_phase_seconds": Counter(
                "sentio_tpu_worker_tick_phase_seconds_total",
                "cumulative pump-iteration seconds per named phase, per "
                "worker replica (fleet-merged from telemetry frames)",
                ["replica", "phase"], registry=r,
            ),
            "worker_tick_phase_ticks": Counter(
                "sentio_tpu_worker_tick_phase_ticks_total",
                "pump iterations observed per named phase, per worker "
                "replica (fleet-merged from telemetry frames)",
                ["replica", "phase"], registry=r,
            ),
            "worker_verify": Counter(
                "sentio_tpu_worker_verify_total",
                "answer verifications landed inside a worker process, by "
                "mode and outcome (fleet-merged from telemetry frames)",
                ["replica", "mode", "outcome"], registry=r,
            ),
            "worker_compiles": Counter(
                "sentio_tpu_worker_compiles_total",
                "XLA compilations observed inside a worker process at "
                "registered jit families (fleet-merged)",
                ["replica", "family"], registry=r,
            ),
            "worker_events": Counter(
                "sentio_tpu_worker_events_total",
                "other worker-process counter series, flattened to one "
                "bounded series label (fleet-merged)",
                ["replica", "series"], registry=r,
            ),
            "worker_observed_sum": Counter(
                "sentio_tpu_worker_observed_sum",
                "worker-process histogram value sums per series "
                "(fleet-merged; pairs with ..._observed_count for means)",
                ["replica", "series"], registry=r,
            ),
            "worker_observed_count": Counter(
                "sentio_tpu_worker_observed_count",
                "worker-process histogram observation counts per series "
                "(fleet-merged)",
                ["replica", "series"], registry=r,
            ),
            # telemetry silence made observable: seconds since the last
            # ACCEPTED telemetry frame from each worker. Climbs ~1 s/s
            # through a partition, snaps back at the first post-heal frame —
            # monitoring.yaml's SentioTpuWorkerTelemetryStale alerts on it
            "worker_telemetry_age": Gauge(
                "sentio_tpu_worker_telemetry_age_seconds",
                "seconds since the router last merged a telemetry frame "
                "from this replica's worker",
                ["replica"], registry=r,
            ),
            # the telemetry epoch fence + cardinality guard, visible:
            # stale_epoch = a healed worker's pre-partition buffer hit the
            # fence (normal during incidents); cardinality = a worker tried
            # to mint more distinct series than the per-replica cap
            "worker_telemetry_dropped": Counter(
                "sentio_tpu_worker_telemetry_dropped_total",
                "worker telemetry frames/series dropped at merge",
                ["replica", "reason"], registry=r,
            ),
            # elastic fleet: membership is now a runtime variable, so the
            # live size is a gauge and every autoscaler decision a counter
            # (monitoring.yaml's SentioTpuAutoscaleFlapping alerts on
            # decision churn; ...FleetAtMaxSaturated on the gauge below)
            "fleet_size": Gauge(
                "sentio_tpu_fleet_live_replicas",
                "live (non-retired) replicas currently wired into the "
                "serving set",
                [], registry=r,
            ),
            "autoscale_decisions": Counter(
                "sentio_tpu_autoscale_decisions_total",
                "executed autoscaler decisions by direction and the "
                "signal that triggered them",
                ["direction", "reason"], registry=r,
            ),
            "fleet_saturated": Gauge(
                "sentio_tpu_fleet_at_max_saturated",
                "1 while the fleet sits at AUTOSCALE_MAX_REPLICAS with "
                "the windowed load still above the scale-out thresholds",
                [], registry=r,
            ),
        }
        # a reader that names a label must find it: every program from the
        # start, zeros included (the benchmark's prom_delta reads nothing
        # where a label its file names is absent)
        from sentio_tpu.infra.phases import DEVICE_PROGRAMS

        for program in DEVICE_PROGRAMS:
            self._prom["device_program"].labels(program)

    # ------------------------------------------------------------- recording

    def record_request(self, endpoint: str, status: int, latency_s: float) -> None:
        if not self.enabled:
            return
        self.memory.inc("requests", (endpoint, str(status)))
        self.memory.observe("request_latency", (endpoint,), latency_s)
        if self._prom:
            self._prom["requests"].labels(endpoint, str(status)).inc()
            self._prom["request_latency"].labels(endpoint).observe(latency_s)

    def record_ttft(self, seconds: float, path: str = "paged") -> None:
        """Time-to-first-token for one sequence (``path``: paged | stream)."""
        if not self.enabled:
            return
        self.memory.observe("ttft", (path,), seconds)
        if self._prom:
            self._prom["ttft"].labels(path).observe(seconds)

    def record_tpot(self, seconds: float, path: str = "paged") -> None:
        """Mean time-per-output-token for one sequence (excludes the first
        token — that interval is TTFT's)."""
        if not self.enabled:
            return
        self.memory.observe("tpot", (path,), seconds)
        if self._prom:
            self._prom["tpot"].labels(path).observe(seconds)

    def record_tick(self, duration_s: float, active_slots: int,
                    queue_depth: int) -> None:
        """One engine pump tick: dispatch wall time plus the point-in-time
        occupancy/queue gauges operators watch between scrapes."""
        if not self.enabled:
            return
        self.memory.observe("tick_duration", (), duration_s)
        self.set_serving_stat("tick_active_slots", float(active_slots))
        self.set_serving_stat("tick_queue_depth", float(queue_depth))
        if self._prom:
            self._prom["tick_duration"].observe(duration_s)

    def record_tick_phases(self, phase_s: dict) -> None:
        """One pump iteration's phase split (seconds per phase, keys from
        :data:`sentio_tpu.infra.phases.TICK_PHASES`). Unknown keys are
        DROPPED — the ``phase`` label space is a fixed bounded set and a
        typo'd phase name must not mint a new metric series."""
        if not self.enabled:
            return
        from sentio_tpu.infra.phases import TICK_PHASES

        hist = self._prom.get("tick_phase")
        for key in TICK_PHASES:
            value = phase_s.get(key)
            if value is None:
                continue
            self.memory.observe("tick_phase", (key,), float(value))
            if hist is not None:
                hist.labels(phase=key).observe(float(value))

    def record_request_stage(self, stage: str, seconds: float) -> None:
        """One closed request stage. A stage outside ``REQUEST_STAGES``
        RAISES: the writer is this program's own code, and a typo'd stage
        must fail there, not mint a series."""
        from sentio_tpu.infra.phases import REQUEST_STAGES

        if stage not in REQUEST_STAGES:
            raise KeyError(f"unknown stage {stage!r} (bounded set: {REQUEST_STAGES})")
        if not self.enabled:
            return
        self.memory.observe("request_stage", (stage,), float(seconds))
        hist = self._prom.get("request_stage")
        if hist is not None:
            hist.labels(stage=stage).observe(float(seconds))

    def record_row_steps(self, counts: dict, kv_pages: Optional[dict] = None,
                         moe: Optional[dict] = None,
                         prefill_latent: Optional[dict] = None,
                         prefill_turns: Optional[dict] = None,
                         conv_state: Optional[dict] = None,
                         ssm_state: Optional[dict] = None,
                         lane_admissions: Optional[dict] = None) -> None:
        """One harvested tick's row-steps by kind (useful / halted / empty),
        the K/V page blocks of its sub-steps (held / tabled / behind_window), of a routed
        family its expert layers' pairs (routed / held) and expert-steps
        (held / touched) — ``MOE_KINDS`` as ``<series>_<kind>`` — of a
        latent family its prefill tokens (new / expanded), chunked
        prefill's turns (taken / waited), and of a family with convolution
        state what its prefill rows started from (zero / tail / carried) and
        the page tails written, of a family with Mamba layers the same starts
        (zero / snapshot / carried), the snapshots written and evicted, the
        state updates its decode sub-steps did and skipped, and the prefix
        tokens cut back; and the lanes the step's admissions took (free /
        spent)."""
        if not self.enabled:
            return
        from sentio_tpu.infra.phases import (
            CONV_START_KINDS,
            KV_PAGE_KINDS,
            LANE_ADMISSION_KINDS,
            PREFILL_LATENT_KINDS,
            PREFILL_TURN_KINDS,
            ROW_STEP_KINDS,
            SSM_ROW_UPDATE_KINDS,
            SSM_SNAPSHOT_EVENTS,
            SSM_START_KINDS,
        )

        moe = moe or {}
        for name, kinds, tick in (
                ("row_steps", ROW_STEP_KINDS, counts),
                ("kv_pages", KV_PAGE_KINDS, kv_pages or {}),
                ("moe_pairs", ("routed", "held"),
                 {k: moe.get(f"pairs_{k}", 0) for k in ("routed", "held")}),
                ("moe_expert_steps", ("held", "touched"),
                 {k: moe.get(f"experts_{k}", 0) for k in ("held", "touched")}),
                ("prefill_latent", PREFILL_LATENT_KINDS, prefill_latent or {}),
                ("conv_starts", CONV_START_KINDS, conv_state or {}),
                ("ssm_starts", SSM_START_KINDS, ssm_state or {}),
                ("ssm_snapshots", SSM_SNAPSHOT_EVENTS, ssm_state or {}),
                ("ssm_row_updates", SSM_ROW_UPDATE_KINDS, ssm_state or {}),
                ("prefill_turns", PREFILL_TURN_KINDS, prefill_turns or {}),
                ("lane_admissions", LANE_ADMISSION_KINDS, lane_admissions or {})):
            if name.startswith(("moe", "prefill_latent", "conv", "ssm")) and not any(tick.values()):
                continue  # no series where no such family is served
            counter = self._prom.get(name)
            for kind in kinds:
                n = int(tick.get(kind, 0))
                self.memory.inc(name, (kind,), n)
                if counter is not None:
                    counter.labels(**{"event" if name == "ssm_snapshots" else "kind": kind}).inc(n)
        for name, n in (("conv_tail_pages", int((conv_state or {}).get("pages", 0))),
                        ("prefix_cut_back", int((ssm_state or {}).get("cut_back_tokens", 0)))):
            if n:
                self.memory.inc(name, (), n)
                if name in self._prom:
                    self._prom[name].inc(n)

    def record_device_program(self, program: str, seconds: float,
                              queued_s: float = 0.0) -> None:
        """One completion stamp: ``seconds`` the device ran ``program``. A
        program outside ``DEVICE_PROGRAMS`` RAISES, as a stage does (every
        label of the set is on ``/metrics`` from the start, zeros included:
        ``_build_prom``). An encoder forward also books its two parts:
        ``queued_s`` behind other device work, and ``seconds`` running."""
        from sentio_tpu.infra.phases import DEVICE_PROGRAMS, ENCODER_PROGRAMS

        if program not in DEVICE_PROGRAMS:
            raise KeyError(f"unknown program {program!r} (bounded set: {DEVICE_PROGRAMS})")
        if not self.enabled:
            return
        booked = [("device_program", program, seconds)]
        if program in ENCODER_PROGRAMS:
            booked += [("encoder_forward", "queued", queued_s),
                       ("encoder_forward", "running", seconds)]
        for name, label, value in booked:
            self.memory.inc(name, (label,), float(value))
            counter = self._prom.get(name)
            if counter is not None:
                counter.labels(label).inc(float(value))

    def set_startup_phases(self, phases: dict) -> None:
        """The tile of one start, seconds a phase (infra/startup.py). A phase
        outside ``STARTUP_PHASES`` RAISES: the writer folds into ``other``."""
        from sentio_tpu.infra.phases import STARTUP_PHASES

        gauge = self._prom.get("startup_seconds")
        for phase, seconds in phases.items():
            if phase not in STARTUP_PHASES:
                raise KeyError(f"unknown phase {phase!r} (bounded set: {STARTUP_PHASES})")
            self.memory.set_gauge("startup_seconds", (phase,), float(seconds))
            if gauge is not None:
                gauge.labels(phase).set(float(seconds))

    def record_compile_time(self, program: str, part: str, seconds: float,
                            cache: Optional[str] = None) -> None:
        """``seconds`` of one compile's ``part`` at ``program`` (a label of
        analysis/audit/fence.py: bounded), and for the backend's part what
        the persistent cache answered."""
        from sentio_tpu.infra.phases import CACHE_OUTCOMES, COMPILE_PARTS

        if part not in COMPILE_PARTS or (cache is not None and cache not in CACHE_OUTCOMES):
            raise KeyError(f"unknown compile part {part!r} or cache outcome {cache!r}")
        if not self.enabled:
            return
        self.memory.inc("compile_seconds", (program, part), float(seconds))
        if "compile_seconds" in self._prom:
            self._prom["compile_seconds"].labels(program, part).inc(float(seconds))
        if cache is not None:
            self.memory.inc("compile_cache", (program, cache))
            if "compile_cache" in self._prom:
                self._prom["compile_cache"].labels(program, cache).inc()

    def record_ingest_stage(self, stage: str, seconds: float) -> None:
        """``seconds`` of one ingest call's ``stage`` (ops/ingest.py)."""
        from sentio_tpu.infra.phases import INGEST_STAGES

        if stage not in INGEST_STAGES:
            raise KeyError(f"unknown ingest stage {stage!r} (bounded set: {INGEST_STAGES})")
        if not self.enabled:
            return
        self.memory.inc("ingest_stage_seconds", (stage,), float(seconds))
        if "ingest_stage_seconds" in self._prom:
            self._prom["ingest_stage_seconds"].labels(stage).inc(float(seconds))

    def record_bm25_update(self, kind: str) -> None:
        """One ingest call's sparse stage took path ``kind`` (ops/ingest.py)."""
        from sentio_tpu.infra.phases import BM25_UPDATE_KINDS

        if kind not in BM25_UPDATE_KINDS:
            raise KeyError(f"unknown bm25 update {kind!r} (bounded set: {BM25_UPDATE_KINDS})")
        if not self.enabled:
            return
        self.memory.inc("bm25_updates", (kind,))
        if "bm25_updates" in self._prom:
            self._prom["bm25_updates"].labels(kind).inc()

    def record_duty_cycle(self, replica: int, fractions: dict) -> None:
        """Publish one replica's host/device/idle duty-cycle fractions
        (:func:`sentio_tpu.infra.phases.duty_fractions` output — they sum
        to 1). Bounded: only the three known states are exported."""
        if not self.enabled:
            return
        gauge = self._prom.get("pump_duty_cycle")
        for state in ("host", "device", "idle"):
            value = float(fractions.get(state, 0.0))
            self.memory.set_gauge("pump_duty_cycle", (str(replica), state),
                                  value)
            if gauge is not None:
                gauge.labels(replica=str(replica), state=state).set(value)

    def record_compiles(self, family: str, n: int = 1) -> None:
        """``n`` XLA compilations at jit family ``family`` (fed by the audit
        registry's cache-miss accounting, analysis/audit/fence.py)."""
        if not self.enabled:
            return
        self.memory.inc("xla_compiles", (family,), n)
        if self._prom:
            self._prom["xla_compiles"].labels(family).inc(n)

    def record_shed(self, reason: str, n: int = 1) -> None:
        """One request dropped by admission control or deadline enforcement
        (``reason``: queue_full | draining | deadline | expired |
        cancelled | crash)."""
        if not self.enabled:
            return
        self.memory.inc("shed", (reason,), n)
        if self._prom:
            self._prom["shed"].labels(reason).inc(n)

    def record_verify(self, mode: str, outcome: str,
                      confidence: Optional[float] = None) -> None:
        """One answer-verification outcome (``mode``: sync | async | gated;
        ``outcome``: pass | warn | fail | skipped_confident |
        skipped_deadline), plus the gate's confidence score when one was
        computed."""
        if not self.enabled:
            return
        self.memory.inc("verify", (mode, outcome))
        if confidence is not None:
            self.memory.observe("verify_confidence", (), float(confidence))
        if self._prom:
            self._prom["verify_total"].labels(mode, outcome).inc()
            if confidence is not None:
                self._prom["verify_confidence"].observe(float(confidence))

    def record_tenant_admitted(self, tenant: str) -> None:
        """One request admitted through WFQ for ``tenant``."""
        if not self.enabled:
            return
        self.memory.inc("tenant_admitted", (tenant,))
        if self._prom:
            self._prom["tenant_admitted"].labels(tenant).inc()

    def record_tenant_shed(self, tenant: str, reason: str) -> None:
        """One request shed by WFQ (``reason``: tenant_quota |
        priority_batch | tenant_deficit)."""
        if not self.enabled:
            return
        self.memory.inc("tenant_shed", (tenant, reason))
        if self._prom:
            self._prom["tenant_shed"].labels(tenant, reason).inc()

    def set_replica_stat(self, replica: int, key: str, value: float) -> None:
        """Publish one point-in-time stat for one serving replica under the
        replica-labeled gauge and the JSON snapshot."""
        self.memory.set_gauge(f"replica_{replica}_{key}", (), value)
        gauge = self._prom.get("replica_stat")
        if gauge is not None:
            gauge.labels(replica=str(replica), stat=key).set(value)

    def record_heartbeat_age(self, replica: int, age_s: float) -> None:
        """Publish one replica's pump heartbeat age (0.0 = idle or fresh).
        Set each watchdog pass, so the gauge's scrape-to-scrape slope under
        a wedged pump is ~1 s/s — the stall signature dashboards alert
        on."""
        if not self.enabled:
            return
        self.memory.set_gauge("pump_heartbeat_age", (str(replica),), age_s)
        gauge = self._prom.get("pump_heartbeat_age")
        if gauge is not None:
            gauge.labels(replica=str(replica)).set(age_s)

    def record_worker_death(self, replica: int) -> None:
        """One replica worker PROCESS death (process-mode replica tier,
        runtime/worker.py) — observed via broken RPC pipe, a false
        ``proc.is_alive()``, or an explicit chaos SIGKILL. Counted once
        per corpse by the router-side shim's death latch."""
        if not self.enabled:
            return
        self.memory.inc("worker_deaths", (str(replica),))
        counter = self._prom.get("worker_deaths")
        if counter is not None:
            counter.labels(str(replica)).inc()

    def record_worker_incarnation(self, replica: int, epoch: int) -> None:
        """Publish one replica slot's CURRENT worker incarnation epoch
        (worker registry, runtime/replica.py) — set at every socket
        (re)registration."""
        if not self.enabled:
            return
        self.memory.set_gauge("worker_incarnation", (str(replica),),
                              float(epoch))
        gauge = self._prom.get("worker_incarnation")
        if gauge is not None:
            gauge.labels(str(replica)).set(float(epoch))

    def record_stale_frames(self, replica: int, n: int = 1) -> None:
        """Count worker frames dropped for carrying a stale incarnation
        epoch — a reconnected worker's pre-partition traffic hitting the
        epoch fence instead of resurrecting dead tickets."""
        if not self.enabled or n <= 0:
            return
        self.memory.inc("worker_stale_frames", (str(replica),), float(n))
        counter = self._prom.get("worker_stale_frames")
        if counter is not None:
            counter.labels(str(replica)).inc(n)

    def record_worker_reconnect(self, outcome: str) -> None:
        """One socket-worker reconnection outcome (``heal`` | ``respawn``
        | ``reconnected`` | ``rejected_auth`` | ``rejected_proto``) —
        the churn series behind SentioTpuWorkerFlapping."""
        if not self.enabled:
            return
        self.memory.inc("worker_reconnects", (outcome,))
        counter = self._prom.get("worker_reconnects")
        if counter is not None:
            counter.labels(outcome).inc()

    def record_fleet_size(self, live: int) -> None:
        """Publish the live (non-retired) replica count — re-derived by
        ``ReplicaSet`` whenever membership changes (join/retire), so the
        gauge steps exactly at the scale events."""
        if not self.enabled:
            return
        self.memory.set_gauge("fleet_size", (), float(live))
        gauge = self._prom.get("fleet_size")
        if gauge is not None:
            gauge.set(float(live))

    def record_autoscale_decision(self, direction: str, reason: str) -> None:
        """One EXECUTED autoscaler decision (``direction``: out | in;
        ``reason``: busy | backlog | idle) — the churn series behind
        SentioTpuAutoscaleFlapping."""
        if not self.enabled:
            return
        self.memory.inc("autoscale_decisions", (direction, reason))
        counter = self._prom.get("autoscale_decisions")
        if counter is not None:
            counter.labels(direction, reason).inc()

    def record_fleet_saturation(self, value: float) -> None:
        """1.0 while the fleet is pinned at max replicas AND the windowed
        load still clears the scale-out thresholds; 0.0 otherwise."""
        if not self.enabled:
            return
        self.memory.set_gauge("fleet_saturated", (), float(value))
        gauge = self._prom.get("fleet_saturated")
        if gauge is not None:
            gauge.set(float(value))

    def record_stream_resume(self, outcome: str) -> None:
        """One mid-flight stream resume outcome (``outcome``: resumed |
        exhausted | failed | opt_out) — the counter behind
        ``sentio_tpu_stream_resumes_total``."""
        if not self.enabled:
            return
        self.memory.inc("stream_resumes", (outcome,))
        counter = self._prom.get("stream_resumes")
        if counter is not None:
            counter.labels(outcome).inc()

    # ----------------------------------------------- fleet telemetry merge

    def export_worker_series(self) -> dict[str, Any]:
        """CUMULATIVE snapshot of this process's counter/histogram registry,
        the payload a worker's telemetry frame carries (runtime/worker.py).
        Cheap: three dict copies under the memory lock, no histogram windows
        (quantiles stay worker-local — only monotonic aggregates ship, so
        the router can difference them into deltas safely)."""
        memory = self.memory
        with memory._lock:
            return {
                "counters": dict(memory.counters),
                "histo_count": dict(memory._histo_total),
                "histo_sum": dict(memory._histo_sum),
            }

    def _publish_worker_delta(self, replica: str, name: str,
                                 labels: tuple, delta_sum: float,
                                 delta_count: float, is_histo: bool) -> None:
        """Route one accepted worker-series delta into the {replica}-labeled
        fleet families. Known bounded-label series keep their label
        structure (phase / mode+outcome / family); everything else flattens
        into one ``series`` label so an unknown worker series can never mint
        an unbounded label SET, only a new value under the guard's cap."""
        if is_histo:
            if name == "tick_phase" and len(labels) == 1:
                self.memory.inc("worker_tick_phase_seconds",
                                (replica, labels[0]), delta_sum)
                self.memory.inc("worker_tick_phase_ticks",
                                (replica, labels[0]), delta_count)
                sec = self._prom.get("worker_tick_phase_seconds")
                cnt = self._prom.get("worker_tick_phase_ticks")
                if sec is not None and delta_sum:
                    sec.labels(replica, labels[0]).inc(delta_sum)
                if cnt is not None and delta_count:
                    cnt.labels(replica, labels[0]).inc(delta_count)
                return
            series = "_".join((name,) + labels) if labels else name
            self.memory.inc("worker_observed_sum", (replica, series),
                            delta_sum)
            self.memory.inc("worker_observed_count", (replica, series),
                            delta_count)
            osum = self._prom.get("worker_observed_sum")
            ocnt = self._prom.get("worker_observed_count")
            if osum is not None and delta_sum > 0:
                osum.labels(replica, series).inc(delta_sum)
            if ocnt is not None and delta_count:
                ocnt.labels(replica, series).inc(delta_count)
            return
        if name == "verify" and len(labels) == 2:
            self.memory.inc("worker_verify", (replica,) + labels, delta_sum)
            counter = self._prom.get("worker_verify")
            if counter is not None:
                counter.labels(replica, labels[0], labels[1]).inc(delta_sum)
            return
        if name == "xla_compiles" and len(labels) == 1:
            self.memory.inc("worker_compiles", (replica, labels[0]),
                            delta_sum)
            counter = self._prom.get("worker_compiles")
            if counter is not None:
                counter.labels(replica, labels[0]).inc(delta_sum)
            return
        series = "_".join((name,) + labels) if labels else name
        self.memory.inc("worker_events", (replica, series), delta_sum)
        counter = self._prom.get("worker_events")
        if counter is not None:
            counter.labels(replica, series).inc(delta_sum)

    def merge_worker_series(self, replica: int, series: dict,
                            epoch: int = 0,
                            pid: Optional[int] = None) -> dict:
        """Fold one worker telemetry frame's CUMULATIVE series snapshot
        (:meth:`export_worker_series` shape) into the router's fleet
        families under ``{replica}`` labels, differencing against the last
        accepted snapshot.

        Fencing & continuity contract (ISSUE 16 leg 4):

        * ``epoch`` below the last accepted epoch → the whole frame is a
          healed worker's pre-partition buffer draining late; DROPPED and
          counted (``reason="stale_epoch"``) — merging it would double-count
          everything the current epoch already shipped.
        * same pid, same-or-higher epoch (a HEAL) → baselines are KEPT: the
          process never died, its cumulative registry kept growing, so the
          next delta is exactly the partition window's truth.
        * pid change (a RESPAWN) → baselines reset to zero: the fresh
          process's registry restarts from nothing and differencing against
          the corpse's totals would swallow the first interval.
        """
        if not self.enabled or not isinstance(series, dict):
            return {"accepted": False, "merged": 0}
        rep = str(replica)
        merged = 0
        with self._worker_lock:
            state = self._worker_last.get(replica)
            if state is None:
                state = {"pid": None, "epoch": int(epoch), "cum": {}}
                self._worker_last[replica] = state
            if int(epoch) < state["epoch"]:
                self.record_telemetry_dropped(replica, "stale_epoch")
                return {"accepted": False, "merged": 0}
            if pid is not None and state["pid"] not in (None, pid):
                state["cum"] = {}  # respawn: fresh process, fresh baselines
            state["epoch"] = int(epoch)
            if pid is not None:
                state["pid"] = pid
            cum = state["cum"]
            plan: list[tuple] = []
            for kind, is_histo in (("counters", False),
                                   ("histo_sum", True)):
                counts = series.get("histo_count", {}) if is_histo else {}
                for key, value in (series.get(kind) or {}).items():
                    name, labels = _parse_series_key(str(key))
                    if name is None:
                        self.record_telemetry_dropped(replica, "malformed")
                        continue
                    scoped = f"{kind}:{key}"
                    if (scoped not in cum and
                            len(cum) >= 2 * MAX_WORKER_SERIES_PER_REPLICA):
                        self.record_telemetry_dropped(replica, "cardinality")
                        continue
                    last_sum, last_count = cum.get(scoped, (0.0, 0.0))
                    delta_sum = max(float(value) - last_sum, 0.0)
                    new_count = float(counts.get(key, 0.0))
                    delta_count = max(new_count - last_count, 0.0)
                    cum[scoped] = (float(value), new_count)
                    if delta_sum <= 0.0 and delta_count <= 0.0:
                        continue
                    plan.append((name, labels, delta_sum, delta_count,
                                 is_histo))
        for name, labels, delta_sum, delta_count, is_histo in plan:
            self._publish_worker_delta(rep, name, labels, delta_sum,
                                          delta_count, is_histo)
            merged += 1
        return {"accepted": True, "merged": merged}

    def record_telemetry_age(self, replica: int, age_s: float) -> None:
        """Publish seconds since the last ACCEPTED telemetry frame from one
        replica's worker — set each supervisor pass, so the gauge climbs
        ~1 s/s through a partition and snaps back at the first post-heal
        frame (the SentioTpuWorkerTelemetryStale signal)."""
        if not self.enabled:
            return
        self.memory.set_gauge("worker_telemetry_age", (str(replica),),
                              float(age_s))
        gauge = self._prom.get("worker_telemetry_age")
        if gauge is not None:
            gauge.labels(str(replica)).set(float(age_s))

    def record_telemetry_dropped(self, replica: int, reason: str,
                                 n: int = 1) -> None:
        """Count telemetry frames/series refused at merge (``reason``:
        stale_epoch | cardinality | malformed)."""
        if not self.enabled or n <= 0:
            return
        self.memory.inc("worker_telemetry_dropped", (str(replica), reason),
                        float(n))
        counter = self._prom.get("worker_telemetry_dropped")
        if counter is not None:
            counter.labels(str(replica), reason).inc(n)

    def worker_telemetry_epoch(self, replica: int) -> Optional[int]:
        """The last accepted telemetry epoch for one replica (None before
        any frame merged) — the epoch-fence drill's assertion hook."""
        with self._worker_lock:
            state = self._worker_last.get(replica)
            return None if state is None else state["epoch"]

    def record_replica_health(self, replica: int, state: str) -> None:
        """Publish one replica's health-state transition: the new state's
        series goes to 1 and every other state's to 0, so
        ``sentio_tpu_replica_health{replica="K"}`` always sums to 1 and a
        dashboard can plot the machine's position directly."""
        from sentio_tpu.runtime.replica import HEALTH_STATES

        for name in HEALTH_STATES:
            value = 1.0 if name == state else 0.0
            self.memory.set_gauge("replica_health", (str(replica), name),
                                  value)
            gauge = self._prom.get("replica_health")
            if gauge is not None:
                gauge.labels(replica=str(replica), state=name).set(value)

    # --------------------------------------------------------------- helpers

    def adjust_inflight(self, delta: int) -> None:
        # gauge writes stay INSIDE the lock: two concurrent finishes could
        # otherwise write counter values out of order and leave the HPA
        # scaling signal stuck at a phantom non-zero on an idle pod
        with self._inflight_lock:
            self._inflight = max(self._inflight + delta, 0)
            value = float(self._inflight)
            self.memory.set_gauge("inflight", (), value)
            if self._prom:
                self._prom["inflight"].set(value)

    @contextmanager
    def track_request(self, endpoint: str):
        t0 = time.perf_counter()
        status = 200
        self.adjust_inflight(+1)
        try:
            yield
        except Exception:
            status = 500
            raise
        finally:
            self.adjust_inflight(-1)
            self.record_request(endpoint, status, time.perf_counter() - t0)

    # ---------------------------------------------------------------- export

    def set_serving_stat(self, key: str, value: float) -> None:
        """Publish one point-in-time decode-service stat under both exports:
        the labeled ``sentio_tpu_serving_stat`` gauge and the JSON
        snapshot."""
        self.memory.set_gauge(f"serving_{key}", (), value)
        gauge = self._prom.get("serving_stat")
        if gauge is not None:
            gauge.labels(stat=key).set(value)

    def set_encoder_param_bytes(self, model: str, held: int) -> None:
        """The weights ``model`` (``embedder`` / ``reranker``) holds, bytes."""
        self.memory.set_gauge("encoder_param_bytes", (model,), float(held))
        gauge = self._prom.get("encoder_param_bytes")
        if gauge is not None:
            gauge.labels(model=model).set(held)

    def bump_serving_total(self, event: str, lifetime_total: float) -> None:
        """Publish a MONOTONIC decode-service total as a Counter (rate()
        stays correct across restarts — Gauge semantics would not). The
        engine reports lifetime totals, so this tracks deltas."""
        last = self._serving_last.get(event, 0.0)
        delta = max(lifetime_total - last, 0.0)
        self._serving_last[event] = lifetime_total
        self.memory.set_gauge(f"serving_{event}", (), lifetime_total)
        counter = self._prom.get("serving_total")
        if counter is not None and delta:
            counter.labels(event=event).inc(delta)

    def export_prometheus(self) -> bytes:
        if self.registry is not None:
            return generate_latest(self.registry)
        return b""

    def export_json(self) -> dict[str, Any]:
        return self.memory.snapshot()


_collector: Optional[MetricsCollector] = None


def get_metrics() -> MetricsCollector:
    global _collector
    if _collector is None:
        _collector = MetricsCollector()
    return _collector


def set_metrics(collector: Optional[MetricsCollector]) -> None:
    global _collector
    _collector = collector
