"""Chrome/Perfetto trace export for flight-recorder timelines.

Turns the flight recorder's tick ring + request table into the Chrome
Trace Event Format (the JSON ``ui.perfetto.dev`` and ``chrome://tracing``
open directly): one process row per serving replica, the pump's ticks as
slices with their named phases (infra/phases.py) nested inside, each
request's span tree (infra/tracing.py: the request stages from pool_wait to
decode, the audit's under ``verify``) on a lane of its own, and replica
health transitions as instants.

Everything here is a PURE function over plain dicts — the exact shapes
``FlightRecorder.timeline()``/``records()`` return — so the exporter is
golden-testable with hand-written fixtures and never touches a clock.

Layout conventions (Chrome trace event fields):

* ``pid`` = replica id (one process row per replica; metadata events name
  them ``replica N``);
* ``tid 0`` = the decode pump: one ``X`` (complete) slice per tick, its
  ``phase_ms`` laid out as child slices in canonical phase order from the
  tick's start — phases sum to the tick's ``pump_ms`` by construction
  (runtime/service.py), so children exactly tile the parent;
* ``tid 1..`` = request lanes: the ``request`` root (receipt → finish)
  and every span the request wrote, each a slice carrying its parent and
  fields — an async or gated audit's ``verify`` span visibly overhangs the
  root, whose record closed when the answer did;
* the ``startup`` record (infra/startup.py: process start → ready, a slice
  a phase) is a lane of its own named ``startup``;
* health transitions ride ``tid 0`` as process-scoped instants.

Timestamps: flight records share one ``perf_counter`` origin
(``FlightRecorder._t0``); Chrome wants microseconds, so ``ts = t_s * 1e6``.
"""

from __future__ import annotations

from typing import Optional

from sentio_tpu.infra.flight import ROOT_SPAN, STARTUP_ID, shift_spans, span_tree
from sentio_tpu.infra.phases import TICK_PHASES

__all__ = ["build_chrome_trace", "build_fleet_trace", "flight_to_chrome"]

# tick args copied onto the tick slice (bounded, plot-friendly)
_TICK_ARGS = (
    "active_slots", "queue_depth", "inbox_depth", "prefill_tokens",
    "decode_tokens", "free_pages", "xla_compiles",
    # what the device ran since the previous tick record, by program, and
    # the completion stamps that account lost or took out of order
    "device_ms", "stamps_dropped", "stamps_set_aside",
)

_PUMP_TID = 0
_REQUEST_TID_BASE = 1

# fleet traces: worker lanes get synthetic pids well above any router
# replica id — one process row per worker INCARNATION, so a slot that
# healed or respawned mid-trace shows its epochs as separate lanes
_FLEET_PID_BASE = 1000


def _us(seconds: float) -> float:
    """Timeline seconds → Chrome microseconds (µs-rounded for stability)."""
    return round(float(seconds) * 1e6, 1)


def _tick_events(ticks: list[dict]) -> list[dict]:
    events: list[dict] = []
    for tick in ticks:
        pid = int(tick.get("replica", 0))
        if tick.get("event") == "replica_health":
            # health transition: process-scoped instant on the pump row
            events.append({
                "name": f"health:{tick.get('state', '?')}",
                "ph": "i", "s": "p",
                "pid": pid, "tid": _PUMP_TID,
                "ts": _us(tick["t_s"]),
                "args": {k: v for k, v in tick.items()
                         if k in ("state", "prior", "reason", "tick")},
            })
            continue
        phase_ms = tick.get("phase_ms")
        pump_ms = tick.get("pump_ms", tick.get("dur_ms"))
        if pump_ms is None:
            continue  # not a pump tick event (e.g. inbox_handoff markers)
        # the record is stamped at the END of the covered span
        t_end = tick["t_s"]
        t_start = t_end - pump_ms / 1e3
        events.append({
            "name": f"tick {tick.get('tick', '?')}",
            "ph": "X", "pid": pid, "tid": _PUMP_TID,
            "ts": _us(t_start), "dur": round(float(pump_ms) * 1e3, 1),
            "args": {k: tick[k] for k in _TICK_ARGS if k in tick},
        })
        if not phase_ms:
            continue
        # phases tile the tick in canonical order (sum == pump_ms by
        # construction, so the children nest exactly inside the parent)
        cursor = t_start
        for phase in TICK_PHASES:
            dur_ms = phase_ms.get(phase)
            if not dur_ms:
                continue
            events.append({
                "name": phase,
                "ph": "X", "pid": pid, "tid": _PUMP_TID,
                "ts": _us(cursor), "dur": round(float(dur_ms) * 1e3, 1),
                "args": {},
            })
            cursor += dur_ms / 1e3
    return events


def _request_events(records: list[dict]) -> tuple[list[dict], dict, dict]:
    """Request lanes, one per record per replica, laid out from the
    record's ``spans`` (infra/tracing.py wrote them; flight.span_tree adds
    the ``request`` root): one slice a span, its parent and fields as args.
    Returns the events plus {pid: max_tid} so thread-name metadata can be
    emitted, and {(pid, tid): name} of the lanes that are no request's (the
    ``startup`` track)."""
    events: list[dict] = []
    lanes: dict[int, int] = {}
    named: dict[tuple, str] = {}
    for record in records:
        pid = int((record.get("engine") or {}).get("replica_id", 0))
        tid = lanes.get(pid, _REQUEST_TID_BASE)
        lanes[pid] = tid + 1
        rid = record.get("request_id", "?")
        if rid == STARTUP_ID:
            named[(pid, tid)] = STARTUP_ID
        spans = record.get("spans") or []
        if not spans or spans[0]["name"] != ROOT_SPAN:
            spans = span_tree(record)
        for sp in spans:
            root = sp["name"] == ROOT_SPAN
            args = dict(sp.get("fields") or {})
            if root:
                args.update({k: record[k] for k in
                             ("status", "mode", "endpoint", "question_chars",
                              "ttft_server_ms", "stages_ms", "stream_lag_max_ms",
                              "process_start_unix", "ready_s", "phases", "ingest")
                             if k in record})
            else:
                args["parent"] = sp["parent"]
                if sp["name"] == "verify" and record.get("verify"):
                    args.update({k: record["verify"][k] for k in
                                 ("mode", "outcome", "confidence", "skipped")
                                 if k in record["verify"]})
            events.append({
                "name": (rid if rid == STARTUP_ID else f"request {rid}") if root else sp["name"],
                "ph": "X", "pid": pid, "tid": tid,
                "ts": _us(sp["t0_s"]),
                "dur": _us(sp["t1_s"] - sp["t0_s"]),
                "args": args,
            })
    return events, lanes, named


def build_chrome_trace(ticks: list[dict], records: list[dict],
                       label: str = "sentio-tpu") -> dict:
    """Chrome Trace Event Format JSON (dict form) from flight tick events
    + request records. Pure and deterministic: same inputs, same output —
    the golden test pins this."""
    events: list[dict] = []
    pids: set[int] = set()
    tick_events = _tick_events(ticks)
    request_events, lanes, named = _request_events(records)
    for event in tick_events + request_events:
        pids.add(event["pid"])
    # metadata rows first: name each replica's process + its lanes
    for pid in sorted(pids):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": f"replica {pid}"}})
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": _PUMP_TID, "args": {"name": "pump"}})
        for tid in range(_REQUEST_TID_BASE, lanes.get(pid, _REQUEST_TID_BASE)):
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": named.get((pid, tid),
                                                                  f"request lane {tid}")}})
    # stable order for byte-stable golden artifacts (Chrome doesn't care)
    events.extend(sorted(
        tick_events + request_events,
        key=lambda e: (e["pid"], e["tid"], e.get("ts", 0.0), e["name"]),
    ))
    return {
        "displayTimeUnit": "ms",
        "otherData": {"source": label},
        "traceEvents": events,
    }


def build_fleet_trace(workers: list[dict], router_ticks: Optional[list] = None,
                      router_records: Optional[list] = None,
                      label: str = "sentio-tpu-fleet") -> dict:
    """One coherent Chrome trace across the fleet: router request lanes on
    top (their native pids, 0..N), one synthetic process row per WORKER
    INCARNATION below, every worker timestamp re-based onto the router's
    timeline before layout.

    Each ``workers`` entry is plain data (pure function — the golden test
    hands fixtures): ``{"replica", "epoch", "shift_s", "uncertainty_s",
    "ticks", "records"}`` where ``shift_s`` is the caller-computed
    worker-timeline → router-timeline correction
    (``worker_origin − clock_offset − router_origin`` for cross-process
    clocks; see ProcessReplica.fetch_flight) and ``uncertainty_s`` is the
    ClockSync bound, stamped on the lane name — a reader can see exactly
    how far causality claims stretch.

    An entry may also carry ``"status": "retired" | "dead"`` — a worker
    incarnation that no longer answers but whose last cached telemetry
    frame the router still holds. Its lane renders from that cached data
    (or as an empty named lane when even that is gone) with the status
    suffixed to the lane name, so churn reads as history instead of a
    silently missing row."""
    all_ticks = [dict(t) for t in (router_ticks or [])]
    all_records = [dict(r) for r in (router_records or [])]
    names: dict[int, str] = {}
    for worker in workers:
        replica = int(worker.get("replica", 0))
        epoch = int(worker.get("epoch", 0))
        shift = float(worker.get("shift_s", 0.0))
        pid = _FLEET_PID_BASE * (replica + 1) + epoch
        bound = worker.get("uncertainty_s")
        status = str(worker.get("status") or "").strip().lower()
        names[pid] = (
            f"worker {replica} epoch {epoch}"
            + (f" (clock ±{float(bound) * 1e3:.1f}ms)"
               if bound is not None else " (clock unaligned)")
            + (f" ({status})" if status else "")
        )
        for tick in worker.get("ticks") or []:
            shifted = dict(tick, replica=pid)
            if "t_s" in shifted:
                shifted["t_s"] = round(float(shifted["t_s"]) + shift, 6)
            all_ticks.append(shifted)
        for record in worker.get("records") or []:
            shifted = dict(record)
            engine = dict(shifted.get("engine") or {})
            engine["replica_id"] = pid
            if engine.get("t_submit_s") is not None:
                engine["t_submit_s"] = round(
                    float(engine["t_submit_s"]) + shift, 6)
            shifted["engine"] = engine
            if shifted.get("t_start_s") is not None:
                shifted["t_start_s"] = round(
                    float(shifted["t_start_s"]) + shift, 6)
            if shifted.get("spans"):
                shifted["spans"] = shift_spans(shifted["spans"], shift)
            all_records.append(shifted)
    trace = build_chrome_trace(all_ticks, all_records, label=label)
    named: set[int] = set()
    for event in trace["traceEvents"]:
        if (event.get("ph") == "M" and event.get("name") == "process_name"
                and event["pid"] in names):
            event["args"]["name"] = names[event["pid"]]
            named.add(event["pid"])
    # dead/retired incarnations whose cached frame carried no ticks or
    # records produce no events, so build_chrome_trace never names their
    # pid — force the metadata row so the lane still appears in the trace
    for pid in sorted(set(names) - named):
        trace["traceEvents"].insert(0, {
            "name": "process_name", "ph": "M", "pid": pid,
            "tid": 0, "args": {"name": names[pid]},
        })
    return trace


def flight_to_chrome(recorder=None, request_id: Optional[str] = None,
                     label: str = "sentio-tpu") -> Optional[dict]:
    """Export a live flight recorder: the WHOLE timeline (``sentio trace
    --chrome``), or one request's record + its tick window
    (``/debug/flight/{id}?format=chrome``). Returns None when the request
    id has no record."""
    if recorder is None:
        from sentio_tpu.infra.flight import get_flight_recorder

        recorder = get_flight_recorder()
    if request_id is not None:
        record = recorder.get(request_id)
        if record is None:
            return None
        return build_chrome_trace(record.pop("ticks", []), [record],
                                  label=label)
    return build_chrome_trace(recorder.timeline(), recorder.records(),
                              label=label)
