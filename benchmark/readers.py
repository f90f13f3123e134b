"""Per-layer metric readers, one kind per function.

A metric is a data file (``layer_metrics/<name>.json`` per layer,
``end_to_end/<name>.json`` end to end) that names a reader kind and what it
reads; a later PR adds a metric by adding such a file. Every reader gets the same ``Observations`` of one run and returns a
number, or ``None`` when it finds nothing to read — the harness then leaves
the metric out of the line.

kinds: ``client`` (the load generator's own spans), ``setup`` (process start
to the end of warm-up), ``prom_delta`` (a
counter's change over the window), ``prom_sample`` (a gauge polled through
the window), ``info`` (a path into ``/info`` after the window), ``trace``
(the reduced device trace).
"""

from __future__ import annotations

import json
import statistics
from dataclasses import dataclass, field
from pathlib import Path

from benchmark.families import load_family
from benchmark.traffic import percentile

HERE = Path(__file__).resolve().parent


@dataclass
class Observations:
    client: dict = field(default_factory=dict)       # traffic.reduce_requests
    prom_before: list = field(default_factory=list)  # parsed /metrics rows
    prom_after: list = field(default_factory=list)
    prom_samples: list = field(default_factory=list)  # [rows, ...] at 2 Hz
    info: dict = field(default_factory=dict)
    trace: dict | None = None                        # trace.reduce_xplane
    model: dict = field(default_factory=dict)        # configuration as run
    mix: dict = field(default_factory=dict)          # traffic mix as run
    window_s: float = 0.0
    setup_s: float = 0.0
    server_env: dict = field(default_factory=dict)
    device_kind: str = ""


def _labelled(rows, series: str, label: str) -> dict[str, float]:
    out: dict[str, float] = {}
    for name, labels, value in rows:
        if name == series and label in labels:
            out[labels[label]] = out.get(labels[label], 0.0) + value
    return out


def read_client(spec: dict, obs: Observations):
    """A percentile (``p50``) of a list the load generator kept, or the
    ``rate`` of a count it kept over the window."""
    values = obs.client.get(spec["field"])
    if not values:
        return None
    if spec["stat"] == "rate":
        return values / obs.window_s
    return percentile(values, float(spec["stat"].lstrip("p")))


def read_setup(spec: dict, obs: Observations):
    return obs.setup_s or None


def read_prom_delta(spec: dict, obs: Observations):
    before = _labelled(obs.prom_before, spec["series"], spec["label"])
    after = _labelled(obs.prom_after, spec["series"], spec["label"])
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    if not delta:
        return None
    if "sum" in spec:
        return sum(delta.get(k, 0.0) for k in spec["sum"])
    if spec.get("den_all"):
        den = sum(delta.values())
        num = sum(v for k, v in delta.items() if k not in spec["num_all_but"])
    else:
        if any(k not in delta for k in spec["num"] + spec["den"]):
            return None
        num = sum(delta[k] for k in spec["num"])
        den = sum(delta[k] for k in spec["den"])
    return None if den <= 0 else spec.get("scale", 1.0) * num / den


def read_prom_sample(spec: dict, obs: Observations):
    values = []
    for rows in obs.prom_samples:
        got = _labelled(rows, spec["series"], spec["label"]).get(spec["value"])
        if got is not None:
            values.append(got)
    if not values:
        return None
    mean = statistics.fmean(values)
    if spec.get("divide_by_env"):
        mean /= float(obs.server_env[spec["divide_by_env"]])
    return spec.get("scale", 1.0) * mean


def read_info(spec: dict, obs: Observations):
    node = obs.info
    for key in spec["path"]:
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    return spec.get("scale", 1.0) * float(node)


def decode_rows_and_context(obs: Observations):
    """What the decode programs of the window worked on: the slots a
    sub-step computes (all of them, occupied or not) and the tokens of
    context the occupied rows held — the rows that shared a tick (sampled
    at 2 Hz) times the mix's mean prompt plus half an answer; ``None`` where
    no sample saw a tick. Every roofline of a decode program or kernel takes
    its context from here, so two of them cannot disagree about it."""
    occupied = read_prom_sample({"series": "sentio_tpu_serving_stat", "label": "stat",
                                 "value": "tick_active_slots"}, obs)
    lo, hi = obs.mix["shapes"]["prompt_tokens"]
    per_row = (lo + hi) / 2 + int(obs.server_env["LLM_MAX_TOKENS"]) / 2
    return int(obs.server_env["LLM_MAX_BATCH"]), None if occupied is None else occupied * per_row


def read_trace(spec: dict, obs: Observations):
    if not obs.trace:
        return None
    prog = obs.trace["programs"].get(spec["program"])
    if not prog or not prog["count"]:
        return None
    stat = spec["stat"]
    if stat == "per_exec_p50_ms":
        return prog["p50_ms"]
    if stat == "kernel_roofline":
        from benchmark.roofline import least_time_s

        # least time of ONE call of the named kernel, by the family's count of
        # what the call must move and compute, over the kernel's median call
        kernel = prog.get("kernels", {}).get(spec["kernel"])
        cost_of = load_family(obs.model).KERNEL_COSTS.get(spec["cost"])
        rows, context = decode_rows_and_context(obs)
        if not kernel or cost_of is None or not context:
            return None
        least = least_time_s(cost_of(obs.model, rows, context), obs.device_kind)
        return 100.0 * least["seconds"] * 1e6 / kernel["p50_us"]
    sub_steps = prog.get("sub_steps")
    if not sub_steps:
        return None
    step_ms = prog["sub_steps_ms"] / sub_steps
    if stat == "per_substep_ms":
        return step_ms
    if stat == "substep_roofline":
        from benchmark.roofline import least_time_s

        rows, context = decode_rows_and_context(obs)
        cost = load_family(obs.model).decode_substep_cost(obs.model, rows, context or 0.0)
        least = least_time_s(cost, obs.device_kind)
        return 100.0 * least["seconds"] * 1e3 / step_ms
    raise ValueError(f"unknown trace stat {stat!r}")


READERS = {"client": read_client, "setup": read_setup, "prom_delta": read_prom_delta,
           "prom_sample": read_prom_sample, "info": read_info, "trace": read_trace}


METRIC_DIRS = {"per_layer": "layer_metrics", "end_to_end": "end_to_end"}


def load_metric(kind: str, name: str) -> dict:
    return json.loads((HERE / METRIC_DIRS[kind] / f"{name}.json").read_text())


def read_metric(spec: dict, obs: Observations):
    return READERS[spec["reader"]](spec, obs)
