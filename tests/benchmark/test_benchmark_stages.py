"""The per-layer metrics that read the program's request stages and counted
row-steps: four data files over the ``prom_delta`` reader. By hand on made-up
``/metrics`` rows (a ratio of label sums; nothing when the program lacks the
series, as the parent commit does). End to end — a traced rehearsal of the RAG
cell on the CPU reports all four as ratios of what the program counted — in
``test_benchmark_rehearsal.py``, with every other rehearsal in the tree's own
``benchmark/.work``: they share it and run one at a time."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import readers  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
STAGE_SUM = "sentio_tpu_request_stage_seconds_sum"
ROW_STEPS = "sentio_tpu_decode_row_steps_total"
NEW = ("stage_encoders_share", "stage_queue_share", "stage_prefill_share",
       "decode_rows_useful_share")

# seconds per stage summed over a window's requests: they add up to 10
STAGES = {"pool_wait": 0.5, "embed": 2.5, "sparse_fuse": 0.0, "rerank": 1.5,
          "select": 0.0, "inbox_wait": 0.75, "slot_wait": 0.25, "prefill": 4.0,
          "other": 0.5}


def rows(stage_s: dict, row_steps: dict, extra: float = 0.0):
    out = [(STAGE_SUM, {"stage": k}, v + extra) for k, v in stage_s.items()]
    out += [(STAGE_SUM, {"stage": k}, 99.0) for k in ("decode", "verify", "stream_lag")]
    return out + [(ROW_STEPS, {"kind": k}, float(v)) for k, v in row_steps.items()]


def read(name: str, before, after):
    obs = readers.Observations(prom_before=before, prom_after=after)
    return readers.read_metric(readers.load_metric("per_layer", name), obs)


@pytest.mark.parametrize("name, want", [
    ("stage_encoders_share", 40.0),      # (2.5 + 1.5) / 10
    ("stage_queue_share", 15.0),         # (0.5 + 0.75 + 0.25) / 10
    ("stage_prefill_share", 40.0),       # 4 / 10
    ("decode_rows_useful_share", 34.0),  # 340 of 1000 row-steps
])
def test_share_is_the_windows_delta_over_its_denominator(name, want):
    """What was on ``/metrics`` before the window cancels out, and the
    stages after the first token (decode, verify, stream_lag) are in no
    denominator: the three stage shares are of the time to first token."""
    steps = {"useful": 340, "halted": 260, "empty": 400}
    before = rows(dict.fromkeys(STAGES, 7.0), dict.fromkeys(steps, 50))
    after = rows({k: v + 7.0 for k, v in STAGES.items()},
                 {k: v + 50 for k, v in steps.items()})
    assert read(name, before, after) == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_series_reports_nothing(name):
    """The parent commit has neither series, and a label the file names may
    be missing (no request finished a window): the reader returns nothing
    and the line leaves the metric out. It never raises."""
    other = [("sentio_tpu_tick_phase_seconds_sum", {"phase": "deliver"}, 1.0)]
    assert read(name, other, other) is None
    short = rows({"prefill": 1.0}, {"useful": 1})
    assert read(name, [], short) is None
    idle = rows(STAGES, {"useful": 5, "halted": 5, "empty": 5})
    assert read(name, idle, idle) is None  # nothing moved: no denominator


@pytest.mark.parametrize("name", NEW)
def test_entry_names_the_layer_as_the_file_spells_it(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    assert entry["layer"] in layers and entry["unit"] == "%"
    spec = readers.load_metric("per_layer", name)
    assert spec["reader"] == "prom_delta" and set(spec["num"]) <= set(spec["den"])
