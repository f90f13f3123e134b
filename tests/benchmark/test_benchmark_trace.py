"""The trace reduction on a slice of a real TPU v5e trace (PR 24's first
chip run), against the numbers written beside it."""

import json
from pathlib import Path

import pytest

from benchmark import trace

FIXTURES = Path(__file__).resolve().parents[2] / "benchmark" / "fixtures"
SLICE = json.loads((FIXTURES / "v5e_rag_open_slice.json").read_text())
EXPECTED = json.loads((FIXTURES / "v5e_rag_open_slice.expected.json").read_text())


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_events({"devices": SLICE["devices"], "host": SLICE["host"]},
                               layers=16, kernel="^paged_attention")


def test_busy_share_and_window(reduced):
    assert reduced["devices"] == EXPECTED["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(EXPECTED["busy_s"])
    assert reduced["window_s"] == pytest.approx(EXPECTED["window_s"])
    assert 0.0 < reduced["busy_s"] < reduced["window_s"] <= 0.440


def test_per_program_times_and_sub_steps(reduced):
    for name, want in EXPECTED["programs"].items():
        got = reduced["programs"][name]
        assert got["count"] == want["count"]
        assert got["total_ms"] == pytest.approx(want["total_ms"])
        assert got["p50_ms"] == pytest.approx(want["p50_ms"])
    tick = reduced["programs"]["jit_step_n"]
    # one tick lies whole inside the slice: 256 kernel calls / 16 layers;
    # the tick cut off by the slice's end is left out of the per-step time
    assert tick["sub_steps"] == 16 and tick["count"] == 2
    assert tick["sub_steps_ms"] / tick["sub_steps"] == pytest.approx(20.087, abs=0.001)
    assert "sub_steps" not in reduced["programs"]["jit_prior_prefill_scatter"]


def test_breakdown_groups_operations_and_labels_gaps(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert [[n, pytest.approx(t)] for n, t in EXPECTED["device_ops"]] == ops[:4]
    assert not any(name.startswith("while") for name, _t in ops)  # loops hold others' time
    gaps = reduced["breakdown"]["idle_gaps"]
    assert [[n, pytest.approx(t)] for n, t in EXPECTED["idle_gaps"]] == gaps[:3]
    assert len(ops) <= 10 and len(gaps) <= 10


def test_names_and_unions():
    text = "%slice_bitcast_fusion.92.remat = bf16[385,128,8,128]{3,2,1,0} fusion(bf16[16,385]...)"
    assert trace.op_name(text) == "slice_bitcast_fusion.92.remat"
    assert trace.op_group("slice_bitcast_fusion.92.remat") == "slice_bitcast_fusion"
    assert trace.op_group("paged_attention.188") == "paged_attention"
    assert trace.program_name("jit_step_n(7518858856421210207)") == "jit_step_n"
    total, gaps = trace.union_length([(0, 10), (5, 20), (30, 40), (32, 35)])
    assert total == 30 and gaps == [(20, 30)]
    host = [("outer", 0, 100, "t"), ("inner", 18, 14, "t"), ("far", 200, 5, "t")]
    assert trace.label_gap((20, 30), host) == "inner"
    assert trace.label_gap((500, 510), host) == "host: nothing recorded"


def test_a_reader_that_finds_nothing_returns_nothing():
    from benchmark import readers

    obs = readers.Observations(trace={"programs": {"jit_step_n": {"count": 2, "total_ms": 1.0, "p50_ms": 0.5}}})
    spec = {"reader": "trace", "program": "jit_step_n", "stat": "per_substep_ms"}
    assert readers.read_metric(spec, obs) is None  # no kernel seen, no sub-steps: left out
    assert readers.read_metric({**spec, "program": "jit_absent"}, obs) is None
    assert readers.read_metric({"reader": "client", "field": "pre_generate_ms", "stat": "p50"}, obs) is None
