"""The trace reduction on a slice of a real TPU v5e trace (PR 24's first
chip run), against the numbers written beside it."""

import json

import pytest
from bench_tree import REPO

from benchmark import trace

FIXTURES = REPO / "benchmark" / "fixtures"
SLICE = json.loads((FIXTURES / "v5e_rag_open_slice.json").read_text())
EXPECTED = json.loads((FIXTURES / "v5e_rag_open_slice.expected.json").read_text())
# the mistral configuration's trace block as ``kernel_block`` reads it
BLOCK = {"substep_kernel": "^paged_attention", "calls_per_substep": 16,
         "kernels": {"paged_attention": "^paged_attention"}}


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_events({"devices": SLICE["devices"], "host": SLICE["host"]}, BLOCK)


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


def test_named_kernels_are_reported_inside_each_program(reduced):
    tick = reduced["programs"]["jit_step_n"]
    got, want = tick["kernels"]["paged_attention"], EXPECTED["programs"]["jit_step_n"]["kernels"]["paged_attention"]
    # the whole tick's 256 calls and the 9 of the tick the slice cuts off
    assert got["calls"] == want["calls"] == 265
    assert got["total_ms"] == pytest.approx(want["total_ms"])
    assert got["p50_us"] == pytest.approx(want["p50_us"]) and 150 < got["p50_us"] < 200
    assert got["total_ms"] * 1e3 / got["calls"] == pytest.approx(171.8, abs=0.1)
    # a program that never calls the kernel says nothing about it
    assert "kernels" not in reduced["programs"]["jit_prior_prefill_scatter"]
    assert "kernels" not in reduced["programs"]["jit_fwd"]
    # no block, no kernel lines and no sub-steps: the rest reads the same
    bare = trace.reduce_events({"devices": SLICE["devices"], "host": SLICE["host"]})
    assert "kernels" not in bare["programs"]["jit_step_n"] and "sub_steps" not in bare["programs"]["jit_step_n"]
    assert bare["programs"]["jit_step_n"]["total_ms"] == tick["total_ms"] and bare["busy_s"] == reduced["busy_s"]
    # a layer that calls the kernel twice: half the sub-steps from the same events
    twice = trace.reduce_events({"devices": SLICE["devices"], "host": SLICE["host"]},
                                {**BLOCK, "calls_per_substep": 32})
    assert twice["programs"]["jit_step_n"]["sub_steps"] == 8


def test_a_configurations_trace_block_and_its_defaults():
    config = {"num_hidden_layers": 16, "trace": {"substep_kernel": "^paged_attention",
                                                 "kernels": {"paged_attention": "^paged_attention"}}}
    assert trace.kernel_block(config) == BLOCK
    # one layer in four has attention: the file says so
    sparse = {**config, "trace": {**config["trace"], "calls_per_substep": 4}}
    assert trace.kernel_block(sparse)["calls_per_substep"] == 4
    assert trace.kernel_block({"num_hidden_layers": 2}) == {
        "substep_kernel": "", "calls_per_substep": 2, "kernels": {}}
    for path in (FIXTURES.parent / "configs").glob("*.json"):
        block = trace.kernel_block(json.loads(path.read_text()))
        assert block["substep_kernel"] and block["kernels"], path.name


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


def kernel_observations(kernels, samples=(4.0, 4.0, 4.0)):
    """Hand-made: 16 slots of which a tick holds 4 rows on average, prompts of
    1,000 to 1,200 tokens and 96-token answers: 4 x (1,100 + 48) tokens."""
    from benchmark import readers

    model = json.loads((FIXTURES.parent / "configs" / "mistral-7b-v0.3-l16.json").read_text())
    rows = [[("sentio_tpu_serving_stat", {"stat": "tick_active_slots"}, v)] for v in samples]
    step = {"count": 3, "total_ms": 300.0, "p50_ms": 100.0, "sub_steps": 24, "sub_steps_ms": 288.0}
    if kernels:
        step["kernels"] = kernels
    return readers.Observations(
        trace={"programs": {"jit_step_n": step}}, model=model, prom_samples=rows,
        mix={"shapes": {"prompt_tokens": [1000, 1200]}}, device_kind="TPU v5 lite",
        server_env={"LLM_MAX_BATCH": "16", "LLM_MAX_TOKENS": "96"})


def test_kernel_roofline_by_hand():
    from benchmark import readers

    spec = readers.load_metric("per_layer", "paged_attn_roofline")
    assert spec["stat"] == "kernel_roofline" and spec["kernel"] == spec["cost"] == "paged_attention"
    obs = kernel_observations({"paged_attention": {"calls": 384, "total_ms": 70.0, "p50_us": 180.0}})
    # 4,592 tokens x (K and V, 8 heads of 128, bf16) = 18.8 MB at 819 GB/s = 22.97 us
    context = 4 * (1100 + 48)
    least_us = context * 2 * 8 * 128 * 2 / 819e9 * 1e6
    assert least_us == pytest.approx(22.97, abs=0.01)
    assert readers.read_metric(spec, obs) == pytest.approx(100 * least_us / 180.0)
    assert 12.7 < readers.read_metric(spec, obs) < 12.8
    # the whole step's roofline takes the SAME rows and context
    assert readers.decode_rows_and_context(obs) == (16, context)
    step = readers.read_metric(readers.load_metric("per_layer", "decode_step_mfu"), obs)
    cost = readers.load_family(obs.model).decode_substep_cost(obs.model, 16, context)
    assert step == pytest.approx(100 * cost["bytes"] / 819e9 * 1e3 / 12.0)


@pytest.mark.parametrize("what, obs, change", [
    ("the kernel is not in the trace", kernel_observations(None), {}),
    ("another kernel is", kernel_observations({"flash_attention": {"calls": 2, "total_ms": 1.0, "p50_us": 500.0}}), {}),
    ("no sample saw a tick", kernel_observations({"paged_attention": {"calls": 1, "total_ms": 0.2, "p50_us": 180.0}}, ()), {}),
    ("the family has no such cost", kernel_observations({"paged_attention": {"calls": 1, "total_ms": 0.2, "p50_us": 180.0}}),
     {"cost": "sparse_select"}),
    ("the program is not in the trace", kernel_observations({"paged_attention": {"calls": 1, "total_ms": 0.2, "p50_us": 180.0}}),
     {"program": "jit_absent"}),
])
def test_kernel_roofline_reads_nothing_rather_than_zero(what, obs, change):
    from benchmark import readers

    spec = {**readers.load_metric("per_layer", "paged_attn_roofline"), **change}
    assert readers.read_metric(spec, obs) is None, what


def test_a_reader_that_finds_nothing_returns_nothing():
    from benchmark import readers

    obs = readers.Observations(trace={"programs": {"jit_step_n": {"count": 2, "total_ms": 1.0, "p50_ms": 0.5}}})
    spec = {"reader": "trace", "program": "jit_step_n", "stat": "per_substep_ms"}
    assert readers.read_metric(spec, obs) is None  # no kernel seen, no sub-steps: left out
    assert readers.read_metric({**spec, "program": "jit_absent"}, obs) is None
    assert readers.read_metric({"reader": "client", "field": "pre_generate_ms", "stat": "p50"}, obs) is None
