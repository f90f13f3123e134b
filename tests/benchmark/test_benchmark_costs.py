"""Configuration files against the program's config class, each through its
OWN family, and the byte and operation counts against numbers worked by hand."""

import dataclasses

import pytest
from bench_tree import load_dir

from benchmark.families import llama as family
from benchmark.families import load_family
from benchmark.roofline import device_peaks, least_time_s

CONFIGS = load_dir("configs")
DENSE = ("mistral-7b-v0.3-l16", "yi-1.5-6b-l16")


def held_to_its_family(model: dict) -> None:
    """Whatever family the file names: ``program_config`` builds the object
    ``check_config`` returns (at the file's depth and positions, every field
    equal), and each published key the family declares (``WIDTHS``) reads in
    that object what it reads in the file. No key is named here: a family
    with heads wider than ``hidden / heads`` or with no ``rms_norm_eps``
    states its own."""
    own = load_family(model)
    for as_run in (model, {**model, **model["rehearsal"]}):
        cfg = own.check_config(as_run, as_run["num_hidden_layers"], as_run["max_position_embeddings"])
        assert dataclasses.is_dataclass(cfg) and type(cfg)(**own.program_config(as_run)) == cfg
        assert dataclasses.asdict(cfg) == own.program_config(as_run)  # what /info reports, field for field
        assert own.WIDTHS and set(own.WIDTHS) <= set(as_run)
        for key, field in own.WIDTHS.items():
            assert getattr(cfg, field) == as_run[key], (key, field)
    # what was cut is held too: the value that runs is the file's, not the published one
    assert set(model["reduced"]) <= set(own.WIDTHS)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_file_widths_are_what_the_program_config_reports(name):
    held_to_its_family(CONFIGS[name])


def test_a_family_with_wider_heads_and_no_rms_eps_is_held_to_its_own_keys(monkeypatch):
    """A family as a stand-in module, a file as a dict: 128 heads of 128 at
    hidden 4,096 (attention four times as wide as the model), a LayerNorm's
    ``layer_norm_eps`` and no ``rms_norm_eps``. At the parent this file read
    ``assert 32 == 128`` whatever family it named; now it is held to the keys
    ITS family declares, and still refused where it departs from them."""
    import sys
    import types

    @dataclasses.dataclass(frozen=True)
    class WideConfig:
        dim: int
        n_heads: int
        head_dim: int
        n_layers: int
        max_len: int
        eps: float

    fields = {"hidden_size": "dim", "num_attention_heads": "n_heads", "head_dim": "head_dim",
              "num_hidden_layers": "n_layers", "max_position_embeddings": "max_len", "layer_norm_eps": "eps"}
    program_config = lambda model: {field: model[key] for key, field in fields.items()}  # noqa: E731
    wide = types.ModuleType("benchmark.families.scratch_wide")
    wide.WIDTHS, wide.program_config = fields, program_config
    wide.check_config = lambda model, layers, max_len: WideConfig(
        **{**program_config(model), "n_layers": layers, "max_len": max_len})
    monkeypatch.setitem(sys.modules, wide.__name__, wide)
    model = {"family": "scratch_wide", "hidden_size": 4096, "num_attention_heads": 128, "head_dim": 128,
             "num_hidden_layers": 4, "max_position_embeddings": 8192, "layer_norm_eps": 1e-5,
             "reduced": ["num_hidden_layers"], "rehearsal": {"hidden_size": 64, "num_attention_heads": 8, "head_dim": 32}}
    assert model["head_dim"] != model["hidden_size"] // model["num_attention_heads"] and "rms_norm_eps" not in model
    held_to_its_family(model)
    # the family's own assumption broken: it would run heads of 64 where the file publishes 128
    wide.program_config = lambda model: {**program_config(model), "head_dim": 64}
    with pytest.raises(AssertionError):
        held_to_its_family(model)
    wide.program_config = program_config
    with pytest.raises(AssertionError):  # a cut the family does not hold the program to
        held_to_its_family({**model, "reduced": ["vocab_size"]})


@pytest.mark.parametrize("name", DENSE)
def test_the_dense_configurations_are_held_to_what_they_were(name):
    """By name, every assertion these two files were held to before a file
    was asked for its family: the ten fields, heads of 128, bf16, and a
    rehearsal whose heads divide."""
    from sentio_tpu.models.llama import LlamaConfig

    model = CONFIGS[name]
    assert model["family"] == "llama" and load_family(model) is family
    cfg = LlamaConfig(**family.program_config(model))
    assert cfg.dim == model["hidden_size"] and cfg.mlp_dim == model["intermediate_size"]
    assert cfg.n_heads == model["num_attention_heads"] and cfg.n_kv_heads == model["num_key_value_heads"]
    assert cfg.head_dim == model["head_dim"] == 128
    assert cfg.vocab_size == model["vocab_size"] and cfg.n_layers == model["num_hidden_layers"]
    assert cfg.rope_theta == model["rope_theta"] and cfg.norm_eps == model["rms_norm_eps"]
    assert cfg.max_len == model["max_position_embeddings"] and cfg.dtype == "bfloat16"
    tiny = LlamaConfig(**family.program_config({**model, **model["rehearsal"]}))
    assert tiny.dim % tiny.n_heads == 0 and tiny.n_heads % tiny.n_kv_heads == 0


def test_mistral_bytes_and_flops_by_hand():
    m = CONFIGS["mistral-7b-v0.3-l16"]
    w = family.weight_bytes(m)
    # wq 4096x4096, wk and wv 4096x1024, wo 4096x4096, three MLP matrices 4096x14336
    params = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert params == 218_103_808 and w["layer"] == 2 * params
    assert w["layers"] == 16 * 2 * params == 6_979_321_856
    assert w["head"] == w["embed"] == 32768 * 4096 * 2 == 268_435_456
    # K and V, 8 heads of 128, bf16, 16 layers
    assert family.kv_bytes_per_token(m) == 2 * 8 * 128 * 2 * 16 == 65_536
    cost = family.decode_substep_cost(m, rows=16, context_tokens=8 * 1200)
    assert cost["bytes"] == 6_979_321_856 + 268_435_456 + 16 * 4096 * 2 + 9600 * 65_536
    assert cost["flops"] == 2 * 16 * (16 * params + 32768 * 4096) + 4 * 9600 * 4096 * 16
    bound = least_time_s(cost, "TPU v5 lite")
    assert bound["bound"] == "bandwidth"
    assert bound["seconds"] == pytest.approx(cost["bytes"] / 819e9) and 0.0095 < bound["seconds"] < 0.0097


def test_pool_and_one_kernel_call_by_hand():
    m, y = CONFIGS["mistral-7b-v0.3-l16"], CONFIGS["yi-1.5-6b-l16"]
    # the scratch page and 16 slots x 18 pages of 128 tokens, 64 KB a token
    assert family.pool_bytes(m, {**m["serve_env"]}) == (1 + 16 * 18) * 128 * 65_536 == 2_424_307_712
    assert family.pool_bytes(y, {**y["serve_env"]}) == (1 + 32 * 10) * 128 * 32_768
    # one layer's K and V of the tokens the rows hold, and nothing of the table
    cost = family.KERNEL_COSTS["paged_attention"](m, rows=16, context_tokens=4 * 1148)
    assert cost == {"bytes": 4592 * 2 * 8 * 128 * 2, "flops": 4 * 4592 * 4096}
    assert cost["bytes"] * m["num_hidden_layers"] == 4592 * family.kv_bytes_per_token(m)
    bound = least_time_s(cost, "TPU v5 lite")
    assert bound["bound"] == "bandwidth" and bound["seconds"] == pytest.approx(22.97e-6, rel=1e-3)
    # 16 calls a sub-step hold exactly the context bytes the whole step counts
    step = family.decode_substep_cost(m, 16, 4592)["bytes"] - family.decode_substep_cost(m, 16, 0)["bytes"]
    assert step == 16 * cost["bytes"]
    assert family.KERNEL_COSTS["paged_attention"](y, 32, 11 * 830)["bytes"] == 9130 * 2 * 4 * 128 * 2


def test_yi_bytes_and_flops_by_hand():
    m = CONFIGS["yi-1.5-6b-l16"]
    params = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert family.weight_bytes(m)["layer"] == 2 * params == 346_030_080
    assert family.weight_bytes(m)["head"] == 64000 * 4096 * 2
    assert family.kv_bytes_per_token(m) == 2 * 4 * 128 * 2 * 16 == 32_768
    # 32 slots of which 11 hold 830 tokens each: the head is the larger part here
    cost = family.decode_substep_cost(m, rows=32, context_tokens=11 * 830)
    assert cost["bytes"] == 16 * 346_030_080 + 524_288_000 + 32 * 4096 * 2 + 9130 * 32_768
    assert cost["flops"] == 2 * 32 * (16 * params + 64000 * 4096) + 4 * 9130 * 4096 * 16
    bound = least_time_s(cost, "TPU v5 lite")
    assert bound["bound"] == "bandwidth" and 0.0077 < bound["seconds"] < 0.0078


def test_an_unknown_device_has_no_peaks():
    assert device_peaks("TPU v5 lite") == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        device_peaks("cpu")
