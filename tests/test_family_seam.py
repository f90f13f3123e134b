"""The seam between ``sentio_tpu/models/`` and ``sentio_tpu/runtime/``: a
decoder family is ONE record its module exports (``models/families.py``), and
the loader, the engine, a worker and the router ask the registry.

(a) every registered family round-trips name → record → configuration class →
name, is initialised by ``load_decoder``, and comes back from its ``asdict``
through the registry — the two asks the router's ``make_spec`` and a worker's
``default_service_factory`` make of it; (b) a family DEFINED HERE, outside
``sentio_tpu/``, is served by the engine with no edit under ``runtime/``; (c)
nothing under ``models/`` or ``kernels/`` imports ``runtime/`` or ``serve/``.
Tiny widths on the CPU; nothing is compiled for a described chip.
"""

import ast
import dataclasses
import pathlib
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
import pytest

from sentio_tpu.models import families
from sentio_tpu.models import layers as L
from sentio_tpu.models import llama
from sentio_tpu.models.families import Family, family, family_of, rebuild_config
from sentio_tpu.models.llama import LlamaConfig, init_cache, init_llama, qkv_proj
from sentio_tpu.runtime.paged import ContinuousBatchingEngine
from sentio_tpu.runtime.weights import load_decoder

from conftest import CacheFreeGreedy

PACKAGE = pathlib.Path(families.__file__).resolve().parents[1]

# ------------------------------------------------------ (a) the six, by name


@pytest.mark.parametrize("name", families.names())
def test_a_registered_family_round_trips_and_is_initialised(name):
    record = family(name)
    assert record.name == name and family(name) is record
    cfg = record.config.tiny()
    assert type(cfg) is record.config and family_of(cfg) is record
    # what crosses a process boundary: the name and the configuration's asdict
    assert rebuild_config(family(family_of(cfg).name).config, dataclasses.asdict(cfg)) == cfg
    decoder = load_decoder(model_config=cfg)
    assert decoder.model_config is cfg and "embed_tokens" in decoder.params
    cache = record.init_cache(cfg, 2, 32, *({"page": (2,), "snapshot": (1,)}[record.state.per] if record.state else ()))
    assert cache["k"].shape[1:3] == (2, 32)
    if record.state is not None:   # the shapes of the pool's state: a layer, then a row each
        import jax

        assert {leaf.shape[1] for leaf in jax.tree_util.tree_leaves(record.state.zeros(cfg, 3))} == {3}
    assert (record.picks is None) == (record.expert_tiles is None)
    assert set(record.refuses) <= {"mesh", "int8", "draft"}
    for what in record.refuses:
        assert type(cfg).__name__ in record.refusal(what, cfg)


def test_a_subclass_is_not_its_parents_family_and_an_unknown_name_is_refused():
    from sentio_tpu.models.moe import MoeConfig

    assert family_of(MoeConfig.tiny()).name == "moe" and family_of(LlamaConfig.tiny()).name == "llama"
    with pytest.raises(KeyError, match="unknown decoder family 'mamba3'.*llama, moe"):
        family("mamba3")

    @dataclass(frozen=True)
    class Stray(LlamaConfig):
        pass

    with pytest.raises(TypeError, match="Stray is the configuration of no registered decoder family"):
        family_of(Stray.tiny())


@pytest.mark.parametrize("name", ["cohere2_moe", "nemotron_h"])
def test_a_workers_spec_rebuilds_the_configuration_of_a_newer_family(name):
    """The router sent ``"llama"`` for every family but ``moe`` and the worker
    built ``LlamaConfig(**asdict)``: a ``TypeError`` for these fields."""
    cfg = family(name).config.tiny()
    fields = dataclasses.asdict(cfg)
    with pytest.raises(TypeError):
        LlamaConfig(**fields)
    cls = family(family_of(cfg).name).config   # ``make_spec`` sends the name, the worker asks for the class
    assert rebuild_config(cls, fields) == cfg
    # as JSON carries it: a list where a field is a tuple comes back a tuple, an unknown key is left out
    as_json = {k: list(v) if isinstance(v, tuple) else v for k, v in fields.items()}
    assert rebuild_config(cls, {**as_json, "not_a_field": 1}) == cfg


# ------------------------------------------- (b) a family defined out here


@dataclass(frozen=True)
class ToyConfig(LlamaConfig):
    """A llama whose attention output is damped and whose head is tied."""

    attn_gain: float = 0.5


def init_toy(rng, cfg):
    params = init_llama(rng, cfg)
    del params["lm_head"]
    return params


def toy_head(params, cfg, x):
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["embed_tokens"]["embedding"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def toy_forward(params, cfg, ids, positions=None, cache=None, cache_index=0, pad_mask=None, attn_fn=None):
    b, t = ids.shape
    if cache is not None:
        cache = dict(cache)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    cos, sin = L.rope_frequencies(cfg.head_dim, cache["k"].shape[2] if cache is not None else cfg.max_len,
                                  cfg.rope_theta)
    x = L.embed(params["embed_tokens"], ids, cfg.jdtype)
    for i in range(cfg.n_layers):
        lp = params[f"layers_{i}"]
        out, cache = llama._attn(lp["attn"], cfg, L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps), positions, cos, sin,
                                 i if cache is not None else 0, cache, cache_index, pad_mask, attn_fn)
        x = x + cfg.attn_gain * out
        x = x + llama._mlp(lp["mlp"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps))
    return toy_head(params, cfg, x), cache


def toy_decode_layer(lp, cfg, i, x, step):
    cos, sin = step.tables
    q, k, v = qkv_proj(lp["attn"], cfg, L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps))
    q, k = (L.apply_rope(a, step.positions, cos, sin) for a in (q, k))
    attn = step.attend(q, k, v, i, scope="attn.full")
    x = x + cfg.attn_gain * L.dense(lp["attn"]["wo"], attn.reshape(x.shape[0], 1, -1), cfg.jdtype)
    return x + llama._mlp(lp["mlp"], cfg, L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps))


# what the registry imports from a family's module
FAMILY = Family(name="toy", config=ToyConfig, init=init_toy, forward=toy_forward, init_cache=init_cache,
                decode_layer=toy_decode_layer, head=lambda params, cfg, x: toy_head(params, cfg, x)[:, 0],
                decode_tables=llama.FAMILY.decode_tables)


def test_a_family_defined_outside_the_package_is_served_and_agrees_with_its_own_forward(monkeypatch):
    monkeypatch.setitem(families._MODULES, "toy", __name__)   # one line of the table: what a family costs
    cfg = ToyConfig.tiny()
    assert family_of(cfg) is FAMILY and rebuild_config(family("toy").config, dataclasses.asdict(cfg)) == cfg
    oracle = CacheFreeGreedy(model_config=cfg, width=64)       # ``load_decoder`` inits it: no ``lm_head``
    assert "lm_head" not in oracle.params
    engine = ContinuousBatchingEngine(model_config=cfg, params=oracle.params, max_slots=2, page_size=16,
                                      max_pages_per_seq=4, steps_per_tick=4, prefill_chunk=16)
    assert engine.forward_fn is toy_forward and not engine.routed and not engine.slot_state
    prompts = ["paging is a layout, not a model", "a family is a record"]
    served = engine.run_all(prompts, max_new_tokens=8)
    wanted = oracle.generate(prompts, max_new_tokens=8)
    assert [r.tokens for r in served] == [r.tokens for r in wanted]
    assert [r.finish_reason for r in served] == [r.finish_reason for r in wanted]
    # the gain is part of the function: another value, another answer's logits
    ids = oracle.tokenizer.encode(prompts[0], add_bos=True)
    louder = CacheFreeGreedy(model_config=dataclasses.replace(cfg, attn_gain=1.0), params=oracle.params, width=64)
    assert np.abs(oracle.logits(ids) - louder.logits(ids)).max() > 1e-3


# ------------------------------------------------------- (c) the import graph


def _imports(path: pathlib.Path) -> set:
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.add(node.module)
    return found


@pytest.mark.parametrize("below", ["models", "kernels"])
def test_nothing_under_models_or_kernels_imports_runtime_or_serve(below):
    files = sorted((PACKAGE / below).rglob("*.py"))
    assert files
    for path in files:
        reached = {m for m in _imports(path) if m.startswith(("sentio_tpu.runtime", "sentio_tpu.serve"))}
        assert not reached, f"{path.relative_to(PACKAGE)} imports {sorted(reached)}"


def test_runtime_and_serve_name_no_family():
    """Their imports of a family's module are helpers, never its identity (a
    configuration class, a forward, an init): those are asked of the record."""
    named = {}
    for below in ("runtime", "serve"):
        for path in sorted((PACKAGE / below).rglob("*.py")):
            for node in ast.walk(ast.parse(path.read_text())):
                if isinstance(node, ast.ImportFrom) and node.module in set(families._MODULES.values()) - {
                        "sentio_tpu.models.llama"}:
                    named.setdefault(str(path.relative_to(PACKAGE)), set()).update(a.name for a in node.names)
    assert named == {"runtime/paged.py": {"latent_attention"}}
