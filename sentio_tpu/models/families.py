"""The decoder families the program serves: one record a family, one table
(importing this module imports no JAX: a router asks it for names).

A family's module exports ONE frozen :class:`Family`, ``FAMILY``: everything
the loader (``runtime/weights.py``), the engine (``runtime/paged.py``), a worker
(``runtime/worker.py``) and the router (``serve/dependencies.py``) ask of it.
They ask HERE, by the name a checkpoint's meta carries or by a configuration's
class, and name no family themselves; a module is imported when it is first
asked for, so a server imports the family it serves and not seven. Adding a
family is its module and one line of ``_MODULES``; ``runtime/`` changes only for
a KIND of state beside the pages that no family has yet (:class:`StateBeside`).
Nothing here or in a family's module imports ``runtime/`` or ``serve/``: what a
decode step needs of the page pool reaches a layer through a :class:`DecodeStep`.
"""

from __future__ import annotations

import importlib
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Optional

# name (a checkpoint meta's ``family``) → the module that exports its ``FAMILY``
_MODULES = {
    "llama": "sentio_tpu.models.llama",
    "moe": "sentio_tpu.models.moe",
    "cohere2_moe": "sentio_tpu.models.cohere2_moe",
    "deepseek_v2": "sentio_tpu.models.deepseek_v2",
    "lfm2_moe": "sentio_tpu.models.lfm2_moe",
    "nemotron_h": "sentio_tpu.models.nemotron_h",
    "jamba": "sentio_tpu.models.jamba",
    "mellum": "sentio_tpu.models.mellum",
}


@dataclass(frozen=True)
class StateBeside:
    """State a family's layers carry BESIDE the pages, whatever the length: a
    decode slot's, and what the prefix cache starts a sequence from. ``per``
    is the kind the engine knows how to keep: ``"page"`` — every page holds
    the state at its end (``models/lfm2_moe.py``: two positions of ``z`` a
    convolution layer) — or ``"snapshot"`` — a bounded pool whose slots the
    radix cache hands to the page boundaries it chooses
    (``models/nemotron_h.py``: a matrix a head, fifty times a page's K and V).
    ``zeros(cfg, rows)``: the state of ``rows`` sequences at position 0, an
    array or a dict of arrays, a layer then a row on the leading axes — the
    pool's shapes. ``page_tokens(cfg)``: a page is whole multiples of this
    many tokens (a snapshot is the scan's state at a chunk boundary)."""

    per: str
    zeros: Callable
    page_tokens: Callable = lambda cfg: 1


@dataclass(frozen=True)
class Family:
    """What the rest of the program asks of a decoder family.

    ``name`` / ``config``: the checkpoint meta's ``family`` and the configuration's
    class (matched by EXACT type: ``MoeConfig`` is a ``LlamaConfig``).
    ``init(rng, cfg)``: the seeded tree. ``forward``: the prefill forward
    (``llama_forward``'s contract), handed ``logits_at`` where the record's
    ``logits_at`` says it takes one. ``init_cache(cfg, rows, length, ...)``: the contiguous
    cache a prefill fills (with state per page: the segment's pages; with
    snapshots: the boundaries a row may leave one at). ``decode_layer(lp, cfg,
    i, x, step)``: layer ``i`` of a decode step on ``x [B, 1, d]`` under a
    :class:`DecodeStep`; ``head(params, cfg, x)``: that step's logits ``[B, V]``
    float32; ``decode_tables(cfg, reach)``: what the step's layers share, made
    once before them (a rotary table over the ``reach`` tokens of a page table).

    What it keeps in the pool, as data: K and V of ``pool_layers(cfg)`` layers,
    or (``latent``) ONE ``cfg.latent_dim`` vector a token and layer; ``state``
    beside them or None. ``picks(cfg)``: the kinds of choice its expert layers
    hand back and how many a token (``{"experts": k}``, ``"groups"`` beside it),
    None where they hand back none; ``expert_tiles``: ``models/moe.py``'s for
    such a family. ``refuses``: of ``"mesh"``, ``"int8"`` (pages) and ``"draft"``
    (a draft model) those it is not served with, each with its reason
    (``{cfg}``: the configuration's class); ``mesh_rules``
    (``parallel/sharding.py``) place the tree of one that takes a mesh."""

    name: str
    config: type
    init: Callable
    forward: Callable
    init_cache: Callable
    decode_layer: Callable
    head: Callable
    decode_tables: Optional[Callable] = None
    logits_at: bool = False
    pool_layers: Callable = lambda cfg: cfg.n_layers
    latent: bool = False
    state: Optional[StateBeside] = None
    picks: Optional[Callable] = None
    expert_tiles: Optional[Callable] = None
    refuses: Mapping[str, str] = field(default_factory=dict)
    mesh_rules: Any = None

    def refusal(self, what: str, cfg) -> Optional[str]:
        """Why ``cfg`` is not served with ``what``, or None where it is."""
        reason = self.refuses.get(what)
        return reason and reason.format(cfg=type(cfg).__name__)


@dataclass
class DecodeStep:
    """What ``runtime/paged.py::paged_decode_forward`` hands every layer of one
    decode step: the rows' facts and the pool's operations, so that a family's
    layer touches no page and knows no kernel.

    ``positions [B, 1]``: where each row's token sits. ``valid [B, 1]`` bool:
    the rows that advance (None: all) — a row that does not is routed nowhere.
    ``tables``: the family's ``decode_tables``. ``attend(q, k, v, layer, window=None,
    scope=None)`` → ``[B, 1, H, D]``: writes the token's keys and values into
    the pool (``layer``: the POOL's) and attends over the pages; for a latent
    pool ``attend(latent [B, latent_dim], queries, layer, sm_scale)`` → ``o_lat``
    (``queries()`` → ``(q_lat, q_pe)``, asked for once the latent is written).
    ``advance(j, step_fn)`` → ``out``: state layer ``j`` of a family with
    :class:`StateBeside`; ``step_fn(held, update)`` → ``(out, after)`` is the
    family's one-token update of the slot's ``held`` state (``update``: the
    kernel the engine bound for it, or None), and the rows that advance keep
    ``after``. ``note(chosen, counts)``: an expert layer's picks ``{kind: [B,
    1, k]}`` and counts, collected for the caller."""

    positions: Any
    valid: Any
    tables: Any
    attend: Callable
    advance: Optional[Callable]
    note: Callable


def names() -> tuple[str, ...]:
    return tuple(_MODULES)


def family(name: str) -> Family:
    """The record of the family a checkpoint's meta names."""
    if name not in _MODULES:
        raise KeyError(f"unknown decoder family {name!r}: the registry has {', '.join(_MODULES)}")
    return importlib.import_module(_MODULES[name]).FAMILY


def family_of(cfg) -> Family:
    """The record of ``cfg``'s family, by its exact class (whose module IS imported)."""
    module = type(cfg).__module__
    found = getattr(sys.modules.get(module), "FAMILY", None)
    if found is None or found.config is not type(cfg) or _MODULES.get(found.name) != module:
        raise TypeError(f"{type(cfg).__name__} is the configuration of no registered decoder family "
                        f"({', '.join(_MODULES)})")
    return found


def rebuild_config(cls: type, fields: Mapping):
    """``dataclasses.asdict`` of a configuration → the configuration, as a
    checkpoint's JSON meta carries it or, beside ``family_of(cfg).name``, a
    worker's spec (``cls``: ``family(name).config``). A list where the field
    is a tuple is turned back (JSON; a frozen config stays hashable) and a
    key the class does not have is left out."""
    known = {f.name: str(f.type).lower() for f in cls.__dataclass_fields__.values()}
    return cls(**{k: tuple(v) if isinstance(v, list) and "tuple" in known[k] else v
                  for k, v in fields.items() if k in known})
