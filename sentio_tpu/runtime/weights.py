"""Checkpoint → live model resolution for the serving stack.

The reference has no weights anywhere — its models are remote APIs keyed by
env credentials (settings.py:27-191 there picks providers/urls). Here the
equivalent configuration surface is a *checkpoint path* per model family
(generator, embedder, reranker): ``cli convert`` writes framework
checkpoints (runtime/checkpoint.py format, meta carrying the model family
and config), and this module loads them back into (params, model_config,
tokenizer) triples for the constructors in ops/; :func:`load_decoder` is
the one place the served decoder is resolved, initialised and placed.

Resolution order per model (mirrors the reference's provider-selection
semantics, factory.py:20-27 there, with its mock-mode fallback):

1. ``checkpoint_path`` set → load params + config from the checkpoint;
   tokenizer from ``tokenizer_path`` (a local HF tokenizer dir — usually
   the original HF checkpoint dir) when given.
2. No path → random-init at the preset size (the deterministic fake-model
   mode tests and offline dev run on, SURVEY.md §4).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Optional

from sentio_tpu.infra import tracing
from sentio_tpu.models import families
from sentio_tpu.runtime.checkpoint import CheckpointError, load_pytree

logger = logging.getLogger(__name__)

# the families that are no decoder (those are ``models/families.py``'s)
_ENCODER_FAMILIES = ("encoder", "cross-encoder")


class WeightsError(Exception):
    pass


def load_model(
    checkpoint_path: str,
    expect_family: Optional[str] = None,
    tokenizer_path: str = "",
    mmap: bool = False,
) -> tuple[Any, Any, Optional[Any]]:
    """→ (params, model_config, tokenizer|None) from a ``cli convert`` /
    ``save_pytree`` checkpoint. The meta's recorded config reconstructs the
    exact dataclass the weights were converted for — a preset mismatch
    cannot silently produce shape errors deep in the first forward pass.
    ``mmap=True`` memory-maps the param leaves in place (process-mode
    replica workers share one page-cache copy per host)."""
    # a span of the record it runs for (the ``startup`` record's ``weights``
    # phase when a component's build reads it): with ``mmap`` the leaves are
    # mapped, and their bytes are read where they are placed
    with tracing.span("weights.read", mmap=bool(mmap)) as read:
        try:
            params, meta = load_pytree(checkpoint_path, mmap=mmap)
        except CheckpointError as exc:
            raise WeightsError(f"cannot load checkpoint {checkpoint_path!r}: {exc}") from exc
        read.fields["bytes"] = tree_bytes(params)

    family = meta.get("family")
    if expect_family and family and family != expect_family:
        raise WeightsError(
            f"checkpoint {checkpoint_path!r} holds a {family!r} model, "
            f"expected {expect_family!r}"
        )
    cfg_dict = meta.get("config")
    if not cfg_dict:
        raise WeightsError(f"checkpoint {checkpoint_path!r} has no config in meta")
    lookup = family or expect_family
    if lookup in _ENCODER_FAMILIES:
        from sentio_tpu.models.transformer import EncoderConfig as cfg_cls
    elif lookup in families.names():
        cfg_cls = families.family(lookup).config
    else:
        raise WeightsError(f"unknown model family {lookup!r} in {checkpoint_path!r}")
    model_config = families.rebuild_config(cfg_cls, cfg_dict)

    tokenizer = None
    if tokenizer_path:
        from sentio_tpu.models.tokenizer import HFTokenizer

        tokenizer = HFTokenizer(tokenizer_path)
        if tokenizer.vocab_size > model_config.vocab_size:
            raise WeightsError(
                f"tokenizer at {tokenizer_path!r} has vocab {tokenizer.vocab_size} "
                f"> model vocab {model_config.vocab_size}"
            )
    logger.info(
        "loaded %s checkpoint from %s (dim=%s, layers=%s)",
        lookup, checkpoint_path, getattr(model_config, "dim", "?"),
        getattr(model_config, "n_layers", "?"),
    )
    return params, model_config, tokenizer


@dataclass(frozen=True)
class Decoder:
    """The served decoder as every caller needs it: weights on the device
    (sharded under a mesh), the configuration they were built for, and the
    tokenizer that goes with them."""

    params: Any
    model_config: Any
    tokenizer: Any


def load_decoder(cfg=None, mesh=None, model_config=None, rng_seed: int = 0,
                 mmap: bool = False) -> Decoder:
    """Checkpoint or seeded init → which family → placed on the device.

    With ``cfg.checkpoint_path`` the weights, their configuration (of the
    decoder family the checkpoint's meta names: ``models/families.py`` has
    them) and, with ``cfg.tokenizer_path``, the tokenizer come from the
    checkpoint. Without one the weights are the
    seeded random init of ``model_config``'s family (the deterministic
    fake-model mode of tests and offline development; ``cfg.model_preset``
    picks the configuration when none is given) under a byte tokenizer.
    ``cfg`` is a ``GeneratorConfig``; None means no checkpoint. The tree is
    the SERVING tree (``models/llama.py::serving_layout``: ``attn.wq_t``,
    ``wk_t``, ``wv_t`` stored [out, in] where a checkpoint holds ``wq``,
    ``wk``, ``wv``) and goes
    to its final placement ONCE: by its family's ``mesh_rules`` under a mesh,
    onto the default device without one. ``mmap`` maps the
    checkpoint's leaves in place (worker processes on one host share one
    page-cache copy)."""
    import jax

    from sentio_tpu.models.llama import LlamaConfig, serving_layout
    from sentio_tpu.models.tokenizer import ByteTokenizer
    from sentio_tpu.parallel.sharding import shard_params

    params = tokenizer = None
    if cfg is not None and cfg.checkpoint_path:
        params, model_config, tokenizer = load_model(
            cfg.checkpoint_path, tokenizer_path=cfg.tokenizer_path, mmap=mmap,
        )
        if not isinstance(model_config, LlamaConfig):
            raise WeightsError(
                f"checkpoint {cfg.checkpoint_path!r} holds a "
                f"{type(model_config).__name__} model — the generator "
                f"serves decoder families ({', '.join(families.names())})"
            )
    if model_config is None:
        preset = cfg.model_preset if cfg is not None else "tiny"
        model_config = (LlamaConfig.tiny() if preset == "tiny"
                        else LlamaConfig.llama3_8b())
    family = families.family_of(model_config)
    if mesh is not None and family.refusal("mesh", model_config):
        # (its record says why: none of these has rules under a mesh yet)
        raise WeightsError(f"a {family.name} model is served on one device a process")
    if params is None:
        params = family.init(jax.random.PRNGKey(rng_seed), model_config)
    # q, k and v in the order the serving programs read them, made before
    # placement: a checkpoint's leaves are turned on the host and no second
    # copy of a weight ever reaches the device
    with tracing.span("weights.place") as place:
        params = shard_params(serving_layout(params), mesh, family.mesh_rules)
        # a placement returns before its bytes have moved: the span waits
        # for them, so that the seconds are the placement's
        jax.block_until_ready(params)
        place.fields["bytes"] = tree_bytes(params)
    return Decoder(
        params=params, model_config=model_config,
        tokenizer=tokenizer or ByteTokenizer(model_config.vocab_size),
    )


def tree_bytes(params: Any) -> int:
    """The bytes a tree of arrays holds (host or device)."""
    import jax

    return int(sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree_util.tree_leaves(params)))


def device_stats(mesh, model_config) -> dict:
    """Health-endpoint payload: device kind, count, mesh shape, the served
    model's size and, where the backend reports it, device memory."""
    import jax

    devices = jax.devices()
    stats = {
        "platform": devices[0].platform if devices else "none",
        "kind": devices[0].device_kind if devices else "none",
        "n_devices": len(devices),
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "model": {
            "layers": model_config.n_layers,
            "dim": model_config.dim,
            "vocab": model_config.vocab_size,
        },
    }
    try:  # HBM headroom where the backend exposes it
        m = devices[0].memory_stats()
        if m:
            stats["memory"] = {
                "bytes_in_use": m.get("bytes_in_use"),
                "peak_bytes_in_use": m.get("peak_bytes_in_use"),
                "bytes_limit": m.get("bytes_limit"),
            }
    except Exception:  # noqa: BLE001 — device stats are best-effort diagnostics
        pass
    return stats
