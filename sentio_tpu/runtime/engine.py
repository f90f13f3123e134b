"""GeneratorEngine: the in-process LLM serving runtime.

This is what replaces the reference's four HTTP process boundaries
(SURVEY.md §3.1): the model lives on the mesh, loaded ONCE at startup
(inverting the reference's lazy first-request graph init, chat.py:38-87
there), and requests become device dispatches:

* **prefill** — bucketed prompt lengths ([B, bucket] padded), one compiled
  program per (batch, bucket) pair, aligned cache write at slot 0;
* **decode** — single fused step: forward(T=1) → sample → append, with
  per-row positions/cache offsets (ragged batches from the coalescer);
* **stream** — the host loop yields tokens as they land, feeding SSE.

Two loops are provided: a host-stepped loop (streaming, early EOS exit) and
a fully-jitted ``lax.while_loop`` bulk loop (no host round-trips — the bench
path). Weights are TP-sharded via parallel/sharding rules when a mesh is
given; the KV cache shards batch-on-dp / heads-on-tp from the same mesh.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Iterator, Optional, Sequence

import numpy as np

from sentio_tpu.analysis.audit.registry import jit_family
from sentio_tpu.config import GeneratorConfig, get_settings
from sentio_tpu.models.llama import LlamaConfig
from sentio_tpu.parallel.batcher import bucket_size

logger = logging.getLogger(__name__)


@dataclass
class GenerationResult:
    text: str
    tokens: list[int]
    prompt_tokens: int
    finish_reason: str  # "stop" | "length"
    latency_ms: float = 0.0


class GeneratorEngine:
    PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)
    BATCH_BUCKETS = (1, 2, 4, 8, 16)

    def __init__(
        self,
        config: Optional[GeneratorConfig] = None,
        model_config: Optional[LlamaConfig] = None,
        params=None,
        tokenizer=None,
        mesh=None,
        rng_seed: int = 0,
        forward_fn=None,
        sharding_rules=None,
    ) -> None:
        """``forward_fn`` swaps the model family behind the serving seams:
        any fn with ``llama_forward``'s (params, cfg, ids, positions, cache,
        cache_index, pad_mask, attn_fn) → (logits, cache) contract — e.g.
        ``models.moe.moe_serving_forward`` for expert-routed checkpoints
        (pair it with ``sharding_rules=MOE_EP_RULES`` under a mesh)."""
        import jax

        from sentio_tpu.models.llama import init_llama
        from sentio_tpu.models.tokenizer import ByteTokenizer

        self.config = config or get_settings().generator
        explicit_params = params
        from_checkpoint = False
        if params is None and self.config.checkpoint_path:
            # real weights: a `cli convert` checkpoint + HF tokenizer; the
            # family rides the checkpoint meta (llama or moe) and the
            # matching forward_fn is auto-selected from the restored config
            from sentio_tpu.runtime.weights import WeightsError, load_model

            params, model_config, ck_tok = load_model(
                self.config.checkpoint_path,
                tokenizer_path=self.config.tokenizer_path,
            )
            if not isinstance(model_config, LlamaConfig):
                raise WeightsError(
                    f"checkpoint {self.config.checkpoint_path!r} holds a "
                    f"{type(model_config).__name__} model — the generator "
                    "engine serves decoder families (llama, moe)"
                )
            tokenizer = tokenizer or ck_tok
            from_checkpoint = True
        self.model_config = model_config or (
            LlamaConfig.tiny() if self.config.model_preset == "tiny" else LlamaConfig.llama3_8b()
        )
        self.tokenizer = tokenizer or ByteTokenizer(self.model_config.vocab_size)
        self.mesh = mesh
        from sentio_tpu.models.llama import llama_forward
        from sentio_tpu.models.moe import MoeConfig, moe_serving_forward

        is_moe = isinstance(self.model_config, MoeConfig)
        if params is None:
            # random-init at the config's family (the fake-model test mode)
            if is_moe:
                from sentio_tpu.models.moe import init_moe

                params = init_moe(jax.random.PRNGKey(rng_seed), self.model_config)
            else:
                params = init_llama(jax.random.PRNGKey(rng_seed), self.model_config)
        from sentio_tpu.parallel.sharding import (
            LLAMA_TP_RULES,
            MOE_EP_RULES,
            shard_params,
        )

        default_rules = MOE_EP_RULES if is_moe else LLAMA_TP_RULES
        rules = sharding_rules if sharding_rules is not None else default_rules
        self.params = shard_params(params, mesh, rules)
        if forward_fn is None:
            forward_fn = moe_serving_forward if is_moe else llama_forward
        elif forward_fn in (moe_serving_forward, llama_forward):
            # the two in-tree families are cheap to cross-check
            if (forward_fn is moe_serving_forward) != is_moe:
                raise ValueError(
                    f"forward_fn {forward_fn.__name__} does not match the "
                    f"{type(self.model_config).__name__} model family"
                )
        elif explicit_params is None and not from_checkpoint:
            # a custom family's fn against default-initialized params would
            # KeyError deep inside jit
            raise ValueError(
                "forward_fn overrides the model family; pass matching params "
                "explicitly (the default init builds the config family's tree)"
            )
        self.forward_fn = forward_fn
        self._rng = jax.random.PRNGKey(rng_seed + 17)
        self._build_fns()

    # ------------------------------------------------------------- compiled fns

    def _build_fns(self) -> None:
        import jax
        import jax.numpy as jnp

        from sentio_tpu.runtime.sampling import sample_tokens

        llama_forward = self.forward_fn  # model-family seam (see __init__)

        cfg = self.model_config
        # Pallas flash attention for the prefill pass (the multi-token causal
        # block); decode (T=1) keeps the fused XLA path. Under a mesh the
        # kernel runs INSIDE shard_map: heads on tp (matching the wq/wk/wv
        # column sharding), ring attention over sp for sequence-parallel
        # long-context prefill.
        from sentio_tpu.kernels import default_attn_fn, make_mesh_attn_fn
        from sentio_tpu.parallel.mesh import AXIS_TP

        if self.mesh is None:
            attn_fn = default_attn_fn()
        elif jax.default_backend() != "tpu":
            attn_fn = None  # CPU test meshes: XLA attention under GSPMD
        elif cfg.n_heads % self.mesh.shape[AXIS_TP] != 0:
            # the one shape the sharded kernel cannot take, tested HERE and
            # not by catching its error per call: nothing downstream may
            # swallow a kernel failure and carry on with XLA attention
            logger.warning(
                "prefill attention: XLA under GSPMD (n_heads=%d does not "
                "divide over tp=%d)", cfg.n_heads, self.mesh.shape[AXIS_TP],
            )
            attn_fn = None
        else:
            attn_fn = make_mesh_attn_fn(self.mesh)

        self._attn_fn = attn_fn  # exposed for the speculative decoder

        @jit_family("engine.prefill")
        def prefill(params, ids, positions, cache, pad_mask):
            # pad_mask marks real (row, token) cells: llama ignores it on the
            # cache path, routed families (MoE) need it so padding claims no
            # expert capacity
            logits, cache = llama_forward(
                params, cfg, ids, positions=positions, cache=cache, cache_index=0,
                pad_mask=pad_mask, attn_fn=attn_fn,
            )
            return logits, cache

        @jit_family("engine.decode_step")
        def decode_step(params, tok, lens, cache, rng, temperature, top_k):
            # tok [B,1]; lens [B] = current absolute position per row.
            # top_k rides TRACED (int32 scalar): per-request values share one
            # compiled program — the old static_argnames form recompiled the
            # whole decode step per distinct k (analysis/baseline.json entry,
            # now fixed)
            logits, cache = llama_forward(
                params, cfg, tok, positions=lens[:, None], cache=cache, cache_index=lens
            )
            rng, sub = jax.random.split(rng)
            nxt, _lp = sample_tokens(logits[:, -1], sub, temperature, top_k=top_k)
            return nxt, cache, rng

        @jit_family("engine.generate_fused",
                    static_argnames=("steps", "eos_id"))
        def generate_fused(params, ids, positions, lens, cache, rng, temperature,
                           steps, top_k, eos_id, pad_mask):
            """Prefill + first-token sample + the whole decode scan as ONE
            compiled program. The bulk path dispatches this once and fetches
            one output — every extra blocking host<->device round trip is
            pure latency at serving batch sizes.
            ``steps`` comes from ``_stable_steps`` (STEP_BUCKETS only) and
            ``top_k`` is traced, so the variant space stays the bounded set
            the compile manifest commits to."""
            logits, cache = llama_forward(
                params, cfg, ids, positions=positions, cache=cache, cache_index=0,
                pad_mask=pad_mask, attn_fn=attn_fn,
            )
            row_valid = pad_mask.any(axis=1, keepdims=True)  # junk bucket rows
            last = jnp.take_along_axis(logits, (lens - 1)[:, None, None], axis=1)[:, 0]
            rng, sub = jax.random.split(rng)
            first, _first_lp = sample_tokens(last, sub, temperature, top_k=top_k)

            def body(carry, _):
                tok, lens, cache, rng, done = carry
                # done rows leave routing too — a finished row must not keep
                # claiming expert capacity from live rows
                logits, cache = llama_forward(
                    params, cfg, tok[:, None], positions=lens[:, None],
                    cache=cache, cache_index=lens,
                    pad_mask=row_valid & ~done[:, None],
                )
                rng, sub = jax.random.split(rng)
                nxt, _lp = sample_tokens(logits[:, -1], sub, temperature, top_k=top_k)
                nxt = jnp.where(done, eos_id, nxt)
                done = done | (nxt == eos_id)
                return (nxt, lens + 1, cache, rng, done), nxt

            if steps > 1:
                init = (first, lens, cache, rng, first == eos_id)
                _, rest = jax.lax.scan(body, init, None, length=steps - 1)
                toks = jnp.concatenate([first[:, None], jnp.moveaxis(rest, 0, 1)], axis=1)
            else:
                toks = first[:, None]
            return toks

        self._prefill = prefill
        self._decode_step = decode_step
        self._generate_fused = generate_fused

    # --------------------------------------------------------------- helpers

    def _encode_batch(self, prompts: Sequence[str], max_new: int):
        import jax.numpy as jnp

        from sentio_tpu.models.llama import init_cache
        from sentio_tpu.models.tokenizer import batch_encode

        # prompts always leave >= 8 decode slots in the window, even at the
        # model's max_len — a prompt that fills the cache exactly would have
        # its first generated token clamped onto the last prompt slot
        max_prompt = min(self.config.max_prompt_tokens, self.model_config.max_len - 8)
        ids, mask = batch_encode(self.tokenizer, prompts, max_len=max_prompt, add_bos=True)
        lens = mask.sum(axis=1).astype(np.int32)
        n = len(prompts)
        rows = bucket_size(n, self.BATCH_BUCKETS)
        width = bucket_size(ids.shape[1], self.PREFILL_BUCKETS)
        ids = np.pad(ids, ((0, rows - n), (0, width - ids.shape[1])),
                     constant_values=self.tokenizer.pad_id)
        lens = np.pad(lens, (0, rows - n), constant_values=1)
        # real (row, token) cells: padding tails AND junk bucket rows are
        # False — llama ignores this on the cache path, routed families use
        # it to keep padding out of expert capacity
        pad_mask = (np.arange(width)[None, :] < lens[:, None]) & (
            np.arange(rows) < n
        )[:, None]

        window = min(
            self.model_config.max_len,
            bucket_size(width + max_new, self.PREFILL_BUCKETS + (self.model_config.max_len,)),
        )
        cache = init_cache(self.model_config, rows, window)
        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            from sentio_tpu.parallel.mesh import AXIS_DP, AXIS_TP

            spec = NamedSharding(self.mesh, P(None, AXIS_DP, None, AXIS_TP, None))
            cache = {k: jax.device_put(v, spec) for k, v in cache.items()}
        positions = np.broadcast_to(np.arange(width, dtype=np.int32)[None, :], ids.shape)
        # ids/positions/lens stay HOST numpy: host math on them (lens.max(),
        # per-row slicing) must not trigger device round trips; they ride to
        # the device as jit-call args (async, no blocking device_put)
        return ids, positions.copy(), lens, cache, n, window, pad_mask

    STEP_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)

    def _stable_steps(self, requested: int, headroom: int) -> int:
        """Static scan lengths must come from the committed STEP_BUCKETS set
        or every distinct value recompiles the whole fused decode loop (the
        compile manifest pins this family's variant space). Requested counts
        round UP to a bucket — the scan over-runs by at most a bucket gap
        and ``generate`` truncates host-side — while a cache-headroom clamp
        rounds DOWN (finish_reason becomes 'length')."""
        from sentio_tpu.parallel.batcher import floor_bucket

        # _encode_batch truncates prompts to leave >= 8 slots, so headroom >= 8
        # always holds in practice; the assert guards the invariant
        assert headroom >= 1, f"no KV headroom ({headroom}); prompt truncation failed"
        # min() with the top bucket: bucket_size returns n ITSELF past the
        # last bucket, which would reopen the one-program-per-value hole
        # for requests above max(STEP_BUCKETS) — those clamp (length-finish
        # at the top bucket) instead of compiling off-manifest
        steps = min(bucket_size(max(requested, 1), self.STEP_BUCKETS),
                    max(self.STEP_BUCKETS))
        if steps > headroom:
            steps = floor_bucket(headroom, self.STEP_BUCKETS)
        return max(min(steps, headroom), 1)

    def compile_variant_space(self) -> dict[str, list[dict]]:
        """The DECLARED compile-variant space per jit family — every
        (shape-static) combination the serving paths above can request,
        derived from the same constants/helpers they use. ``sentio audit``
        abstractly lowers each descriptor and diffs the result against the
        committed compile manifest; widening any bucket set here (or in the
        helpers) is a deliberate, manifest-visible act."""
        cfg = self.model_config
        max_prompt = min(self.config.max_prompt_tokens, cfg.max_len - 8)
        # achievable prefill widths: bucket_size over 1..max_prompt
        top_w = bucket_size(max_prompt, self.PREFILL_BUCKETS)
        widths = sorted(
            {b for b in self.PREFILL_BUCKETS if b <= top_w} | {top_w}
        )
        # achievable cache windows per width (_encode_batch): the bucket set
        # extended by max_len, values above width, capped at max_len
        ext = sorted(set(self.PREFILL_BUCKETS) | {cfg.max_len})

        def windows(width: int) -> list[int]:
            return sorted({min(cfg.max_len, b) for b in ext if b > width})

        rows = list(self.BATCH_BUCKETS)
        # achievable fused-scan lengths (_stable_steps: STEP_BUCKETS only,
        # down-clamped by headroom < max_len)
        steps = [b for b in self.STEP_BUCKETS if b <= cfg.max_len - 1]
        space: dict[str, list[dict]] = {
            "engine.prefill": [
                {"rows": r, "width": w, "window": win}
                for w in widths for win in windows(w) for r in rows
            ],
            "engine.decode_step": [
                {"rows": r, "window": win}
                for win in sorted({win for w in widths for win in windows(w)})
                for r in rows
            ],
            "engine.generate_fused": [
                {"rows": r, "width": w, "window": win, "steps": s}
                for w in widths for win in windows(w) for r in rows
                for s in steps if s < win
            ],
        }
        return space

    # ----------------------------------------------------------------- public

    def generate(
        self,
        prompts: Sequence[str],
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        top_k: int = 0,
    ) -> list[GenerationResult]:
        """Batched bulk generation through the on-device scan loop. Batches
        larger than the biggest batch bucket are chunked transparently."""
        import jax
        import jax.numpy as jnp

        from sentio_tpu.infra import faults

        faults.hit("engine.generate")

        max_batch = max(self.BATCH_BUCKETS)
        if len(prompts) > max_batch:
            out: list[GenerationResult] = []
            for start in range(0, len(prompts), max_batch):
                out.extend(
                    self.generate(
                        prompts[start : start + max_batch],
                        max_new_tokens=max_new_tokens,
                        temperature=temperature,
                        top_k=top_k,
                    )
                )
            return out

        t0 = time.perf_counter()
        requested = max_new_tokens or self.config.max_new_tokens
        temp = self.config.temperature() if temperature is None else temperature
        ids, positions, lens, cache, n, window, pad_mask = self._encode_batch(prompts, requested)
        max_new = self._stable_steps(requested, window - int(lens.max()))

        # one dispatch, one fetch: prefill + sampling + decode scan fused
        self._rng, sub = jax.random.split(self._rng)
        toks = np.asarray(self._generate_fused(
            self.params, ids, positions, lens, cache, sub,
            jnp.asarray(temp, jnp.float32), max_new, np.int32(top_k),
            self.tokenizer.eos_id, pad_mask,
        ))
        dt_ms = (time.perf_counter() - t0) * 1000.0

        out = []
        for i in range(n):
            # steps round UP to a bucket; the over-run tail past the caller's
            # budget is dropped here (EOS inside it must not flip the reason)
            row = toks[i, :requested].tolist()
            if self.tokenizer.eos_id in row:
                cut = row.index(self.tokenizer.eos_id)
                row, reason = row[:cut], "stop"
            else:
                reason = "length"
            out.append(
                GenerationResult(
                    text=self.tokenizer.decode(row),
                    tokens=row,
                    prompt_tokens=int(lens[i]),
                    finish_reason=reason,
                    latency_ms=dt_ms,
                )
            )
        return out

    def stream(
        self,
        prompt: str,
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        top_k: int = 0,
    ) -> Iterator[str]:
        """Host-stepped decode yielding decoded text increments (SSE feed).
        UTF-8 safe: bytes are buffered until they decode cleanly."""
        import jax
        import jax.numpy as jnp

        max_new = max_new_tokens or self.config.max_new_tokens
        temp = self.config.temperature() if temperature is None else temperature
        ids, positions, lens, cache, _, window, pad_mask = self._encode_batch([prompt], max_new)
        # the stream loop is host-driven (no static scan length), so the
        # caller's budget applies exactly — only the cache window clamps it
        max_new = max(min(max_new, window - int(lens.max())), 1)

        logits, cache = self._prefill(self.params, ids, positions, cache, pad_mask)
        last = jnp.take_along_axis(logits, (lens - 1)[:, None, None], axis=1)[:, 0]
        from sentio_tpu.runtime.sampling import sample_tokens

        self._rng, sub = jax.random.split(self._rng)
        tok, _lp = sample_tokens(last, sub, temp, top_k=top_k)
        lens = jnp.asarray(lens)
        emitted: list[int] = []
        flushed = ""
        for _ in range(max_new):
            t = int(tok[0])
            if t == self.tokenizer.eos_id:
                break
            emitted.append(t)
            text = self.tokenizer.decode(emitted)
            # withhold at most the final char: a trailing '�' may be an
            # incomplete UTF-8 sequence the next token resolves
            safe = text[:-1] if text.endswith("�") else text
            if len(safe) > len(flushed):
                yield safe[len(flushed):]
                flushed = safe
            tok, cache, self._rng = self._decode_step(
                self.params, tok[:, None], lens, cache, self._rng,
                jnp.asarray(temp, jnp.float32), np.int32(top_k),
            )
            lens = lens + 1
        final = self.tokenizer.decode(emitted)
        if len(final) > len(flushed):
            yield final[len(flushed):]

    def device_stats(self) -> dict:
        """Health-endpoint payload: device kind, count, mesh shape."""
        import jax

        devices = jax.devices()
        stats = {
            "platform": devices[0].platform if devices else "none",
            "kind": devices[0].device_kind if devices else "none",
            "n_devices": len(devices),
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "model": {
                "layers": self.model_config.n_layers,
                "dim": self.model_config.dim,
                "vocab": self.model_config.vocab_size,
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
