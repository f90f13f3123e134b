"""Speculative decoding INSIDE the paged continuous-batching engine.

Decode is bandwidth-bound — every step streams the full target weights for
one token per row. A small draft model proposes ``k`` tokens, then the target
scores all of them in ONE forward of T = k+1, amortizing its weight stream
over up to k+1 emitted tokens. The draft only changes HOW FAST tokens appear,
never the output's law. This module fuses that draft/verify/accept math into
the paged engine's tick protocol, so continuous batching and speculation
compose: every live slot drafts and verifies in the same fused dispatch,
page tables stay the source of truth, and requests still join/leave without
recompilation.

Design (one compiled ``spec_tick`` per (k, out_w) pair):

1. **Densify** — each row's page table gathers into a contiguous
   [L, S, W, Hkv, D] cache (int8 pages dequantize on the way in). Decode
   attention reads the whole past KV anyway, so the extra densification
   traffic is second-order next to the target's weight stream — the thing
   speculation amortizes.
2. **Rounds** — a ``lax.while_loop`` of draft(k)+verify(k+1)+accept rounds
   (greedy rows: longest agree-prefix then the target's own correction
   token, bit-exact vs plain decode; sampled rows: rejection sampling via
   :func:`accept_and_correct`, marginally exact). Both rules are computed and selected PER ROW by temperature, so
   mixed batches serve correctly. Per-row tick budgets bound emissions;
   EOS halts rows (unless ignore_eos).
3. **Scatter back** — the dense cache writes back through the same
   ``scatter_prefill`` every other admission path uses (re-quantization is
   idempotent: absmax scales reproduce exactly), and the tick returns the
   engine's standard device-carried decode state (tok/lens/halted).

Window-limit nuance: a verify block writes KV for up to spec_k+1 positions
past the accepted length, so that headroom is reserved inside each
request's page window. Admission over-allocates pages to cover it, but a
request already at ``max_pages_per_seq`` cannot get extra pages — such
window-limited requests finish (reason "length") up to spec_k+1 tokens
earlier than the plain engine. Greedy bit-parity therefore holds for
requests at least spec_k+1 tokens clear of the window, i.e. everything the
window was sized for.

int8 nuance: within a tick the verify attends the current rounds' KV at
FULL precision (it lives in the dense cache before the tick-end
re-quantization), while the plain int8 engine reads every decode step
through int8. Spec output under ``kv_quant="int8"`` therefore differs from
the plain int8 engine within quantization noise — and is at least as close
to the unquantized model. Greedy bit-parity holds for the unquantized pool.

The host fetches ONE packed buffer per tick — ``[S, out_w + 3]`` rows of
``[echo, emitted_count, verify_count, tokens...]`` — preserving the
engine's one-fetch-per-tick cost model.

Cache discipline: both models write k/v at absolute positions; entries
beyond a row's accepted length are stale but never attended (position-based
causal masks) and are overwritten by later rounds/ticks at the same offsets.
"""

from __future__ import annotations

from sentio_tpu.analysis.audit.registry import jit_family


def refuse_recurrent_state(cfg) -> None:
    """Speculation is refused, with its record's reason (``models/families.py``),
    for a family not served with a draft — above all one with RECURRENT state
    beside the pages: a verify block advances it by k + 1 tokens and nothing
    here keeps the state a rejected token would roll back to (K and V need no
    such thing: a rejected position is simply overwritten)."""
    from sentio_tpu.models.families import family_of

    reason = family_of(cfg).refusal("draft", cfg)
    if reason:
        raise ValueError(reason)


def accept_and_correct(rng, drafts, qdists, tprobs):
    """Rejection-sampling acceptance for sampled speculation.

    drafts [B, k] proposed tokens; qdists [B, k, V] the draft's sampling
    distributions; tprobs [B, k+1, V] the target's distributions at the
    verified positions. Accept d_j with probability min(1, p_t(d_j)/q(d_j))
    while the prefix holds; at the first rejection sample the correction
    from the residual ``norm(relu(p_t - q))``, and after a full accept
    sample the bonus token from the target's (k+1)-th distribution. The
    emitted marginal equals sampling from the target alone — the standard
    speculative-sampling guarantee (tested empirically in
    tests/test_paged_spec.py).

    Returns (n_accept [B], correction [B]).
    """
    import jax
    import jax.numpy as jnp

    b, k = drafts.shape
    rng_u, rng_c = jax.random.split(rng)
    u = jax.random.uniform(rng_u, (b, k))
    p_chosen = jnp.take_along_axis(tprobs[:, :k], drafts[..., None], axis=2)[..., 0]
    q_chosen = jnp.take_along_axis(qdists, drafts[..., None], axis=2)[..., 0]
    ratio = p_chosen / jnp.maximum(q_chosen, 1e-20)
    acc = u < jnp.minimum(ratio, 1.0)
    n_accept = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)

    # correction distribution at position j* = n_accept
    resid = jnp.maximum(tprobs[:, :k] - qdists, 0.0)          # [B, k, V]
    resid_full = jnp.concatenate([resid, tprobs[:, k:]], axis=1)
    sel = jnp.take_along_axis(
        resid_full, n_accept[:, None, None], axis=1
    )[:, 0]                                                    # [B, V]
    norm = sel.sum(-1, keepdims=True)
    tsel = jnp.take_along_axis(tprobs, n_accept[:, None, None], axis=1)[:, 0]
    # identical target/draft distributions → zero residual → target dist
    dist = jnp.where(norm > 1e-9, sel / jnp.maximum(norm, 1e-9), tsel)
    correction = jax.random.categorical(rng_c, jnp.log(dist + 1e-20), axis=-1)
    return n_accept, correction.astype(jnp.int32)


def build_spec_tick(target_fwd, cfg, draft_fwd, dcfg, eos_id: int,
                    ignore_eos: bool, page_size: int):
    """→ jitted ``spec_tick(params_t, params_d, tok, lens, halted,
    page_table, k_pages, v_pages, d_k, d_v, rng, temps, budgets, k=…,
    out_w=…)``; returns the 9-tuple ``(packed, tok', lens', halted',
    k_pages', v_pages', d_k', d_v', rng')`` where ``packed`` is
    ``[S, out_w + 3]``: column 0 echoes the input token, column 1 the
    emitted count, column 2 the verify (round) count, columns 3.. the
    emitted tokens."""
    import jax
    import jax.numpy as jnp

    from sentio_tpu.runtime.paged import dequantize_pages, scatter_prefill

    refuse_recurrent_state(cfg)

    def densify(pages, table, dtype):
        if isinstance(pages, dict):
            dense = dequantize_pages(
                pages["q"][:, table], pages["s"][:, table], dtype
            )
        else:
            dense = pages[:, table]  # [L, S, NB, page, Hkv, Hd]
        lcount, s, nb, pg, hk, hd = dense.shape
        return dense.reshape(lcount, s, nb * pg, hk, hd)

    @jit_family("paged_spec.spec_tick", static_argnames=("k", "out_w"),
                donate_argnums=(6, 7, 8, 9))
    def spec_tick(params_t, params_d, tok, lens, halted, page_table,
                  k_pages, v_pages, d_k, d_v, rng, temps, budgets,
                  k, out_w):
        s_rows = tok.shape[0]
        tcache = {
            "k": densify(k_pages, page_table, cfg.jdtype),
            "v": densify(v_pages, page_table, cfg.jdtype),
        }
        dcache = {"k": d_k, "v": d_v}
        sampled_row = temps > 0.0
        inv_t = (1.0 / jnp.maximum(temps, 1e-6))[:, None]

        out0 = jnp.full((s_rows, out_w), eos_id, jnp.int32)
        emitted0 = jnp.zeros((s_rows,), jnp.int32)
        done0 = halted | (budgets <= 0)

        def round_body(state):
            (cur, lens, emitted, done, halted, tcache, dcache, out, rounds,
             rng_in) = state
            entry_done = done
            live = ~done[:, None]

            # ---- draft k+1 autoregressive steps (the last one only for its
            # k/v write — see speculative.py's draft_step rationale)
            def draft_step(carry, key):
                dtok, dlens, dcache = carry
                logits, dcache = draft_fwd(
                    params_d, dcfg, dtok[:, None], positions=dlens[:, None],
                    cache=dcache, cache_index=dlens, pad_mask=live,
                )
                last = logits[:, -1]
                qdist = jax.nn.softmax(
                    last.astype(jnp.float32) * inv_t, axis=-1
                )
                nxt = jnp.where(
                    sampled_row,
                    jax.random.categorical(key, last * inv_t, axis=-1),
                    jnp.argmax(last, axis=-1),
                ).astype(jnp.int32)
                return (nxt, dlens + 1, dcache), (nxt, qdist)

            rng_in, draft_rng, acc_rng = jax.random.split(rng_in, 3)
            (_, _, dcache), (drafts, qdists) = jax.lax.scan(
                draft_step, (cur, lens, dcache),
                jax.random.split(draft_rng, k + 1),
            )
            drafts = jnp.moveaxis(drafts, 0, 1)[:, :k]   # [S, k]
            qdists = jnp.moveaxis(qdists, 0, 1)[:, :k]   # [S, k, V]

            # ---- one T=k+1 target verify over [cur, d1..dk]
            block = jnp.concatenate([cur[:, None], drafts], axis=1)
            pos = lens[:, None] + jnp.arange(k + 1)[None, :]
            t_logits, tcache = target_fwd(
                params_t, cfg, block, positions=pos, cache=tcache,
                cache_index=lens,
                pad_mask=jnp.broadcast_to(live, (s_rows, k + 1)),
            )

            j = jnp.arange(k + 1)[None, :]
            # greedy rule (bit-exact vs plain decode for temp-0 rows)
            targets = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
            agree = drafts == targets[:, :k]
            n_acc_g = jnp.cumprod(agree.astype(jnp.int32), axis=1).sum(axis=1)
            corr_g = jnp.take_along_axis(targets, n_acc_g[:, None], axis=1)[:, 0]
            # rejection-sampling rule (marginally exact for sampled rows)
            tprobs = jax.nn.softmax(
                t_logits.astype(jnp.float32) * inv_t[..., None], axis=-1
            )
            n_acc_s, corr_s = accept_and_correct(acc_rng, drafts, qdists, tprobs)
            n_accept = jnp.where(sampled_row, n_acc_s, n_acc_g)
            correction = jnp.where(sampled_row, corr_s, corr_g)

            emit_n = n_accept + 1
            round_toks = jnp.where(
                j < n_accept[:, None], jnp.pad(drafts, ((0, 0), (0, 1))),
                jnp.where(j == n_accept[:, None], correction[:, None], eos_id),
            )
            # per-row tick budget FIRST: surplus verified tokens are
            # discarded (re-decoded next tick) — only a tick-boundary
            # effect. EOS is evaluated strictly INSIDE the capped window:
            # an EOS beyond the cap was never emitted, so it must neither
            # halt the row (it would hang forever un-folded) nor truncate.
            emit_n = jnp.minimum(emit_n, budgets - emitted)
            emit_n = jnp.where(done, 0, jnp.maximum(emit_n, 0))
            if not ignore_eos:
                eos_in = (round_toks == eos_id) & (j < emit_n[:, None])
                # positions up to and INCLUDING the first in-window EOS
                thru_eos = jnp.cumsum(jnp.cumsum(eos_in, axis=1), axis=1) <= 1
                emit_n = jnp.minimum(
                    emit_n, (thru_eos & (j < emit_n[:, None])).sum(axis=1)
                )
                halted = halted | (~done & eos_in.any(axis=1))

            def write_row(out_row, toks_row, off, n):
                upd = jax.lax.dynamic_update_slice(out_row, toks_row, (off,))
                keep = jnp.arange(out_row.shape[0])
                return jnp.where((keep >= off) & (keep < off + n), upd, out_row)

            out = jax.vmap(write_row)(out, round_toks, emitted, emit_n)
            new_cur = jnp.take_along_axis(
                round_toks, jnp.maximum(emit_n - 1, 0)[:, None], axis=1
            )[:, 0]
            cur = jnp.where(emit_n > 0, new_cur, cur)
            lens = lens + emit_n
            emitted = emitted + emit_n
            done = done | halted | (emitted >= budgets)
            # per-row verify count (rows live at round entry ran a verify) —
            # emitted/verifies is the tokens-per-verify ratio operators
            # tune the draft against
            rounds = rounds + (~entry_done).astype(jnp.int32)
            return (cur, lens, emitted, done, halted, tcache, dcache, out,
                    rounds, rng_in)

        def cond(state):
            return jnp.any(~state[3])

        rounds0 = jnp.zeros((s_rows,), jnp.int32)
        state = (tok, lens, emitted0, done0, halted, tcache, dcache, out0,
                 rounds0, rng)
        cur, lens, emitted, _, halted, tcache, dcache, out, rounds, rng = \
            jax.lax.while_loop(cond, round_body, state)

        k_pages, v_pages = scatter_prefill(
            k_pages, v_pages, tcache["k"], tcache["v"], page_table
        )
        # ONE host-fetchable buffer per tick: col 0 echoes the input token
        # (freshly admitted rows' deferred first tokens reach the host in
        # the same fetch, like the plain tick's packed row 0), col 1 is the
        # emitted count, col 2 the verify count, cols 3.. the emitted tokens
        packed = jnp.concatenate(
            [tok[:, None], emitted[:, None], rounds[:, None], out], axis=1
        )
        return (packed, cur, lens, halted,
                k_pages, v_pages, dcache["k"], dcache["v"], rng)

    return spec_tick
