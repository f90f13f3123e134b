"""Tick-phase time attribution (infra/phases.py + the pump/engine wiring).

The tier-1 conservation gate (ISSUE 12 acceptance): for a sanitized
multi-request run, every tick's ``sum(phase_ms)`` equals its ``pump_ms``
within tolerance, duty-cycle fractions sum to 1±0.01, and the ``phase_ms``
key set is exactly the fixed bounded ``TICK_PHASES`` — the metrics
cardinality guard drops anything else."""

import threading
import time

import pytest

from sentio_tpu.infra.flight import FlightRecorder, set_flight_recorder
from sentio_tpu.infra.metrics import MetricsCollector, set_metrics
from sentio_tpu.infra.phases import (
    DUTY_STATES,
    HOST_PHASES,
    KV_PAGE_KINDS,
    REQUEST_STAGES,
    ROW_STEP_KINDS,
    TICK_PHASES,
    TTFT_STAGES,
    PhaseTimer,
    duty_fractions,
    tile_ttft,
)
from sentio_tpu.runtime.paged import ContinuousBatchingEngine
from sentio_tpu.runtime.service import PagedGenerationService


@pytest.fixture()
def recorder():
    rec = FlightRecorder()
    set_flight_recorder(rec)
    yield rec
    set_flight_recorder(None)


@pytest.fixture()
def metrics():
    m = MetricsCollector()
    set_metrics(m)
    yield m
    set_metrics(None)


def _engine(**kw):
    defaults = dict(max_slots=4, page_size=16, max_pages_per_seq=4,
                    steps_per_tick=4, max_tick_steps=8, pipeline_depth=2)
    defaults.update(kw)
    return ContinuousBatchingEngine(**defaults)


class TestPhaseTimer:
    def test_add_and_context(self):
        t = PhaseTimer()
        t.add("deliver", 0.25)
        with t.phase("inbox_drain"):
            pass
        assert t.acc["deliver"] == 0.25
        assert t.acc["inbox_drain"] >= 0.0
        assert t.total() >= 0.25

    def test_unknown_key_rejected(self):
        """A typo'd phase must fail at the writer — the bounded-set
        guarantee is enforced where the key is minted."""
        t = PhaseTimer()
        with pytest.raises(KeyError):
            t.add("not_a_phase", 1.0)
        with pytest.raises(KeyError):
            t.phase("not_a_phase")

    def test_snapshot_and_reset(self):
        t = PhaseTimer()
        t.add("other", 0.002)
        snap = t.snapshot_ms()
        assert set(snap) == set(TICK_PHASES)
        assert snap["other"] == 2.0
        t.reset()
        assert t.total() == 0.0


class TestDutyFractions:
    def test_sums_to_one(self):
        out = duty_fractions(
            {"inbox_drain": 0.1, "device_wait": 0.3, "deliver": 0.1}, 1.0)
        assert set(out) == set(DUTY_STATES)
        assert sum(out.values()) == pytest.approx(1.0, abs=1e-6)
        assert out["host"] == pytest.approx(0.2, abs=1e-6)
        assert out["device"] == pytest.approx(0.3, abs=1e-6)

    def test_skew_clamped_and_renormalized(self):
        # busy marginally exceeding elapsed (clock skew): idle clamps at 0
        # and the fractions still sum to 1
        out = duty_fractions({"other": 0.8, "device_wait": 0.4}, 1.0)
        assert out["idle"] == 0.0
        assert sum(out.values()) == pytest.approx(1.0, abs=1e-6)

    def test_zero_elapsed_is_idle(self):
        assert duty_fractions({}, 0.0) == {
            "host": 0.0, "device": 0.0, "idle": 1.0}

    def test_host_phase_rollup_covers_everything_but_device(self):
        assert set(HOST_PHASES) | {"device_wait"} == set(TICK_PHASES)


class TestConservation:
    """THE acceptance gate: phase decomposition conserves wall time."""

    def _run_traffic(self, svc, n=8, tokens=8):
        threads = [
            threading.Thread(
                target=svc.generate, args=(f"phase probe request {i} ",),
                kwargs={"max_new_tokens": tokens},
            )
            for i in range(n)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)

    def test_per_tick_conservation_and_bounded_keys(self, recorder, metrics):
        svc = PagedGenerationService(_engine())
        try:
            self._run_traffic(svc)
        finally:
            svc.close()
        ticks = [e for e in recorder.timeline() if "phase_ms" in e]
        assert len(ticks) >= 3, "multi-request run produced too few ticks"
        for tick in ticks:
            phase_ms = tick["phase_ms"]
            # the fixed bounded key set — exactly, not just a subset
            assert set(phase_ms) == set(TICK_PHASES)
            assert all(v >= 0.0 for v in phase_ms.values())
            # conservation: phases tile the pump iteration ("other" absorbs
            # the residual by construction; rounding leaves sub-ms slack)
            total = sum(phase_ms.values())
            assert total == pytest.approx(
                tick["pump_ms"], rel=0.05, abs=0.5), (
                f"phase sum {total} != pump_ms {tick['pump_ms']}: {phase_ms}"
            )
            # the engine-step subset is bounded by its measured dur_ms span
            engine_ms = (phase_ms["admission_build"]
                         + phase_ms["prefill_dispatch"]
                         + phase_ms["decode_dispatch"]
                         + phase_ms["device_wait"])
            assert engine_ms <= tick["dur_ms"] * 1.05 + 0.5
        # at least one tick paid real dispatch/wait time
        assert any(
            t["phase_ms"]["decode_dispatch"] + t["phase_ms"]["device_wait"]
            > 0.0
            for t in ticks
        )

    def test_duty_cycle_sums_to_one(self, recorder, metrics):
        svc = PagedGenerationService(_engine())
        try:
            self._run_traffic(svc)
            stats = svc.stats()
        finally:
            svc.close()
        duty = stats["duty_cycle"]
        assert set(duty) == set(DUTY_STATES)
        assert sum(duty.values()) == pytest.approx(1.0, abs=0.01)
        # phase totals carry the same bounded key set
        assert set(stats["phase_seconds"]) == set(TICK_PHASES)
        assert stats["duty_elapsed_s"] > 0
        # traffic ran: the window cannot be pure idle
        assert duty["idle"] < 1.0
        assert duty["host"] + duty["device"] > 0.0

    def test_phase_histogram_and_cardinality_guard(self, recorder, metrics):
        svc = PagedGenerationService(_engine())
        try:
            self._run_traffic(svc, n=4)
        finally:
            svc.close()
        histos = metrics.memory.snapshot()["histograms"]
        recorded = {k for k in histos if k.startswith("tick_phase(")}
        assert recorded, "pump recorded no tick phases"
        assert recorded <= {f"tick_phase{(p,)}" for p in TICK_PHASES}
        # the guard: an unknown phase key is dropped, not minted as a series
        metrics.record_tick_phases({"bogus_phase": 1.0, "deliver": 0.001})
        histos = metrics.memory.snapshot()["histograms"]
        assert not any("bogus_phase" in k for k in histos)
        assert any("deliver" in k for k in histos)

    def test_reset_duty_cycle_rebases_window(self, recorder, metrics):
        svc = PagedGenerationService(_engine())
        try:
            self._run_traffic(svc, n=2, tokens=4)
            before = svc.stats()["phase_seconds"]
            assert sum(before.values()) > 0
            svc.reset_duty_cycle()
            time.sleep(0.01)
            after = svc.stats()
            assert sum(after["phase_seconds"].values()) == pytest.approx(
                0.0, abs=1e-6)
            assert after["duty_cycle"]["idle"] == pytest.approx(1.0, abs=0.01)
        finally:
            svc.close()

    def test_finishing_tick_stays_in_request_window(self, recorder, metrics):
        """Regression (review): the pump must record the tick BEFORE
        delivering results — finish_engine stamps tick_last from the
        recorder sequence, and the window filter (first < tick <= last)
        would otherwise exclude the very tick each request finished in
        (a generation finishing in its first tick would report an EMPTY
        window). The completed phase split is amended on afterwards."""
        svc = PagedGenerationService(_engine())
        try:
            svc.generate("window probe", max_new_tokens=4,
                         request_id="win-1")
        finally:
            svc.close()  # pump joined: the final tick's amend has landed
        record = recorder.get("win-1")
        assert record is not None
        assert record["ticks"], "finishing tick missing from the window"
        last = record["ticks"][-1]
        assert last["tick"] == record["engine"]["tick_last"]
        # the amended phase decomposition rides the window's final tick
        assert set(last["phase_ms"]) == set(TICK_PHASES)
        assert "pump_ms" in last

    def test_amend_tick(self, recorder):
        seq = recorder.record_tick(replica=0, dur_ms=1.0)
        t_before = recorder.timeline()[-1]["t_s"]
        assert recorder.amend_tick(
            seq, pump_ms=2.0, phase_ms={"other": 2.0}) == 1
        evt = recorder.timeline()[-1]
        assert evt["pump_ms"] == 2.0
        assert evt["phase_ms"] == {"other": 2.0}
        assert evt["t_s"] >= t_before  # restamped to the span's end
        assert recorder.amend_tick(10_000, pump_ms=1.0) == 0

    def test_direct_engine_step_publishes_phases(self, recorder):
        eng = _engine(pipeline_depth=1)
        eng.run_all(["direct engine probe"], max_new_tokens=4)
        phases = eng.last_step_phases
        assert set(phases) <= set(TICK_PHASES)
        assert sum(phases.values()) > 0.0


class TestRequestStages:
    """The same contract one level up: request stages tile the server-side
    time to first token as tick phases tile ``pump_ms``."""

    def test_the_stage_set_is_fixed_and_the_tile_is_its_head(self):
        assert REQUEST_STAGES[:len(TTFT_STAGES)] == TTFT_STAGES
        assert TTFT_STAGES[-1] == "other"
        assert set(REQUEST_STAGES) - set(TTFT_STAGES) == {"decode", "verify", "stream_lag"}
        assert not set(REQUEST_STAGES) & {f"tick.{p}" for p in TICK_PHASES}

    @pytest.mark.parametrize("stage_s, ttft_s", [
        ({}, 0.25),
        ({"prefill": 0.2, "inbox_wait": 0.04}, 0.25),
        ({"embed": 0.3, "rerank": 0.3, "prefill": 0.5}, 1.0),  # overlap: other < 0
    ])
    def test_tile_conserves_by_construction(self, stage_s, ttft_s):
        tile = tile_ttft(stage_s, ttft_s)
        assert tuple(tile) == TTFT_STAGES
        assert sum(tile.values()) == pytest.approx(ttft_s, abs=1e-12)
        assert tile["other"] == pytest.approx(ttft_s - sum(stage_s.values()), abs=1e-12)

    def test_every_request_of_a_run_tiles_its_ttft(self, recorder, metrics):
        """More callers than slots: slot_wait is real, and every request's
        nine stages still sum to its server-side TTFT; the histogram holds
        each stage once a request, the sums add up to the TTFTs'."""
        svc = PagedGenerationService(_engine(max_slots=2))
        n = 6
        try:
            threads = [
                threading.Thread(
                    target=svc.generate, args=(f"stage probe request {i} ",),
                    kwargs={"max_new_tokens": 8, "request_id": f"stage-{i}"})
                for i in range(n)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
        finally:
            svc.close()
        records = [recorder.get(f"stage-{i}") for i in range(n)]
        for record in records:
            assert tuple(record["stages_ms"]) == TTFT_STAGES
            assert sum(record["stages_ms"].values()) == pytest.approx(
                record["ttft_server_ms"], abs=1e-6)
            # a bare service call: nothing before the ticket, so the three
            # engine stages are the whole of it — the record starts at the
            # ticket's own stamp, however long a loaded host takes from
            # there to the recorder (to the microsecond the stamps are kept to)
            engine = sum(record["stages_ms"][k]
                         for k in ("inbox_wait", "slot_wait", "prefill"))
            assert engine == pytest.approx(record["ttft_server_ms"], abs=0.005)
            assert record["stages_ms"]["other"] == pytest.approx(0.0, abs=0.005)
            names = [sp["name"] for sp in record["spans"]]
            assert names == ["request", "inbox_wait", "slot_wait", "prefill", "decode"]
        assert max(r["stages_ms"]["slot_wait"] for r in records) > 0.0
        histos = {k.split("'")[1]: v for k, v in
                  metrics.export_json()["histograms"].items()
                  if k.startswith("request_stage")}
        assert {k: v["count"] for k, v in histos.items()} == {
            **dict.fromkeys(TTFT_STAGES, n), "decode": n}
        observed = sum(histos[s]["mean"] * n for s in TTFT_STAGES)
        assert observed * 1e3 == pytest.approx(
            sum(r["ttft_server_ms"] for r in records), abs=0.01)


class TestRowSteps:
    """Counted, not sampled: what the slots did with the sub-steps the
    device ran. useful + halted + empty == slots x sub-steps on every tick."""

    @pytest.mark.parametrize("depth", [1, 2])
    def test_every_tick_conserves_failed_ticks_included(self, recorder, metrics, depth):
        from sentio_tpu.infra import faults

        # ticks of four sub-steps and no longer: the longest answer is then three
        # ticks however the three requests arrive (on a loaded host all three are
        # in the inbox before the first tick, and ticks that grow to eight over an
        # empty queue decode them in two: no third step to die)
        engine = _engine(pipeline_depth=depth, max_tick_steps=4)
        svc = PagedGenerationService(engine, retry_budget=2)
        try:
            # the third step dies: at depth 2 with a tick in flight, which
            # the reset then books as delivered to nobody
            with faults.inject("paged.step", error=RuntimeError("row-step probe"),
                               times=1, skip=2) as rule:
                threads = [
                    threading.Thread(
                        target=svc.generate, args=(f"row step probe {i} ",),
                        kwargs={"max_new_tokens": 5 + 3 * i, "timeout_s": 120})
                    for i in range(3)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
                assert not any(t.is_alive() for t in threads)
            assert rule.fired == 1
        finally:
            faults.reset()
            svc.close()
        ticks = [e for e in recorder.timeline() if "row_steps" in e]
        assert any(e.get("event") == "tick_failure" for e in ticks)
        assert len(ticks) >= 4
        for tick in ticks:
            assert tuple(tick["row_steps"]) == ROW_STEP_KINDS
            assert all(n >= 0 for n in tick["row_steps"].values())
            assert sum(tick["row_steps"].values()) == engine.max_slots * tick["sub_steps"], tick
        ring = {k: sum(t["row_steps"][k] for t in ticks) for k in ROW_STEP_KINDS}
        assert ring["useful"] > 0 and ring["halted"] > 0 and ring["empty"] > 0
        # the engine's lifetime totals conserve too; they also hold what a
        # reset flushed (dispatched, never harvested), which no tick saw
        total = engine.row_steps_total
        assert all(total[k] >= ring[k] for k in ROW_STEP_KINDS)
        assert sum(total.values()) % engine.max_slots == 0
        if depth == 1:
            assert total == ring
        counters = metrics.export_json()["counters"]
        assert {k: counters[f"row_steps('{k}',)"] for k in ROW_STEP_KINDS} == ring

    def test_useful_row_steps_are_the_tokens_decoded_after_the_first(self, recorder):
        """A bare engine run: every delivered token but each request's
        first (prefill samples it) is one useful row-step."""
        engine = _engine(pipeline_depth=1)
        results = engine.run_all(["alpha beta", "gamma"], max_new_tokens=7)
        delivered = sum(len(r.tokens) for r in results)
        assert engine.row_steps_total["useful"] == delivered - len(results)
        assert sum(engine.row_steps_total.values()) == (
            engine.max_slots * engine.total_sub_steps)


class TestKvPages:
    """``sentio_tpu_decode_kv_pages_total``: the K/V page blocks of the
    sub-steps the device ran — ``held`` what the decode kernel's walk copies
    and computes, by its own rule, ``tabled`` every cell of every table,
    ``behind_window`` what the rows hold where a layer's window starts later."""

    def test_a_hand_counted_tick(self):
        engine = _engine(max_slots=4, page_size=16, max_pages_per_seq=8)
        a, b = engine.slots[0], engine.slots[1]
        a.active, a.length, a.inflight_steps = True, 20, 0
        b.active, b.length, b.inflight_steps = True, 15, 4
        # 4 sub-steps, budgets 3 and 2; slots 2 and 3 hold no request.
        # slot 0 advances at lens 20, 21, 22 (2 blocks each), then stands: 7
        # slot 1 is at 15 + 4 in flight: 19, 20 (2 blocks each), then 1, 1: 6
        # slots 2, 3: one block (the scratch page) a sub-step: 4 + 4
        got = engine._kv_pages([3, 2, 0, 0], 4)
        # (a family with no window holds nothing behind one)
        assert got == {"held": 7 + 6 + 4 + 4, "tabled": 4 * 4 * 8, "behind_window": 0}

    @pytest.mark.parametrize("lens, blocks", [
        (0, 1), (15, 1), (16, 2), (17, 2), (8 * 16 - 1, 8), (8 * 16 + 40, 8)])
    def test_the_rule_is_the_kernels(self, lens, blocks):
        """lens // page + 1, never past the table: the host counts by the
        function the kernel's docstring names as its own rule."""
        import numpy as np

        from sentio_tpu.kernels.paged_attention import blocks_walked

        assert int(blocks_walked(np.asarray([lens]), 16, 8)[0]) == blocks

    @pytest.mark.parametrize("depth", [1, 2])
    def test_ticks_and_metrics_carry_it(self, recorder, metrics, depth):
        engine = _engine(pipeline_depth=depth)
        svc = PagedGenerationService(engine)
        try:
            svc.generate("kv pages probe " * 3, max_new_tokens=9, timeout_s=120)
        finally:
            svc.close()
        ticks = [e for e in recorder.timeline() if e.get("sub_steps")]
        assert ticks
        cells = engine.max_slots * engine.max_pages_per_seq
        for tick in ticks:
            assert tuple(tick["kv_pages"]) == KV_PAGE_KINDS
            assert tick["kv_pages"]["tabled"] == cells * tick["sub_steps"], tick
            # every row costs its one block; none more than its table
            assert (engine.max_slots * tick["sub_steps"] <= tick["kv_pages"]["held"]
                    <= tick["kv_pages"]["tabled"]), tick
        ring = {k: sum(t["kv_pages"][k] for t in ticks) for k in KV_PAGE_KINDS}
        # one request in four slots of four pages: most of a walk of the
        # table is no work
        assert ring["held"] < 0.5 * ring["tabled"]
        assert engine.kv_pages_total == ring
        counters = metrics.export_json()["counters"]
        assert {k: counters[f"kv_pages('{k}',)"] for k in KV_PAGE_KINDS} == ring
        text = metrics.export_prometheus().decode()
        for kind in KV_PAGE_KINDS:
            assert (f'sentio_tpu_decode_kv_pages_total{{kind="{kind}"}} '
                    f'{float(ring[kind])}') in text

    def test_a_row_that_does_not_advance_is_read_as_one_block(self):
        """A free slot carries its last request's ``lens`` on the device and
        a frozen row its own: the attention gets 0 for both, so the kernel
        walks one block for them (its cost is the blocks ``lens`` names)."""
        import jax
        import jax.numpy as jnp

        from sentio_tpu.models.llama import LlamaConfig, init_llama
        from sentio_tpu.runtime.paged import (
            _paged_attn_xla, init_pool, paged_decode_forward)

        cfg = LlamaConfig.tiny()
        params = init_llama(jax.random.PRNGKey(0), cfg)
        pool = init_pool(cfg, 9, 16)
        seen = []

        def spy(q, k_pages, v_pages, layer, table, lens, n_rep):
            seen.append(lens)
            return _paged_attn_xla(q, k_pages, v_pages, layer, table, lens, n_rep)

        lens = jnp.asarray([37, 21, 5], jnp.int32)
        table = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0], [5, 6, 7, 8]], jnp.int32)
        logits, _, _ = paged_decode_forward(
            params, cfg, jnp.zeros(3, jnp.int32), lens, table, pool.k, pool.v,
            attn_impl=spy, write_mask=jnp.asarray([True, False, True]))
        assert len(seen) == cfg.n_layers
        assert all(got.tolist() == [37, 0, 5] for got in seen)
        assert bool(jnp.isfinite(logits).all())


class TestReplicaAggregation:
    def test_failed_tick_flushes_partial_phases(self, recorder, metrics):
        """ISSUE 13 satellite: a pump iteration that ends in a tick failure
        still records a ``phase_ms`` decomposition (partial engine snapshot,
        residual folded into ``other``; the same bounded key set and
        conservation contract as a successful tick) — chaos-round Perfetto
        traces must not hole every failed tick."""
        from sentio_tpu.infra import faults

        svc = PagedGenerationService(_engine(), retry_budget=1)
        try:
            with faults.inject("paged.step",
                               error=RuntimeError("phase flush probe"),
                               times=1) as rule:
                result = svc.generate("phase flush probe request",
                                      max_new_tokens=4, timeout_s=120)
            assert rule.fired == 1
            # the ticket was requeued past the failed tick (crash
            # containment) and finished normally
            assert result.finish_reason in ("stop", "length")
            stats = svc.stats()
            assert stats["tick_failures"] == 1
        finally:
            faults.reset()
            svc.close()
        failed = [e for e in recorder.timeline()
                  if e.get("event") == "tick_failure"]
        assert len(failed) == 1, "failed tick recorded no flight event"
        tick = failed[0]
        phase_ms = tick["phase_ms"]
        assert set(phase_ms) == set(TICK_PHASES)
        assert all(v >= 0.0 for v in phase_ms.values())
        assert sum(phase_ms.values()) == pytest.approx(
            tick["pump_ms"], rel=0.05, abs=0.5)
        # the failed iteration's wall time landed in the duty totals too
        # (phase_seconds grew by at least the failed tick's pump span)
        assert sum(stats["phase_seconds"].values()) * 1e3 >= (
            tick["pump_ms"] * 0.5
        )

    def test_replica_set_duty_cycle(self, recorder, metrics):
        from sentio_tpu.runtime.replica import ReplicaSet

        e0 = _engine(max_slots=2)
        e1 = ContinuousBatchingEngine(
            params=e0.params, tokenizer=e0.tokenizer, max_slots=2,
            page_size=16, max_pages_per_seq=4, steps_per_tick=4,
            max_tick_steps=8, pipeline_depth=2)
        rs = ReplicaSet(
            [PagedGenerationService(e0), PagedGenerationService(e1)],
            supervise=False,
        )
        try:
            threads = [
                threading.Thread(
                    target=rs.generate, args=(f"replica duty probe {i} ",),
                    kwargs={"max_new_tokens": 4},
                )
                for i in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            stats = rs.stats()
        finally:
            rs.close()
        assert sum(stats["duty_cycle"].values()) == pytest.approx(
            1.0, abs=0.01)
        assert set(stats["phase_seconds"]) == set(TICK_PHASES)
        for row in stats["replicas"]:
            assert sum(row["duty_cycle"].values()) == pytest.approx(
                1.0, abs=0.01)
