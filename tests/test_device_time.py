"""The device's time, booked by program from inside the program
(infra/tracing.py::DeviceStamper, ISSUE 40): a completion stamp for every
dispatch, the bounded program set, the counters and span fields they feed,
chunked prefill's turns, and a stall that says which side it was on.

Fake arrays (anything with ``block_until_ready``) drive the stamper where a
test needs to decide WHEN a program is done; the engine and the encoders
drive it with real ones."""

import threading
import time

import pytest

from sentio_tpu.infra import tracing
from sentio_tpu.infra.flight import FlightRecorder, set_flight_recorder
from sentio_tpu.infra.metrics import MetricsCollector, set_metrics
from sentio_tpu.infra.phases import (
    DEVICE_PROGRAMS,
    ENCODER_FORWARD_PARTS,
    ENCODER_PROGRAMS,
    PREFILL_TURN_KINDS,
)
from sentio_tpu.runtime.paged import ContinuousBatchingEngine
from sentio_tpu.runtime.service import PagedGenerationService


@pytest.fixture()
def recorder():
    rec = FlightRecorder()
    set_flight_recorder(rec)
    yield rec
    set_flight_recorder(None)


class Booked(MetricsCollector):
    """The registry, keeping every completion stamp booked into it."""

    def __init__(self):
        super().__init__()
        self.stamps = []

    def record_device_program(self, program, seconds, queued_s=0.0):
        super().record_device_program(program, seconds, queued_s)
        self.stamps.append((program, seconds, queued_s))


@pytest.fixture()
def metrics():
    m = Booked()
    set_metrics(m)
    yield m
    set_metrics(None)


@pytest.fixture()
def stamper():
    """A stamper of the test's own: its counts start at zero and another
    test's late stamps land elsewhere."""
    s = tracing.DeviceStamper()
    tracing.set_stamper(s)
    yield s
    s.wait_idle(30)
    tracing.set_stamper(None)


class Done:
    """An output that is ready after ``seconds`` (from its first wait), or
    once ``gate`` is set."""

    def __init__(self, seconds: float = 0.0, gate: threading.Event = None):
        self.seconds, self.gate = seconds, gate

    def block_until_ready(self):
        if self.gate is not None:
            assert self.gate.wait(30)
        if self.seconds:
            time.sleep(self.seconds)
        return self


def dispatch(stamper, program, out, **kw) -> int:
    """One dispatch as a site makes it: the place taken, the call made, the
    output handed over."""
    with stamper.dispatching(program, **kw) as stamp:
        stamp.out = out
    return stamp.seq


class Broken:
    def block_until_ready(self):
        raise RuntimeError("Array has been deleted.")


class Intervals(FlightRecorder):
    """A recorder that keeps every completion stamp handed to it."""

    def __init__(self):
        super().__init__()
        self.stamps = []

    def note_device_time(self, request_id, name, t_dispatch, t_start, t_done):
        self.stamps.append((t_dispatch, t_start, t_done))


def _engine(**kw):
    defaults = dict(max_slots=4, page_size=16, max_pages_per_seq=8, ignore_eos=True,
                    steps_per_tick=4, max_tick_steps=8, pipeline_depth=2)
    defaults.update(kw)
    return ContinuousBatchingEngine(**defaults)


def _counters(metrics, name):
    return {k.split("'")[1]: v for k, v in metrics.export_json()["counters"].items()
            if k.startswith(f"{name}(")}


def _seconds(metrics):
    """The registry's booked seconds by program, the unbooked at zero."""
    return {**dict.fromkeys(DEVICE_PROGRAMS, 0.0), **_counters(metrics, "device_program")}


def _lost(stamper):
    """(dropped, set aside) since the last tick record, as the ring gets them."""
    fields = stamper.take_tick_fields()
    return fields["stamps_dropped"], fields["stamps_set_aside"]


class TestTheSet:
    def test_the_program_set_is_fixed_and_bounded(self):
        assert DEVICE_PROGRAMS == ("decode", "prefill", "admit", "embed", "rerank", "other")
        assert set(ENCODER_PROGRAMS) < set(DEVICE_PROGRAMS)
        assert ENCODER_FORWARD_PARTS == ("queued", "running")
        assert PREFILL_TURN_KINDS == ("taken", "waited")

    def test_a_typo_raises_at_the_writer(self, stamper, metrics):
        with pytest.raises(KeyError, match="decod"):
            stamper.dispatching("decod")
        with pytest.raises(KeyError, match="prefil"):
            metrics.record_device_program("prefil", 0.1)
        assert not metrics.stamps and not _counters(metrics, "device_program")

    def test_every_label_is_published_from_the_start_zeros_included(
            self, stamper, metrics, recorder):
        """The benchmark's reader returns nothing where a label it names is
        absent: all six programs are on ``/metrics`` before any stamp."""
        text = metrics.export_prometheus().decode()
        for program in DEVICE_PROGRAMS:
            assert f'sentio_tpu_device_program_seconds_total{{program="{program}"}} 0.0' in text
        dispatch(stamper, "admit", Done(0.002))
        assert stamper.wait_idle()
        assert _counters(metrics, "device_program")["admit"] >= 0.002
        text = metrics.export_prometheus().decode()
        assert 'sentio_tpu_device_program_seconds_total{program="decode"} 0.0\n' in text
        # an encoder's parts appear with its first forward, both of them
        assert "sentio_tpu_encoder_forward_seconds_total{" not in text
        metrics.record_device_program("embed", 0.003, queued_s=0.3)
        assert _counters(metrics, "encoder_forward") == {"queued": 0.3, "running": 0.003}

    def test_the_series_nothing_read_are_gone(self, metrics):
        text = metrics.export_prometheus()
        for gone in (b"decode_tokens_per_second", b"sentio_llm_", b"sentio_embeddings"):
            assert gone not in text
        assert not hasattr(metrics, "record_llm") and not hasattr(metrics, "record_embeddings")


class TestBooking:
    def test_the_booked_seconds_tile_the_run_less_its_idle_exactly(self, stamper, metrics):
        """A program holds the device from the later of its dispatch and its
        predecessor's completion to its own completion; what lies between
        two intervals is idle and is booked nowhere. So the six labels sum
        to last done - first start less the idle between, by construction."""
        keep = Intervals()
        set_flight_recorder(keep)
        try:
            plan = [("decode", 0.02), ("prefill", 0.01), ("admit", 0.0), ("decode", 0.015),
                    ("embed", 0.004), ("other", 0.0), ("rerank", 0.004), ("decode", 0.01)]
            for n, (program, seconds) in enumerate(plan):
                dispatch(stamper, program, Done(seconds), spans=[("tile", "x")])
                if n == 3:
                    assert stamper.wait_idle()
                    time.sleep(0.03)  # the device stands idle: booked nowhere
            assert stamper.wait_idle()
        finally:
            set_flight_recorder(None)
        stamps = keep.stamps
        assert len(stamps) == len(plan)
        idle = 0.0
        for (_d0, _s0, done0), (sent, start, done) in zip(stamps, stamps[1:]):
            assert start == max(done0, sent)  # the rule, to the bit
            assert done >= start
            idle += start - done0
        assert idle >= 0.03
        totals = _seconds(metrics)
        span = stamps[-1][2] - stamps[0][1]
        assert sum(totals.values()) == pytest.approx(span - idle, abs=1e-9)
        assert sum(totals.values()) == pytest.approx(
            sum(done - start for _d, start, done in stamps), abs=1e-12)
        # the tick ring is handed what the registry holds, and nothing was lost
        fields = stamper.take_tick_fields()
        assert fields["device_ms"] == pytest.approx(
            {p: s * 1e3 for p, s in totals.items()}, abs=1e-3)
        assert tuple(fields["device_ms"]) == DEVICE_PROGRAMS
        assert fields["stamps_dropped"] == fields["stamps_set_aside"] == 0
        assert not any(stamper.take_tick_fields()["device_ms"].values())  # taken once
        assert set(_counters(metrics, "device_program")) == {p for p, _s in plan}
        assert totals["decode"] >= 0.045 and totals["prefill"] >= 0.01
        # queued + running of the encoders' forwards: dispatch -> done
        parts = _counters(metrics, "encoder_forward")
        encoders = [s for (p, _), s in zip(plan, stamps) if p in ENCODER_PROGRAMS]
        assert parts["queued"] + parts["running"] == pytest.approx(
            sum(done - sent for sent, _s, done in encoders), abs=1e-9)

    def test_the_place_in_the_order_is_taken_before_the_call(self, stamper, metrics):
        """The jit call is where the program is enqueued, somewhere inside
        its milliseconds: an encoder forward dispatched whole while the
        pump's call is still returning ran AFTER the tick on the device, and
        must not be waited for first (it would be given the tick's time)."""
        keep = Intervals()
        set_flight_recorder(keep)
        try:
            tick = Done(0.05)
            with stamper.dispatching("decode", spans=[("o", "tick")]) as stamp:
                # the tick is enqueued; its call has not returned yet
                dispatch(stamper, "rerank", Done(), spans=[("o", "forward")])
                time.sleep(0.01)
                stamp.out = tick
            assert stamper.wait_idle()
        finally:
            set_flight_recorder(None)
        totals = _seconds(metrics)
        assert totals["decode"] >= 0.05 and totals["rerank"] < 0.01
        assert len(keep.stamps) == 2 and keep.stamps[0][2] <= keep.stamps[1][1]

    def test_a_call_that_does_not_return_is_set_aside_and_stamped_when_it_has(
            self, stamper, metrics, recorder, monkeypatch, caplog):
        """A dispatch that compiles or hangs keeps its place for ``STALL_S``;
        then what was dispatched behind it is stamped, and it when it
        returns. The set-aside is counted once and logged once."""
        monkeypatch.setattr(tracing, "STALL_S", 0.05)
        returned = threading.Event()

        def compiling():
            with stamper.dispatching("prefill") as stamp:
                assert returned.wait(30)
                stamp.out = Done()

        slow = threading.Thread(target=compiling)
        with caplog.at_level("INFO", logger="sentio_tpu.infra.tracing"):
            slow.start()
            time.sleep(0.02)
            seq = dispatch(stamper, "decode", Done())
            stamper.harvested(seq)
            deadline = time.perf_counter() + 10
            while not metrics.stamps and time.perf_counter() < deadline:
                time.sleep(0.01)
            time.sleep(0.12)  # the stamper comes back to the hanging call, and counts it once
            assert [p for p, _s, _q in metrics.stamps] == ["decode"]  # past the call that hangs
            returned.set()
            slow.join(timeout=30)
            assert stamper.wait_idle()
        assert [p for p, _s, _q in metrics.stamps] == ["decode", "prefill"]
        assert _lost(stamper) == (0, 1)
        lines = [r.getMessage() for r in caplog.records if "set aside" in r.getMessage()]
        assert len(lines) == 1 and "prefill" in lines[0]

    def test_a_set_aside_program_that_ran_first_gives_its_time_to_the_one_stamped_ahead(
            self, stamper, metrics, recorder, monkeypatch):
        """What a set-aside costs (the class's docstring says it): the tick's
        call hangs past ``STALL_S`` AFTER enqueuing its program, which runs
        on the device before the forward dispatched behind it. The forward
        is stamped first and is booked from its own dispatch through the
        tick's whole run; the tick, stamped when its call returns, books
        nothing. The sum holds, two labels do not, and the count says so."""
        monkeypatch.setattr(tracing, "STALL_S", 0.05)
        device = threading.Event()  # set when the device has run tick and forward
        returned = threading.Event()

        def tick():
            with stamper.dispatching("decode", tick=5) as stamp:
                assert returned.wait(30)
                stamp.out = Done()  # it ran long ago

        held = threading.Thread(target=tick)
        held.start()
        time.sleep(0.01)
        t_forward = time.perf_counter()
        dispatch(stamper, "embed", Done(gate=device))
        time.sleep(0.15)  # the tick's 0.1 s on the device, then the forward's
        device.set()
        deadline = time.perf_counter() + 10
        while not metrics.stamps and time.perf_counter() < deadline:
            time.sleep(0.005)
        t_both_done = time.perf_counter()
        returned.set()
        held.join(timeout=30)
        assert stamper.wait_idle()
        assert [p for p, _s, _q in metrics.stamps] == ["embed", "decode"]
        totals = _seconds(metrics)
        assert totals["embed"] >= 0.14  # the tick's run is in it
        assert totals["decode"] < 0.05  # and not where it belongs
        assert totals["embed"] <= t_both_done - t_forward  # the sum still tiles
        assert _lost(stamper) == (0, 1)
        # a length booked out of order is no evidence of a stall
        assert not [e for e in recorder.timeline() if e.get("event") == "stall"]

    def test_a_call_that_raises_is_dropped(self, stamper, metrics, recorder, caplog):
        with caplog.at_level("WARNING", logger="sentio_tpu.infra.tracing"):
            with pytest.raises(ZeroDivisionError):
                with stamper.dispatching("decode", tick=9) as stamp:
                    stamp.out = 1 / 0
            dispatch(stamper, "decode", Done())
            assert stamper.wait_idle()
        assert _lost(stamper) == (1, 0) and len(metrics.stamps) == 1
        lines = [r.getMessage() for r in caplog.records if "stamps dropped" in r.getMessage()]
        assert len(lines) == 1 and "decode, tick 9" in lines[0]

    def test_a_deleted_or_failing_array_is_dropped_and_the_stamper_lives(
            self, stamper, metrics, recorder):
        import jax.numpy as jnp

        gone = jnp.ones(3)
        gone.block_until_ready()
        gone.delete()
        dispatch(stamper, "decode", gone)
        dispatch(stamper, "prefill", Broken())
        dispatch(stamper, "other", object())  # not an array at all
        dispatch(stamper, "decode", jnp.ones(3) + 1)
        assert stamper.wait_idle()
        assert _lost(stamper) == (3, 0) and len(metrics.stamps) == 1
        assert _seconds(metrics)["prefill"] == _seconds(metrics)["other"] == 0.0

    def test_the_stamper_drops_its_reference_once_stamped(self, stamper, metrics, recorder):
        import weakref

        out = Done()
        ref = weakref.ref(out)
        dispatch(stamper, "decode", out)
        del out
        assert stamper.wait_idle()
        dispatch(stamper, "decode", Done())  # the loop's locals move on
        assert stamper.wait_idle()
        assert ref() is None

    def test_a_reset_drains_the_stamper(self, stamper, metrics, recorder, caplog):
        """A failed tick's arrays are not worth waiting for: the engine's
        reset drops what is queued, counted, and the one being waited on
        runs to its end."""
        eng = _engine()
        gate = threading.Event()
        dispatch(stamper, "decode", Done(gate=gate))
        for _ in range(3):
            dispatch(stamper, "prefill", Done(gate=gate))
        time.sleep(0.05)  # the thread is inside the first one's wait
        with caplog.at_level("WARNING", logger="sentio_tpu.infra.tracing"):
            eng.reset()
        assert _lost(stamper) == (3, 0)
        assert sum("stamps dropped: 3" in r.getMessage() for r in caplog.records) == 1
        gate.set()
        assert stamper.wait_idle()
        assert len(metrics.stamps) == 1 and _seconds(metrics)["prefill"] == 0.0
        assert _lost(stamper) == (0, 0)
        # and the engine serves on
        assert eng.run_all(["after the reset"], max_new_tokens=4)[0].tokens

    def test_many_threads_dispatch_at_once_and_no_stamp_is_lost(self, stamper, metrics):
        """No lock is taken round a dispatch: more threads than cores on a
        shortened switch interval, and every dispatch still gets a number
        of its own and is stamped once; the six labels still sum to the
        intervals booked."""
        import sys

        keep = Intervals()
        set_flight_recorder(keep)
        seqs, workers, each = [], 16, 40
        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            def work(k):
                for n in range(each):
                    program = DEVICE_PROGRAMS[(k + n) % len(DEVICE_PROGRAMS)]
                    seqs.append(dispatch(stamper, program, Done(), spans=[("s", "x")]))

            threads = [threading.Thread(target=work, args=(k,)) for k in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert stamper.wait_idle(60)
        finally:
            sys.setswitchinterval(was)
            set_flight_recorder(None)
        assert len(set(seqs)) == len(seqs) == workers * each
        assert len(metrics.stamps) == workers * each and _lost(stamper) == (0, 0)
        assert len(keep.stamps) == workers * each
        assert sum(_seconds(metrics).values()) == pytest.approx(
            sum(done - start for _d, start, done in keep.stamps), abs=1e-9)
        assert all(done >= start >= 0.0 for _d, start, done in keep.stamps)

    def test_a_stamp_leaves_an_annotation_with_its_duration_and_tick(
            self, stamper, metrics, recorder, monkeypatch):
        seen = []

        class Ann:
            def __init__(self, name, **fields):
                seen.append((name, fields))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(tracing, "annotation", Ann)
        dispatch(stamper, "decode", Done(0.01), tick=7)
        dispatch(stamper, "embed", Done(), spans=[("req-1", "embed")])
        assert stamper.wait_idle()
        (name0, f0), (name1, f1) = seen
        assert name0 == "device.decode" and f0["tick"] == 7 and f0["dur_ms"] >= 10.0
        assert name1 == "device.embed" and f1["request_id"] == "req-1" and "tick" not in f1


class TestStall:
    def test_a_program_that_holds_the_device_too_long_is_the_devices(
            self, stamper, metrics, recorder, monkeypatch, caplog):
        monkeypatch.setattr(tracing, "STALL_S", 0.05)
        recorder.record_tick(dur_ms=1.0)
        dispatch(stamper, "decode", Done(0.0), tick=3)
        dispatch(stamper, "prefill", Done(0.12), tick=4)
        dispatch(stamper, "decode", Done(0.0), tick=4)
        with caplog.at_level("WARNING", logger="sentio_tpu.infra.tracing"):
            assert stamper.wait_idle()
        stalls = [e for e in recorder.timeline() if e.get("event") == "stall"]
        assert len(stalls) == 1
        assert stalls[0]["side"] == "device" and stalls[0]["program"] == "prefill"
        assert stalls[0]["gap_ms"] >= 120.0 and stalls[0]["step"] == 4
        lines = [r.getMessage() for r in caplog.records if "stall" in r.getMessage()]
        assert len(lines) == 1 and "device's side" in lines[0] and "last_ticks" in lines[0]

    def test_a_harvest_long_after_its_tick_was_done_is_the_pumps(
            self, stamper, metrics, recorder, monkeypatch, caplog):
        monkeypatch.setattr(tracing, "STALL_S", 0.05)
        prompt = dispatch(stamper, "decode", Done(), tick=1)
        late = dispatch(stamper, "decode", Done(), tick=2)
        stamper.harvested(prompt)
        assert stamper.wait_idle()
        time.sleep(0.08)  # the pump stands; the device was done long ago
        with caplog.at_level("WARNING", logger="sentio_tpu.infra.tracing"):
            stamper.harvested(late)
            assert stamper.wait_idle()
        stalls = [e for e in recorder.timeline() if e.get("event") == "stall"]
        assert len(stalls) == 1
        assert stalls[0]["side"] == "pump" and stalls[0]["gap_ms"] >= 80.0
        assert any("pump's side" in r.getMessage() for r in caplog.records)

    def test_a_harvest_held_back_by_a_compile_is_no_stall(
            self, stamper, metrics, recorder, monkeypatch, caplog):
        """A warm-up compiles the next tick's programs with a tick in
        flight: its harvest comes seconds after its completion and the pump
        was working all the while. ``stall on the`` stays for the stalls."""
        from sentio_tpu.analysis.audit import fence

        monkeypatch.setattr(tracing, "STALL_S", 0.05)
        seq = dispatch(stamper, "decode", Done(), tick=1)
        assert stamper.wait_idle()
        time.sleep(0.08)
        fence.note_compile("paged.prior_prefill_scatter", "a new bucket")
        with caplog.at_level("WARNING", logger="sentio_tpu.infra.tracing"):
            stamper.harvested(seq)
            assert stamper.wait_idle()
        assert not [e for e in recorder.timeline() if e.get("event") == "stall"]
        assert not [r for r in caplog.records if "stall on the" in r.getMessage()]

    def test_a_quiet_run_logs_no_stall(self, stamper, metrics, recorder):
        svc = PagedGenerationService(_engine(max_slots=2))
        try:
            svc.generate("a quiet run", max_new_tokens=6, timeout_s=300)
        finally:
            svc.close()
        assert stamper.wait_idle()
        assert not [e for e in recorder.timeline() if e.get("event") == "stall"]


class TestSpanFields:
    """flight.note_device_time: the span a program was dispatched in gains
    ``device_queued_ms`` and ``device_ms``, early stamp or late."""

    def _span(self, rec, name, t0, t1):
        rec.add_span("r", name, rec.origin() + t0, rec.origin() + t1, None, {"k": 1})

    def _note(self, rec, name, dispatch, start, done):
        o = rec.origin()
        rec.note_device_time("r", name, o + dispatch, o + start, o + done)

    def _fields(self, rec, name):
        return next(sp for sp in rec.get("r")["spans"] if sp["name"] == name)["fields"]

    def test_a_late_stamp_is_written_onto_the_closed_span(self, recorder):
        recorder.start_request("r")
        self._span(recorder, "embed", 1.0, 1.5)
        self._note(recorder, "embed", 1.1, 1.4, 1.45)
        fields = self._fields(recorder, "embed")
        assert fields["device_queued_ms"] == pytest.approx(300.0, abs=0.01)
        assert fields["device_ms"] == pytest.approx(50.0, abs=0.01)
        assert fields["k"] == 1

    def test_an_early_stamp_waits_for_its_span(self, recorder):
        recorder.start_request("r")
        for k in range(3):  # a chunked prompt's segments land before the span exists
            self._note(recorder, "prefill", 2.0 + k, 2.2 + k, 2.5 + k)
        self._note(recorder, "rerank", 2.0, 2.1, 2.2)  # another span's: left alone
        self._span(recorder, "prefill", 1.9, 5.0)
        fields = self._fields(recorder, "prefill")
        assert fields["device_queued_ms"] == pytest.approx(600.0, abs=0.01)
        assert fields["device_ms"] == pytest.approx(900.0, abs=0.01)
        self._span(recorder, "rerank", 1.95, 2.3)
        assert self._fields(recorder, "rerank")["device_ms"] == pytest.approx(100.0, abs=0.01)

    def test_it_is_cut_to_the_span_and_counts_no_instant_twice(self, recorder):
        recorder.start_request("r")
        self._span(recorder, "prefill", 10.0, 11.0)
        # segment 2 was dispatched while segment 1 still ran; the last stamp
        # was taken late, after the span had closed
        self._note(recorder, "prefill", 10.1, 10.2, 10.6)
        self._note(recorder, "prefill", 10.3, 10.7, 11.4)
        fields = self._fields(recorder, "prefill")
        assert fields["device_queued_ms"] == pytest.approx(100.0 + 100.0, abs=0.01)
        assert fields["device_ms"] == pytest.approx(400.0 + 300.0, abs=0.01)
        assert fields["device_queued_ms"] + fields["device_ms"] <= 1000.0 + 0.01

    def test_a_stamp_of_no_span_is_kept_bounded_and_dropped_with_the_record(self, recorder):
        from sentio_tpu.infra.flight import MAX_DEVICE_PENDING

        recorder.note_device_time("nobody", "embed", 1.0, 1.1, 1.2)  # no record: ignored
        recorder.start_request("r")
        for _ in range(MAX_DEVICE_PENDING + 10):
            self._note(recorder, "embed", 1.0, 1.1, 1.2)
        assert len(recorder._device_pending["r"]) == MAX_DEVICE_PENDING
        recorder.finish_request("r")
        assert "r" not in recorder._device_pending


class TestTheEngine:
    def test_turns_taken_and_waited_are_the_pending_segments_of_every_tick(
            self, recorder, metrics, stamper):
        """More callers than one and ``prefill_chunk`` set: ONE segment a
        tick over all slots, so a tick with n slots holding a pending segment
        books one turn taken and n - 1 waited."""
        eng = _engine(prefill_chunk=32)
        seen = []
        advance = eng._advance_prefill

        def counting():
            seen.append(sum(s.active and s.prefill_todo is not None for s in eng.slots))
            advance()

        eng._advance_prefill = counting
        prompts = [f"a long prompt, number {i}, " * 6 for i in range(3)]
        results = eng.run_all(prompts, max_new_tokens=4)
        assert all(r.tokens for r in results)
        turns = eng.prefill_turns_total
        assert tuple(turns) == PREFILL_TURN_KINDS
        assert turns["taken"] == sum(1 for n in seen if n)
        assert turns["taken"] + turns["waited"] == sum(seen)
        assert turns["waited"] > 0  # three prompts of several segments each
        assert turns["taken"] == sum(r.prefill_segments for r in results)

    def test_a_run_publishes_turns_device_ms_and_the_prefill_spans_fields(
            self, recorder, metrics, stamper):
        svc = PagedGenerationService(_engine(prefill_chunk=32))
        n = 3
        try:
            threads = [threading.Thread(
                target=svc.generate, args=(f"a long prompt, number {i}, " * 6,),
                kwargs={"max_new_tokens": 6, "request_id": f"dev-{i}", "timeout_s": 300})
                for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
        finally:
            svc.close()
        assert stamper.wait_idle()
        turns = _counters(metrics, "prefill_turns")
        assert turns == {k: float(v) for k, v in svc.engine.prefill_turns_total.items()}
        assert turns["taken"] >= 3 * n
        ticks = [e for e in recorder.timeline() if "phase_ms" in e]
        assert ticks and all(tuple(e["device_ms"]) == DEVICE_PROGRAMS for e in ticks)
        assert all(tuple(e["prefill_turns"]) == PREFILL_TURN_KINDS for e in ticks)
        assert sum(e["prefill_turns"]["taken"] for e in ticks) == turns["taken"]
        # nothing lost; a call set aside is a call that compiled for over a second
        assert all(e["stamps_dropped"] == 0 for e in ticks)
        assert (sum(e["stamps_set_aside"] for e in ticks)
                <= sum(e["xla_compiles"] for e in ticks))
        ring = {p: sum(e["device_ms"][p] for e in ticks) for p in DEVICE_PROGRAMS}
        totals = _seconds(metrics)
        assert ring["decode"] > 0.0 and ring["prefill"] > 0.0
        for program in DEVICE_PROGRAMS:  # the ring lacks only what was stamped after its last record
            assert ring[program] <= totals[program] * 1e3 + 0.01
        assert totals["embed"] == totals["rerank"] == 0.0
        for i in range(n):
            prefill = next(sp for sp in recorder.get(f"dev-{i}")["spans"]
                           if sp["name"] == "prefill")
            fields = prefill["fields"]
            assert fields["device_ms"] > 0.0 and fields["device_queued_ms"] >= 0.0
            # what is left of the span is turn wait: never negative
            span_ms = (prefill["t1_s"] - prefill["t0_s"]) * 1e3
            assert fields["device_queued_ms"] + fields["device_ms"] <= span_ms + 0.01
            assert fields["segments"] >= 3

    def test_an_untraced_caller_books_programs_and_no_span(self, recorder, metrics, stamper):
        eng = _engine()
        assert eng.run_all(["no request id anywhere"], max_new_tokens=4)[0].tokens
        assert stamper.wait_idle()
        totals = _seconds(metrics)
        assert totals["decode"] > 0.0 and totals["prefill"] > 0.0
        assert totals["admit"] > 0.0 and _lost(stamper)[0] == 0  # a slow compile may be set aside
        assert not recorder.records()


class TestTheEncoders:
    def test_embed_and_rerank_spans_carry_their_forwards(self, recorder, metrics, stamper):
        from sentio_tpu.config import EmbedderConfig
        from sentio_tpu.models.document import Document
        from sentio_tpu.ops.dense_index import TpuDenseIndex
        from sentio_tpu.ops.embedder import TpuEmbedder
        from sentio_tpu.ops.reranker import CrossEncoderReranker
        from sentio_tpu.ops.retrievers import DenseRetriever

        emb = TpuEmbedder(EmbedderConfig(provider="tpu", model_preset="tiny",
                                         batch_size=8, coalesce=False))
        docs = [Document(text=f"passage {i} about topic {i % 3}", id=f"d{i}") for i in range(6)]
        index = TpuDenseIndex(dim=emb.dimension)
        index.add(docs, emb.embed_many([d.text for d in docs]))
        retriever = DenseRetriever(embedder=emb, index=index)
        reranker = CrossEncoderReranker()
        recorder.start_request("enc")
        with tracing.span("retrieve", request_id="enc"):
            hits = retriever.retrieve("topic 1", top_k=3)
            ranked = reranker.rerank("topic 1", hits)
        assert len(ranked.documents) == 3
        assert stamper.wait_idle()
        spans = {sp["name"]: sp for sp in recorder.get("enc")["spans"]}
        for name in ("embed", "rerank"):
            fields = spans[name]["fields"]
            assert fields["device_ms"] > 0.0 and fields["device_queued_ms"] >= 0.0
            span_ms = (spans[name]["t1_s"] - spans[name]["t0_s"]) * 1e3
            assert fields["device_queued_ms"] + fields["device_ms"] <= span_ms + 0.01
        totals = _seconds(metrics)
        assert totals["embed"] > 0.0 and totals["rerank"] > 0.0
        assert totals["other"] > 0.0  # the index's top-k, under the embed span too
        parts = _counters(metrics, "encoder_forward")
        assert parts["running"] == pytest.approx(totals["embed"] + totals["rerank"], abs=1e-9)

    def test_a_coalesced_batch_gives_each_of_its_requests_the_batchs_figures(
            self, recorder, metrics, stamper):
        from sentio_tpu.config import EmbedderConfig
        from sentio_tpu.ops.embedder import TpuEmbedder

        emb = TpuEmbedder(EmbedderConfig(provider="tpu", model_preset="tiny", batch_size=8,
                                         coalesce=True, coalesce_deadline_ms=200.0))
        if emb._query_batcher is None:
            pytest.skip("this build coalesces nothing")
        emb.embed_device(["warm the program"])
        assert stamper.wait_idle()
        before = len(metrics.stamps)

        def one(i):
            recorder.start_request(f"co-{i}")
            with tracing.span("embed", request_id=f"co-{i}"):
                emb.embed_device([f"query number {i}"]).block_until_ready()

        threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        emb.close()
        assert stamper.wait_idle()
        assert len(metrics.stamps) == before + 1  # ONE forward for both
        fields = [recorder.get(f"co-{i}")["spans"][1]["fields"] for i in range(2)]
        assert all(f["device_ms"] > 0.0 for f in fields)


class TestTheAccountBesideTheDevices:
    """eval/device_account.py pairs each stamp of a profile window with the
    device's own execution that ended just before it."""

    def test_a_pair_the_windows_edge_cut_is_on_neither_side(self):
        from sentio_tpu.eval.device_account import _match

        ms = 1e6  # ns
        lo = 1000 * ms
        # the device's row: a tick the window's start cut to 26 of its 141 ms, two whole ones
        runs = {"decode": [(1026 * ms, 26.0), (1170 * ms, 141.0), (1313 * ms, 140.0)],
                "prefill": [(1029 * ms, 3.0)]}
        # the stamps: each holds the whole program and lands 1-2 ms after it
        stamps = [("decode", 1027 * ms, 141.2), ("prefill", 1030 * ms, 2.9),
                  ("decode", 1171.5 * ms, 141.4), ("decode", 1314 * ms, 140.1),
                  ("decode", 1460 * ms, 141.0)]  # its execution ended after the window
        out = _match(stamps, runs, lo)
        assert out["decode"]["pairs"] == 2
        assert out["decode"]["stamps_ms"] == pytest.approx(281.5)
        assert out["decode"]["modules_ms"] == pytest.approx(281.0)
        assert out["decode"]["lag_ms_max"] == pytest.approx(1.5)
        assert out["prefill"] == {"pairs": 1, "stamps_ms": 2.9, "modules_ms": 3.0,
                                  "lag_ms_p50": 1.0, "lag_ms_max": 1.0}
