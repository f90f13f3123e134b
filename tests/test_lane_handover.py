"""A lane is handed on at the dispatch that spends its row's last budget
(runtime/paged.py: ``_spent``, ``_admit``, ``_harvest``).

At ``pipeline_depth`` 2 results lag one tick, lanes do not: a row whose every
remaining token rides the tick in flight has its lane admitted into while
that tick runs, and the tick's record retires the old slot at its harvest.
The bar is the engine's own: greedy answers are those of a depth-1 engine and
of the cache-free oracle, request for request, and the page pool conserves on
every tick (this module runs with the sanitizer armed, ``conftest.py``).
"""

import dataclasses
import threading

import jax
import pytest

from conftest import CacheFreeGreedy
from sentio_tpu.analysis.sanitizer import _live_slots, check_engine_invariants
from sentio_tpu.infra import faults
from sentio_tpu.infra.flight import FlightRecorder, set_flight_recorder
from sentio_tpu.infra.metrics import MetricsCollector, set_metrics
from sentio_tpu.infra.phases import LANE_ADMISSION_KINDS, ROW_STEP_KINDS
from sentio_tpu.models.llama import LlamaConfig, init_llama
from sentio_tpu.models.tokenizer import ByteTokenizer
from sentio_tpu.runtime.paged import ContinuousBatchingEngine
from sentio_tpu.runtime.service import PagedGenerationService

TICK = 4
PROMPTS = [f"lane handover prompt number {i} " * (1 + i % 3) for i in range(7)]


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny()


@pytest.fixture(scope="module")
def oracle(cfg):
    return CacheFreeGreedy(cfg, rng_seed=0)


def engine_of(oracle, depth, **over):
    return ContinuousBatchingEngine(**{**dict(
        model_config=oracle.model_config, params=oracle.params, tokenizer=oracle.tokenizer,
        max_slots=2, page_size=16, max_pages_per_seq=8, num_pages=64, steps_per_tick=TICK,
        max_tick_steps=TICK, pipeline_depth=depth), **over})


def fresh(engine):
    """An engine as new, but for its compiled programs (an engine compiles its
    own: a test that builds one pays seconds): pool, allocator, radix cache
    and slots rebuilt by ``reset``, the counters the tests read zeroed."""
    engine.reset()
    engine.lane_admissions_total = dict.fromkeys(LANE_ADMISSION_KINDS, 0)
    engine.row_steps_total = dict.fromkeys(ROW_STEP_KINDS, 0)
    engine.total_sub_steps = 0
    return engine


@pytest.fixture(scope="module")
def shared(oracle):
    """Two lanes at depth 1 and 2, one lane at depth 2: ``fresh`` before use."""
    engines = {}

    def get(depth, lanes=2):
        if (depth, lanes) not in engines:
            engines[depth, lanes] = engine_of(oracle, depth, max_slots=lanes)
        return fresh(engines[depth, lanes])

    return get


def drive(engine, prompts, max_new, each_step=None):
    """Submit all, step to the end → (results in submit order, step() calls)."""
    budgets = max_new if isinstance(max_new, (list, tuple)) else [max_new] * len(prompts)
    ids = [engine.submit(p, n, 0.0) for p, n in zip(prompts, budgets)]
    done, steps = {}, 0
    while engine.has_work:
        before = list(engine.slots), [s.active for s in engine.slots]
        out = engine.step()
        steps += 1
        assert steps < 500
        for r in out:
            done[r.request_id] = r
        if each_step is not None:
            each_step(engine, before, out)
    return [done[i] for i in ids], steps


def answers(results):
    return [(r.tokens, r.finish_reason) for r in results]


class TestTokenExact:
    @pytest.mark.parametrize("max_new", [9, 8, 5, 2])
    def test_more_requests_than_lanes(self, oracle, shared, max_new):
        """Seven requests on two lanes, budgets that end on a tick's edge and
        inside one: the depth-2 answers are depth 1's and the oracle's, five
        admissions took a lane in flight, and the pipeline costs ONE step()
        more than depth 1 in all (the last harvest) where it cost one a
        request before."""
        want = oracle.generate(PROMPTS, max_new_tokens=max_new)
        one, steps_one = drive(shared(1), PROMPTS, max_new)
        engine = shared(2)
        two, steps_two = drive(engine, PROMPTS, max_new)
        assert answers(two) == answers(one) == answers(want)
        assert engine.lane_admissions_total == {"free": 2, "spent": 5}
        assert steps_two == steps_one + 1

    def test_one_lane_never_idles_between_requests(self, oracle, shared):
        """One lane, four requests whose budgets end on a tick's edge: every
        request after the first starts in the step after the one that
        dispatched its predecessor's last tick, so the lane runs no sub-step
        on a finished row and none empty — every row-step folded a token."""
        prompts, max_new = PROMPTS[:4], 1 + 2 * TICK
        engine = shared(2, lanes=1)
        got, _ = drive(engine, prompts, max_new)
        assert answers(got) == answers(oracle.generate(prompts, max_new_tokens=max_new))
        assert engine.lane_admissions_total == {"free": 1, "spent": 3}
        assert engine.row_steps_total == {"useful": 4 * 2 * TICK, "halted": 0, "empty": 0}

    def test_mixed_budgets_and_first_tokens_that_end_a_request(self, oracle, shared):
        """Budgets of one token (the row is spent with only its first token
        in flight) beside longer ones."""
        budgets = [1, 7, 1, 12, 3, 1, 6]
        want = [oracle.generate([p], max_new_tokens=n)[0] for p, n in zip(PROMPTS, budgets)]
        one, _ = drive(shared(1), PROMPTS, budgets)
        engine = shared(2)
        two, _ = drive(engine, PROMPTS, budgets)
        assert answers(two) == answers(one) == answers(want)
        assert engine.lane_admissions_total["spent"] > 0

    def test_a_chunked_admission_takes_a_spent_lane(self, oracle):
        """``prefill_chunk`` of one page under every prompt: the new slot sits
        in the handed-on lane with ``prefill_todo`` set for several ticks,
        while the device's carry there still holds the old row's token, length
        and halt flag (``merge_admitted`` runs only behind the last segment).
        The old row rides on as a halted lane would: same answers as depth 1
        and the oracle."""
        want = oracle.generate(PROMPTS, max_new_tokens=9)
        one, _ = drive(engine_of(oracle, 1, prefill_chunk=16), PROMPTS, 9)
        engine = engine_of(oracle, 2, prefill_chunk=16)
        segments = []
        two, _ = drive(engine, PROMPTS, 9, each_step=lambda e, _b, _o: segments.extend(
            s.prefill_segments for s in e.slots if s.active and s.prefill_todo is not None))
        assert answers(two) == answers(one) == answers(want)
        assert engine.lane_admissions_total["spent"] > 0
        assert max(segments) >= 2  # a request was seen mid-prefill, segments behind it


def count_by_observation(counts):
    """An ``each_step`` for ``drive``: a lane whose slot changed in a step was
    admitted into; it was taken IN FLIGHT if it held a request when the step
    began (nothing retires before ``_admit`` inside a step)."""
    def look(engine, before, _out):
        slots, active = before
        for i, slot in enumerate(engine.slots):
            if slot is not slots[i]:
                counts["spent" if active[i] else "free"] += 1
    return look


class TestTheCounter:
    def test_spent_counts_the_admissions_that_took_a_lane_in_flight(self, shared):
        seen = dict.fromkeys(LANE_ADMISSION_KINDS, 0)
        engine = shared(2)
        drive(engine, PROMPTS, [9, 3, 12, 1, 7, 8, 5], each_step=count_by_observation(seen))
        assert engine.lane_admissions_total == seen
        assert seen["spent"] > 0 and sum(seen.values()) == len(PROMPTS)
        stats = engine.stats()
        assert {k: stats[f"lane_admissions_{k}"] for k in LANE_ADMISSION_KINDS} == seen

    @pytest.mark.parametrize("case", ["a-free-lane", "depth-1", "spec"])
    def test_zero_where_nothing_is_handed_on(self, shared, cfg, case):
        """With a lane free for every request, at depth 1 (nothing is in
        flight when ``_admit`` runs) and on a speculative engine (budgets are
        verify blocks) every admission takes a free lane."""
        seen = dict.fromkeys(LANE_ADMISSION_KINDS, 0)
        if case == "a-free-lane":
            engine, prompts = shared(2), PROMPTS[:2]
        elif case == "depth-1":
            engine, prompts = shared(1), PROMPTS
        else:
            f32 = dataclasses.replace(cfg, dtype="float32")
            params = init_llama(jax.random.PRNGKey(0), f32)
            engine = ContinuousBatchingEngine(
                model_config=f32, params=params, draft_params=params, draft_config=f32, spec_k=2,
                max_slots=2, page_size=16, max_pages_per_seq=8, steps_per_tick=TICK,
                max_tick_steps=TICK, pipeline_depth=2)
            prompts = PROMPTS[:5]
        got, _ = drive(engine, prompts, 9, each_step=count_by_observation(seen))
        assert engine.lane_admissions_total == seen == {"free": len(prompts), "spent": 0}
        assert all(len(r.tokens) <= 9 for r in got)


class TestHowARowEnds:
    def test_an_eos_inside_the_spent_tick(self, cfg, oracle):
        """The row's last tick is in flight, its lane already taken, and the
        row ends EARLIER than its budget on an EOS inside that tick: the
        record's slot retires there with ``stop``, as the oracle stops."""
        max_new = 1 + 2 * TICK + 3  # the third tick carries the last three
        stream = oracle.generate(PROMPTS[:1], max_new_tokens=max_new)[0].tokens
        at = next(i for i in range(2 * TICK + 1, max_new) if stream[i] not in stream[:i])
        tokenizer = ByteTokenizer(cfg.vocab_size)
        tokenizer.eos_id = stream[at]
        stopping = CacheFreeGreedy(cfg, params=oracle.params, tokenizer=tokenizer)
        engine = engine_of(stopping, 2)
        two, _ = drive(engine, PROMPTS, max_new)
        assert answers(two) == answers(stopping.generate(PROMPTS, max_new_tokens=max_new))
        assert (two[0].finish_reason, len(two[0].tokens)) == ("stop", at)
        assert engine.lane_admissions_total["spent"] > 0

    def test_a_row_that_ends_by_page_capacity(self, oracle):
        """A window of four pages: the budget asks for more than the pages
        hold, so ``_remaining`` runs out on capacity — the same bound the
        fold retires on."""
        window, max_new = 4 * 16, 40
        prompts = [p * 2 for p in PROMPTS[:5]]
        engine = engine_of(oracle, 2, max_pages_per_seq=4)
        two, _ = drive(engine, prompts, max_new)
        assert engine.lane_admissions_total["spent"] > 0
        kept = window - window // 2  # the prompt's half of the window, BOS included
        for prompt, got in zip(prompts, two):
            assert got.finish_reason == "length" and got.prompt_tokens == kept
            assert len(got.tokens) == window - kept < max_new  # the last one is sampled, never written
            want = oracle.generate([prompt[:kept - 1]], max_new_tokens=len(got.tokens))[0]
            assert got.tokens == want.tokens


class TestAPoolWithNoRoom:
    @pytest.mark.parametrize("spare, spent", [(0, 0), (1, 1)])
    def test_falls_back_to_the_harvest(self, oracle, spare, spent):
        """Each request holds three pages. A pool of two requests' pages has
        no room for a third beside them: the spent lane waits for its harvest,
        as it always did, and nothing fails. One more request's worth of
        pages and the first of the two lanes, spent in the same step, is handed
        on; the second finds the pool empty again and waits."""
        prompts, max_new = [p[:30] for p in PROMPTS[:4]], 2 * TICK + 1
        per_request = -(-(31 + max_new) // 16)
        assert per_request == 3
        engine = engine_of(oracle, 2, num_pages=1 + (2 + spare) * per_request, prefix_cache=False)
        two, _ = drive(engine, prompts, max_new)
        assert answers(two) == answers(oracle.generate(prompts, max_new_tokens=max_new))
        assert engine.lane_admissions_total == {"free": 4 - spent, "spent": spent}
        assert engine.allocator.free_pages == engine.allocator.num_pages - 1

    def test_a_tight_pool_with_the_prefix_cache_on(self, oracle, monkeypatch):
        """A pool of three requests' pages whose cache keeps every retired
        prompt: as on a server in its steady state, hardly a page is FREE and
        an admission — into a free lane or a spent one — evicts the least
        recently used prefixes for its pages. Same answers, and the pool
        conserves."""
        prompts, max_new = [f"{i} heads no other prompt " + p[:14] for i, p in enumerate(PROMPTS)], 2 * TICK + 1
        engine = engine_of(oracle, 2, num_pages=1 + 3 * 4)  # four pages a request, two of them cached when it retires
        evict, lanes_free = engine._radix.evict, []

        def watched(n):
            lanes_free.append(len(engine._free_slot_indices()))
            return evict(n)

        monkeypatch.setattr(engine._radix, "evict", watched)
        two, _ = drive(engine, prompts, max_new)
        assert answers(two) == answers(oracle.generate(prompts, max_new_tokens=max_new))
        assert 0 in lanes_free and engine.lane_admissions_total["spent"] > 0  # a handover evicted for its pages
        assert engine.allocator.free_pages + engine.stats()["prefix_cache_pages"] == engine.allocator.num_pages - 1

    def test_a_request_with_no_room_beside_the_old_row_holds_the_queue(self, oracle, monkeypatch):
        """Nine pages, two rows of three, then a request of five and one of
        three. With both lanes spent the five fit only once a harvest has
        freed an old row's pages. The lane waits for that: nothing is evicted
        in vain, the smaller request behind does not jump the head into a lane
        that is not free yet, and no head skip is counted."""
        prompts = [PROMPTS[0][:30], PROMPTS[3][:30], PROMPTS[2][:62], PROMPTS[6][:30]]
        max_new = 2 * TICK + 1
        engine = engine_of(oracle, 2, num_pages=1 + 9)
        evict, evictions, skips, order = engine._radix.evict, [], [], []

        def watched(n):
            evictions.append((len(engine._free_slot_indices()), evict(n)))  # lanes free, pages the cache gave up
            return evictions[-1][1]

        monkeypatch.setattr(engine._radix, "evict", watched)

        def look(e, _before, _out):
            skips.append(e._head_skips)
            order.extend(s.request_id for s in e.slots if s.active and s.request_id not in order)

        two, _ = drive(engine, prompts, max_new, each_step=look)
        assert answers(two) == answers(oracle.generate(prompts, max_new_tokens=max_new))
        assert engine.lane_admissions_total == {"free": 4, "spent": 0} and not any(skips)
        assert order == [r.request_id for r in two] and all(lanes for lanes, gave_up in evictions if gave_up)


def step_until(engine, done, condition):
    for _ in range(200):
        for r in engine.step():
            done[r.request_id] = r
        if condition():
            return
    raise AssertionError("the condition never held")


class TestTheOverlap:
    """A handed-on slot lives inside ONE ``step()``: detached in ``_admit``,
    retired by the harvest. Between steps — where ``cancel`` and a failed
    tick's ``reset`` happen — every request that holds pages owns its lane."""

    def test_cancel_of_the_request_that_took_the_lane(self, oracle, shared):
        """The step that handed a lane on delivered the old request; its
        successor, cancelled while its first tick is in flight, frees its
        pages and the stale tick is not replayed into the lane's next one."""
        max_new = 1 + 2 * TICK
        engine = shared(2, lanes=1)
        ids = [engine.submit(p, max_new, 0.0) for p in PROMPTS[:4]]
        done = {}
        step_until(engine, done, lambda: engine.lane_admissions_total["spent"] == 1)
        assert list(done) == [ids[0]] and engine.slots[0].request_id == ids[1]
        assert engine.cancel(ids[1]) and not engine.cancel(ids[0])
        while engine.has_work:
            for r in engine.step():
                done[r.request_id] = r
        want = oracle.generate(PROMPTS[:4], max_new_tokens=max_new)
        assert ids[1] not in done
        assert [done[i].tokens for i in (ids[0], ids[2], ids[3])] == [want[k].tokens for k in (0, 2, 3)]
        assert engine.allocator.free_pages + engine.stats()["prefix_cache_pages"] == engine.allocator.num_pages - 1

    def test_cancel_of_a_spent_request_before_its_lane_is_taken(self, oracle, shared):
        """Spent, its last tick in flight, cancelled between two steps: the
        lane is FREE for the next admission and the record's slot, retired,
        is skipped by its harvest."""
        max_new = 1 + 2 * TICK
        engine = shared(2, lanes=1)
        ids = [engine.submit(p, max_new, 0.0) for p in PROMPTS[:3]]
        done = {}
        step_until(engine, done, lambda: engine._spent(engine.slots[0]))
        assert engine.slots[0].request_id == ids[0] and engine.cancel(ids[0])
        while engine.has_work:
            for r in engine.step():
                done[r.request_id] = r
        want = oracle.generate(PROMPTS[:3], max_new_tokens=max_new)
        assert ids[0] not in done
        assert [done[i].tokens for i in ids[1:]] == [w.tokens for w in want[1:]]
        assert engine.lane_admissions_total == {"free": 2, "spent": 1}

    def test_a_failed_step_after_a_handover_resets_and_the_requests_run_again(self, oracle, shared):
        """``paged.step`` dies in the step after a handover, with the new
        request's first tick in flight: ``reset`` books that tick as
        delivered to nobody, every page comes back, and the requests the
        layer above resubmits get the answers they always got."""
        max_new = 1 + 2 * TICK
        engine = shared(2)
        ids = [engine.submit(p, max_new, 0.0) for p in PROMPTS[:6]]
        done = {}
        step_until(engine, done, lambda: engine.lane_admissions_total["spent"] >= 1)
        with faults.inject("paged.step", error=RuntimeError("handover probe"), times=1):
            with pytest.raises(RuntimeError, match="handover probe"):
                engine.step()
        engine.reset()
        check_engine_invariants(engine)
        assert engine.allocator.free_pages == engine.allocator.num_pages - 1
        assert sum(engine.row_steps_total.values()) == engine.max_slots * engine.total_sub_steps
        left = [k for k, i in enumerate(ids) if i not in done]
        again, _ = drive(engine, [PROMPTS[k] for k in left], max_new)
        want = oracle.generate(PROMPTS[:6], max_new_tokens=max_new)
        assert [r.tokens for r in again] == [want[k].tokens for k in left]
        assert all(done[i].tokens == want[k].tokens for k, i in enumerate(ids) if i in done)

    def test_the_pool_conserves_while_two_requests_share_a_lane(self, oracle, shared, monkeypatch):
        """Between ``_admit`` and the harvest the old slot's pages and pins
        are still its own, beside the new request's: the invariants hold
        THERE, not only at the end of the step."""
        engine = shared(2)
        dispatch, handed_on = engine._dispatch_tick, []

        def checked():
            check_engine_invariants(engine)
            handed_on.append(sum(1 for s in _live_slots(engine) if engine.slots[s.lane] is not s))
            return dispatch()

        monkeypatch.setattr(engine, "_dispatch_tick", checked)
        prompts = ["a head every prompt shares: 2 pages " + p for p in PROMPTS if len(p) < 70]
        got, _ = drive(engine, prompts, 9)
        assert answers(got) == answers(oracle.generate(prompts, max_new_tokens=9))
        assert sum(handed_on) == engine.lane_admissions_total["spent"] > 0
        assert engine.stats()["prefix_hits"] > 0


class TestTheTickRing:
    def test_row_steps_conserve_and_the_ring_holds_the_admissions(self):
        """Through the service: every tick's row-steps still sum to slots x
        sub-steps, each tick names the lanes its admissions took, and ring,
        engine, ``/metrics`` and the flight summary agree."""
        recorder, metrics = FlightRecorder(), MetricsCollector()
        set_flight_recorder(recorder)
        set_metrics(metrics)
        engine = ContinuousBatchingEngine(max_slots=2, page_size=16, max_pages_per_seq=4,
                                          steps_per_tick=TICK, max_tick_steps=TICK, pipeline_depth=2)
        svc = PagedGenerationService(engine)
        try:
            threads = [threading.Thread(target=svc.generate, args=(f"ring probe {i} ",),
                                        kwargs={"max_new_tokens": 9, "timeout_s": 120}) for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
        finally:
            svc.close()
            set_flight_recorder(None)
            set_metrics(None)
        ticks = [e for e in recorder.timeline() if "row_steps" in e]
        for tick in ticks:
            assert tuple(tick["row_steps"]) == ROW_STEP_KINDS
            assert tuple(tick["lane_admissions"]) == LANE_ADMISSION_KINDS
            assert sum(tick["row_steps"].values()) == engine.max_slots * tick["sub_steps"], tick
        ring = {k: sum(t["lane_admissions"][k] for t in ticks) for k in LANE_ADMISSION_KINDS}
        assert ring == engine.lane_admissions_total and sum(ring.values()) == 6 and ring["spent"] > 0
        counters = metrics.export_json()["counters"]
        assert {k: counters[f"lane_admissions('{k}',)"] for k in LANE_ADMISSION_KINDS} == ring
        assert recorder.stage_summary()["lane_admissions"] == ring
        assert {k: svc.stats()[f"lane_admissions_{k}"] for k in LANE_ADMISSION_KINDS} == ring


def state_family(name):
    if name == "lfm2_moe":
        from sentio_tpu.models.lfm2_moe import Lfm2MoeConfig as Config, init_lfm2_moe as init
    else:
        from sentio_tpu.models.nemotron_h import NemotronHConfig as Config, init_nemotron_h as init
    cfg = dataclasses.replace(Config.tiny(), dtype="float32")
    return cfg, init(jax.random.PRNGKey(0), cfg)


class TestStateFamilies:
    @pytest.mark.parametrize("name, page, over", [
        ("lfm2_moe", 8, {}), ("nemotron_h", 16, {"ssm_snapshots": 4}),
        ("lfm2_moe", 8, {"prefill_chunk": 16, "num_pages": 96}), ("nemotron_h", 16, {"ssm_snapshots": 4, "prefill_chunk": 32})],
        ids=["lfm2_moe", "nemotron_h", "lfm2_moe-chunked", "nemotron_h-chunked"])
    def test_a_lanes_own_state_is_the_new_requests_from_its_first_token(self, name, page, over):
        """A family that keeps state a LANE (convolution columns, a Mamba
        matrix) beside the pages: the old row's last tick updates the lane's
        state, the new request's prefill (a Mamba family) or ``merge_admitted``
        (a convolution family) overwrites it behind that tick, by device
        order. Token-exact under handover, radix hits that restore a tail or
        a snapshot included: these families JOIN, the engine asks no family
        whether a lane may be handed on. Chunked, the new request's state is
        carried in the lane from segment to segment while the old row's last
        tick is still ahead of the first of them."""
        cfg, tree = state_family(name)
        head = "a shared head of the prompt that fills whole pages " * 2
        own = 3 if "prefill_chunk" in over else 1  # past the cached head, more than a chunk is a request's own
        prompts = [head + f"state family prompt {i} " * own for i in range(6)]  # one width: few programs to compile

        def engine(depth):
            return ContinuousBatchingEngine(
                model_config=cfg, params=tree, max_slots=2, page_size=page, max_pages_per_seq=24,
                steps_per_tick=TICK, max_tick_steps=TICK, pipeline_depth=depth, **over)

        two_engine = engine(2)
        two, _ = drive(two_engine, prompts, 9)
        assert two_engine.lane_admissions_total == {"free": 2, "spent": 4}
        assert two_engine.stats()["prefix_hits"] > 0
        assert ("prefill_chunk" in over) == all(r.prefill_segments > 1 for r in two)
        if name == "lfm2_moe":  # its tiny experts tie: the engine and a whole forward part ways at any depth
            want, _ = drive(engine(1), prompts, 9)
        else:
            oracle = CacheFreeGreedy(cfg, params=two_engine.params, tokenizer=two_engine.tokenizer, width=256)
            want = oracle.generate(prompts, max_new_tokens=9)
        assert answers(two) == answers(want)
