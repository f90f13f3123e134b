"""The traffic generator: a function of the seed alone, the same amount of
work for every seed, latency from the due time, and prompts that land in
the prefill programs a mix declares — computed by the program's own prompt
builders and the engine's own bucketing, so a mix of any length is held to
the programs IT lands in."""

import threading
import types
from collections import Counter

import pytest
from bench_tree import BENCH, load_dir

from benchmark import traffic

MIXES = load_dir("traffic")

# Two mixes that are no files: a long-context closed loop (8 one-chunk files a
# question, prompts of 4.6 to 4.8k tokens, 9 prefill segments a request, rows
# that hold 38 pages) which passes every per-mix test, and its twin 20
# characters a file shorter, whose smallest prompt ends in another width
# bucket than its largest — the control of the band test.
LONG = {
    "name": "scratch-long", "loop": "closed", "clients": 8, "stagger_s": 4.0, "mode": "fast", "verifier": False,
    "corpus": {"files": 1024, "file_chars": [460, 480]},
    "questions": {"chars": [200, 230], "top_k": [8]},
    "shapes": {"prompt_tokens": [4616, 4814]},
    "serve_env": {"CONTEXT_TOKEN_BUDGET": "1024", "LLM_MAX_TOKENS": "96", "USE_VERIFIER": "0",
                  "PREFIX_CACHE": "1", "PREFILL_CHUNK": "512", "KV_PAGE_SIZE": "128", "KV_MAX_PAGES_PER_SEQ": "40",
                  "FUSION_METHOD": "weighted_rrf", "SPARSE_WEIGHT": "1.0", "DENSE_WEIGHT": "0.005",
                  "RETRIEVAL_TOP_K": "8", "RERANK_TOP_K": "8"},
    "warmup_bursts": [1, 8],
    "warm_programs": {"paged.step_n": 1, "paged.prior_prefill_scatter": 5, "paged.merge_admitted": 1},
}
STRADDLING = {**LONG, "name": "scratch-straddling", "corpus": {"files": 1024, "file_chars": [440, 460]},
              "shapes": {"prompt_tokens": [4456, 4654]}}
SCRATCH = {"scratch-long": LONG, "scratch-straddling": STRADDLING}
ALL = {**MIXES, **SCRATCH}
PER_MIX = sorted(MIXES) + ["scratch-long"]


def test_schedule_is_a_function_of_the_seed_alone():
    a = traffic.poisson_schedule(1.5, 40.0, 7)
    assert a == traffic.poisson_schedule(1.5, 40.0, 7)
    b = traffic.poisson_schedule(1.5, 40.0, 3_000_000_019)
    assert a != b and len(a) == len(b) == 60
    assert all(0.0 < t < 40.0 for t in a) and a == sorted(a)
    gaps = lambda ts: sorted(round(y - x, 9) for x, y in zip([0.0] + ts, ts))  # noqa: E731
    assert gaps(a) == pytest.approx(gaps(b))  # the same gaps in another order


@pytest.mark.parametrize("mix", PER_MIX)
def test_every_seed_gives_the_same_sizes(mix):
    spec = ALL[mix]
    sizes = lambda seed: Counter(len(d) for _n, d in traffic.make_corpus(spec, seed))  # noqa: E731
    assert sizes(1) == sizes(2_147_483_700)
    asks = lambda seed: Counter((len(r.payload["question"]), r.payload["top_k"])  # noqa: E731
                                for r in traffic.make_requests(spec, seed, 64, "window"))
    assert asks(1) == asks(2_147_483_700)
    assert traffic.make_requests(spec, 5, 8, "window")[3].payload == \
        traffic.make_requests(spec, 5, 8, "window")[3].payload
    lo, hi = spec["corpus"]["file_chars"]
    assert all(lo <= len(d) <= hi for _n, d in traffic.make_corpus(spec, 9))


def test_latency_is_taken_from_the_due_time_not_the_send_time():
    """One worker, a first request that stalls for 5 s: the second goes out
    3 s late, and those 3 s are in its latency."""
    now = [100.0]
    lock = threading.Lock()

    def clock():
        with lock:
            return now[0]

    def sleep(seconds):
        with lock:
            now[0] += seconds

    def send(payload):
        t_send = clock()
        sleep(5.0 if payload["question"] == "slow" else 0.25)
        return {"t_send": t_send, "t_sources": t_send + 0.1, "problem": None,
                "pieces": [(clock(), "ab"), (clock() + 1.0, "cde")], "t_done": clock()}

    requests = [traffic.Request(0, 1.0, {"question": "slow"}),
                traffic.Request(1, 3.0, {"question": "quick"})]
    t0 = traffic.run_open_loop(send, requests, 10.0, workers=1, clock=clock, sleep=sleep)
    assert requests[0].late_s == 0.0 and requests[1].late_s == pytest.approx(3.0)
    out = traffic.reduce_requests(requests, t0, 10.0)
    assert out["ttft_ms"] == pytest.approx([5000.0, 3250.0])  # 3 s of waiting + 0.25 s
    assert out["generator_late_ms_max"] == pytest.approx(3000.0)
    # 3 tokens after the first event's 2, one second later
    assert out["tpot_ms"] == pytest.approx([1000.0 / 3, 1000.0 / 3])
    # due time -> last token event: the first token's wait and a second more
    assert out["answer_ms"] == pytest.approx([6000.0, 4250.0])
    assert out["attempted"] == 2 and out["failed"] == 0


def test_failed_requests_count_in_no_latency():
    ok = traffic.Request(0, 0.0, {})
    ok.t_due, ok.result = 1.0, {"t_send": 1.0, "t_sources": 1.1, "problem": None,
                                "pieces": [(1.5, "abcd"), (2.5, "efgh")], "t_done": 2.6}
    bad = traffic.Request(1, 0.0, {})
    bad.t_due, bad.result = 1.0, {"t_send": 1.0, "t_sources": None, "pieces": [(9.0, "sorry")],
                                  "problem": "no verifier verdict", "t_done": 9.0}
    out = traffic.reduce_requests([ok, bad], 0.0, 2.0)
    assert out["attempted"] == 2 and out["failed"] == 1 and len(out["ttft_ms"]) == 1
    assert out["answer_ms"] == pytest.approx([1500.0])
    assert out["answer_tokens_in_window"] == 4  # the second piece came after the window


def _answered(index, t_due, first, last, problem=None, send_lag=0.0):
    req = traffic.Request(index, 0.0, {})
    req.t_due = t_due
    req.result = {"t_send": t_due + send_lag, "t_sources": t_due + send_lag + 0.1, "problem": problem,
                  "pieces": [(first, "a" * 16), (last, "b" * 240)], "t_done": last}
    return req


def test_the_time_to_a_whole_answer_runs_from_the_due_time_to_the_last_token():
    """``answer_ms``: due time -> last token event, whatever the request waited
    before it was sent; a degraded request and one that never answered are in
    ``failed`` and in no latency."""
    sent_late = _answered(0, t_due=10.0, first=12.0, last=15.0, send_lag=1.5)
    degraded = _answered(1, t_due=10.0, first=11.0, last=40.0, problem="degraded: generator fallback")
    refused = traffic.Request(2, 0.0, {})
    refused.t_due, refused.result = 10.0, {"t_send": 10.0, "t_sources": None, "pieces": [],
                                           "problem": "status 503", "t_done": 10.1}
    never_sent = traffic.Request(3, 0.0, {})   # the window closed before a caller took it
    out = traffic.reduce_requests([sent_late, degraded, refused, never_sent], 0.0, 60.0)
    assert out["answer_ms"] == pytest.approx([5000.0]) and out["ttft_ms"] == pytest.approx([2000.0])
    assert out["attempted"] == 3 and out["failed"] == 2
    assert out["tpot_ms"] == pytest.approx([3000.0 / 240])


@pytest.mark.parametrize("stalled", [3, 20])
def test_one_stalled_request_among_24_moves_the_median_answer_by_at_most_one_request(stalled):
    """Why the median and not the rate: a stall of delivery that holds one
    answer for 30 s (PR 27's runs) takes that much out of the mean and the
    rate, and moves the median by at most the step to the next request."""
    step = 10.0   # ms between neighbours of the quiet run's sorted answers
    quiet = [_answered(i, t_due=100.0 + i, first=101.0 + i, last=105.0 + i + i * step / 1e3) for i in range(24)]
    held = [_answered(i, t_due=100.0 + i, first=101.0 + i, last=105.0 + i + i * step / 1e3 + (30.0 if i == stalled else 0.0))
            for i in range(24)]
    a, b = (traffic.reduce_requests(reqs, 100.0, 40.0) for reqs in (quiet, held))
    p50 = lambda out: traffic.percentile(out["answer_ms"], 50)  # noqa: E731
    assert p50(a) == pytest.approx(5000.0 + 11 * step)
    assert 0.0 <= p50(b) - p50(a) <= step + 1e-6
    assert p50(b) - p50(a) == pytest.approx(step if stalled < 12 else 0.0)
    mean = lambda out: sum(out["answer_ms"]) / 24  # noqa: E731
    assert mean(b) - mean(a) == pytest.approx(30000.0 / 24)
    assert b["stream_gap_max_ms"] > 30000.0 > a["stream_gap_max_ms"]   # and the window note says which run it was


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert traffic.percentile(values, 50) == 50 and traffic.percentile(values, 90) == 90
    assert traffic.percentile([3.0], 90) == 3.0 and traffic.percentile([], 50) is None


def _prompts(spec, file_chars, question_chars, score):
    """The prompts the PROGRAM builds for the largest/smallest request of a
    mix: the answer's and, where the mix audits, the audit's (which embeds
    the answer's prompt as its head and quotes the answer, every answer token
    3 bytes of text: ``families/llama.py::make_params``)."""
    from sentio_tpu.models.document import Document
    from sentio_tpu.ops.generator import LLMGenerator
    from sentio_tpu.ops.prompts import PromptBuilder

    docs = [Document(text="x" * file_chars, id=f"i{i}",
                     metadata={"source": f"d{i:05d}.txt", "rerank_score": score})
            for i in range(spec["questions"]["top_k"][0])]
    generator, builder, query = LLMGenerator(), PromptBuilder(), "q" * question_chars
    answer = generator.build_prompt(query, docs)
    audit = builder.build("verify", instruction=builder.load("profile"), context=generator.prepare_context(docs),
                          query=query, answer="\ufffd" * int(spec["serve_env"]["LLM_MAX_TOKENS"]))
    assert audit.startswith(answer[: len(answer) // 2])
    return answer, audit


def _tokens(text: str) -> int:
    return len(text.encode()) + 1  # ByteTokenizer, +1 BOS


def _common_tokens(a: str, b: str) -> int:
    a, b = a.encode(), b.encode()
    return 1 + next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), min(len(a), len(b)))


def _serving_envs(spec) -> list[dict]:
    """What the engine is built from, for every cell of this mix: the
    program's defaults, under the cell's configuration, under the mix. A mix
    no cell uses yet stands on the defaults and on what it states itself."""
    from sentio_tpu.config import GeneratorConfig

    d = GeneratorConfig()
    defaults = {"KV_PAGE_SIZE": d.kv_page_size, "KV_MAX_PAGES_PER_SEQ": d.kv_max_pages_per_seq,
                "PREFILL_CHUNK": d.prefill_chunk, "USE_VERIFIER": int(d.use_verifier),
                "LLM_MAX_TOKENS": d.max_new_tokens, "VERIFIER_MAX_TOKENS": d.verifier_max_tokens}
    configs = {c["name"]: c for c in load_dir("configs").values()}
    cells = [configs[w["config"]] for w in BENCH["workloads"] if w["traffic"] == spec["name"]]
    return [{**defaults, **config["serve_env"], **spec["serve_env"]} for config in cells or [{"serve_env": {}}]]


def prefill_programs(spec, env) -> dict:
    """Every prefill dispatch of the smallest and of the largest request of a
    mix as ``(width bucket, prior-page bucket, samples the first token)``, in
    order, by the ENGINE's own rules: what the radix cache serves (the
    template's whole pages for an answer, the answer prompt's whole pages for
    its audit), ``_admit``'s test for chunking, ``_advance_prefill``'s
    segments, ``_prefill_width`` and ``_prior_bucket``. → ``{"lo": {"answer":
    [...], "audit": [...]}, "hi": {...}, "prompt_tokens": [lo, hi]}``."""
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine as Engine

    page, chunk = int(env["KV_PAGE_SIZE"]), int(env["PREFILL_CHUNK"])
    engine = types.SimpleNamespace(page_size=page, max_pages_per_seq=int(env["KV_MAX_PAGES_PER_SEQ"]),
                                   PREFILL_BUCKETS=Engine.PREFILL_BUCKETS)
    window = engine.max_pages_per_seq * page

    def dispatches(tokens: int, cached: int, max_new: int):
        # the prompt is never cut: ``_admit`` keeps min(max_new + 2, window / 2) of the window for the answer
        assert tokens <= window - min(max_new + 2, window // 2), (tokens, window)
        todo, done, out = tokens - cached, 0, []
        if not (chunk and todo > chunk):
            return [(Engine._prefill_width(engine, todo), Engine._prior_bucket(engine, cached // page), True)]
        while todo:
            seg = min(chunk, todo)
            out.append((Engine._prefill_width(engine, seg),
                        Engine._prior_bucket(engine, (cached + done) // page), todo <= chunk))
            todo, done = todo - seg, done + seg
        return out

    (f_lo, f_hi), (q_lo, q_hi) = spec["corpus"]["file_chars"], spec["questions"]["chars"]
    # "(score 0.512)" is the shortest a score prints, "(score -0.512)" the usual longest
    ends = {"lo": _prompts(spec, f_lo, q_lo, 0.512), "hi": _prompts(spec, f_hi, q_hi, -0.512)}
    # the static template head, whatever is retrieved and asked: its whole pages are cached
    other = _prompts(spec, f_hi, q_hi, 0.25)[0].replace("d00000.txt", "e00000.txt", 1)
    head = _common_tokens(ends["hi"][0], other) // page * page
    out = {"prompt_tokens": [_tokens(ends["lo"][0]), _tokens(ends["hi"][0])], "head_pages": head // page}
    for end, (answer, audit) in ends.items():
        n = _tokens(answer)
        out[end] = {"answer": dispatches(n, head, int(env["LLM_MAX_TOKENS"])),
                    "audit": dispatches(_tokens(audit), n // page * page, int(env["VERIFIER_MAX_TOKENS"]))
                    if int(env["USE_VERIFIER"]) else []}
    return out


def _band_holds(spec):
    """What ``test_prompts_land_in_the_declared_prefill_programs`` asserts of
    a mix, file or dict."""
    from sentio_tpu.graph.nodes import select_documents
    from sentio_tpu.models.document import Document
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine as Engine

    assert len(spec["questions"]["top_k"]) == 1
    k, f_hi = spec["questions"]["top_k"][0], spec["corpus"]["file_chars"][1]
    # the context budget keeps every retrieved passage, or the prompt would depend on which ones it drops
    docs = [Document(text="x" * f_hi, id=f"i{i}", metadata={"rerank_score": 0.5}) for i in range(k)]
    assert len(select_documents(docs, int(spec["serve_env"]["CONTEXT_TOKEN_BUDGET"]))[0]) == k
    seen = []
    for env in _serving_envs(spec):
        got = prefill_programs(spec, env)
        assert got["prompt_tokens"] == spec["shapes"]["prompt_tokens"], "the declared band is not what the program builds"
        assert got["lo"] == got["hi"], ("the smallest and the largest prompt run other programs at both ends "
                                        f"of the band: {got['lo']} / {got['hi']}")
        # ... and that set is what the mix's warm-up is held to: a chunked
        # admission is one row, an unchunked one any row bucket the callers fill
        programs = set(got["lo"]["answer"] + got["lo"]["audit"])
        chunked = len(got["lo"]["answer"]) > 1
        callers = min(spec.get("clients") or spec["workers"], int(env.get("LLM_MAX_BATCH", 1 << 30)))
        rows = 1 if chunked else sum(1 for b in Engine.ADMIT_BUCKETS if b < 2 * callers)
        assert spec["warm_programs"]["paged.prior_prefill_scatter"] == len(programs) * rows
        seen.append(got)
    return seen


@pytest.mark.parametrize("mix", PER_MIX)
def test_prompts_land_in_the_declared_prefill_programs(mix):
    """The set of compiled programs must not depend on what is retrieved:
    whatever files come back, the smallest and the largest prompt of the
    mix's declared band run the SAME prefill dispatches — the count of
    segments, the width bucket of each, the prior-page bucket of each, for the
    answer and for its audit — and that set is what ``warm_programs`` warms.
    Every number is the program's: its prompt builders, ``PREFILL_BUCKETS``,
    ``_prefill_width``, ``_prior_bucket``, the mix's ``PREFILL_CHUNK`` and the
    cell's page size and window."""
    assert _band_holds(ALL[mix])


def test_the_two_mixes_land_where_they_always_did():
    """By name, what the constants of this test used to say (512, two
    segments, ``prompt // 128 == 8``; one dispatch in the 512 bucket): the
    computation may not loosen what the two cells' mixes are held to."""
    for rag in _band_holds(MIXES["rag-open"]):  # once for every cell of the mix
        assert rag["head_pages"] == 2  # the 288-token template head: two whole pages
        # two 512-wide segments over the 2-page head, the second over 6 pages in the bucket of 8; the audit over
        # the answer prompt's 8 pages (one prior bucket at both ends), then over 12 in the bucket of 16
        assert rag["lo"] == rag["hi"] == {"answer": [(512, 2, False), (512, 8, True)],
                                          "audit": [(512, 8, False), (512, 16, True)]}
        assert rag["prompt_tokens"] == [1041, 1134] and all(n // 128 == 8 for n in rag["prompt_tokens"])
    assert MIXES["rag-open"]["warm_programs"]["paged.prior_prefill_scatter"] == 4
    for name in ("chat-closed", "chat-closed-16"):   # 16 callers fill the same four row buckets as 24: they stop at 8
        for chat in _band_holds(MIXES[name]):
            # one dispatch in the 512 bucket over the head, in any of four row buckets
            assert chat["head_pages"] == 2 and chat["lo"] == chat["hi"] == {"answer": [(512, 2, True)], "audit": []}
            assert chat["prompt_tokens"] == [658, 754]
        assert MIXES[name]["warm_programs"]["paged.prior_prefill_scatter"] == 4


# a mix named ``<mix>-<n>`` is ``<mix>`` at a stated concurrency of n callers
STATED = sorted((name, base, int(name[len(base) + 1:])) for name in MIXES for base in MIXES
                if name.startswith(base + "-") and name[len(base) + 1:].isdigit())


def test_a_mix_at_a_stated_concurrency_is_found():
    assert ("chat-closed-16", "chat-closed", 16) in STATED


@pytest.mark.parametrize("name, base, callers", STATED)
def test_a_mix_at_a_stated_concurrency_is_its_base_mix_in_every_other_field(name, base, callers):
    """``chat-closed-16`` is ``chat-closed`` with 16 callers and stays so: a
    cell whose step time follows the rows that advance (a routed family)
    must read one load whatever the program under test does with callers it
    cannot serve at once, and the ONLY thing that may differ from the mix the
    dense cells run is how many callers there are — and what follows from
    that: the callers' first requests as far apart (``stagger_s`` over
    ``clients``), the last warm-up burst as large as the loop, the rest of
    the bursts, every size, the serving environment, the prefill program set
    and the rehearsal as they are."""
    mix, like = MIXES[name], MIXES[base]
    assert set(like) <= set(mix) and set(mix) - set(like) <= {"warm_note"}
    differ = {k for k in like if mix[k] != like[k]}
    assert differ - {"warm_programs"} == {"name", "why", "clients", "stagger_s", "warmup_bursts"}
    if "warm_programs" in differ:   # the warm-up's count held tighter than the base mix holds it, never looser, and it says why
        assert "warm_note" in mix and set(mix["warm_programs"]) == set(like["warm_programs"])
        assert all(mix["warm_programs"][k] >= n for k, n in like["warm_programs"].items())
    assert like["loop"] == "closed" and mix["clients"] == callers and f"{callers} callers" in mix["why"]
    assert mix["stagger_s"] / callers == pytest.approx(like["stagger_s"] / like["clients"])
    assert mix["warmup_bursts"] == like["warmup_bursts"][:-1] + [callers] and like["warmup_bursts"][-1] == like["clients"]


def test_a_long_mix_lands_in_nine_segments_and_its_straddling_twin_fails_the_band_alone():
    (long,) = _band_holds(LONG)
    # 8 full segments over priors of 2, 6, 10 ... 30 pages and a last one over 34, capped at the window's 40
    assert long["lo"]["answer"] == [(512, 2, False), (512, 8, False), (512, 16, False), (512, 16, False),
                                    (512, 32, False), (512, 32, False), (512, 32, False), (512, 32, False),
                                    (512, 40, True)]
    assert -(-(long["prompt_tokens"][1] + 96) // 128) == 39  # pages a row holds when its answer ends
    # the twin: the same count of segments, and a last one of 104 tokens at one end (the 128 bucket), 302 at the other
    with pytest.raises(AssertionError, match="other programs at both ends") as caught:
        _band_holds(STRADDLING)
    assert "(128, 40, True)" in str(caught.value) and "(512, 40, True)" in str(caught.value)
    for passes in (test_every_seed_gives_the_same_sizes, test_no_two_requests_of_a_run_name_the_same_file,
                   test_sparse_retrieval_ranks_the_named_files_first):
        passes("scratch-straddling")


def _named_files(request) -> set[int]:
    return {int(w[2:7]) for w in request.payload["question"].split()
            if w.startswith("zq") and w.endswith(("ka", "kb"))}


@pytest.mark.parametrize("mix", PER_MIX)
def test_no_two_requests_of_a_run_name_the_same_file(mix):
    """Warm-up (three rounds at worst) and a window name disjoint files, so
    no two prompts can open with the same passage whatever the reranker
    does, and the radix cache never matches deeper than the template head."""
    spec = ALL[mix]
    groups, sent, seen, asked = traffic.request_groups(spec), 0, [], 0
    for round_no in range(3):
        for size in spec["warmup_bursts"]:
            seen += traffic.make_requests(spec, 7, size, f"warm-{round_no}-{size}",
                                          first_group=groups - sent - size)
            sent += size
    in_window = round(spec["rate_rps"] * 51) if spec["loop"] == "open" else groups - sent
    seen += traffic.make_requests(spec, 7, in_window, "window")
    for request in seen:
        files = _named_files(request)
        assert len(files) == request.payload["top_k"]
        asked += len(files)
    assert len(set().union(*map(_named_files, seen))) == asked


@pytest.mark.parametrize("mix", PER_MIX)
def test_sparse_retrieval_ranks_the_named_files_first(mix):
    """The program's own BM25 puts exactly the files a question names in
    the first ``top_k`` places; with the dense leg at 0.01 of the sparse one
    in ``weighted_rrf`` (rank constant 60) the fused list keeps them there:
    1/63 for the third beats 1/64 + 0.01/61 for any other file."""
    from sentio_tpu.models.document import Document
    from sentio_tpu.ops.bm25 import BM25Index

    spec = ALL[mix]
    env = spec["serve_env"]
    assert env["FUSION_METHOD"] == "weighted_rrf"
    sparse, dense = float(env["SPARSE_WEIGHT"]), float(env["DENSE_WEIGHT"])
    top_k = max(spec["questions"]["top_k"])
    assert sparse / (60 + top_k) > sparse / (60 + top_k + 1) + dense / 61
    docs = [Document(text=body.decode(), id=name) for name, body in traffic.make_corpus(spec, 3)]
    index = BM25Index().build(docs)
    for request in traffic.make_requests(spec, 3, 40, "window"):
        hits = index.search(request.payload["question"], top_k=request.payload["top_k"])
        assert {i for i, _score in hits} == _named_files(request)
