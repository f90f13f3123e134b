"""The traffic generator: a function of the seed alone, the same amount of
work for every seed, latency from the due time, and prompts that land in
the prefill programs a mix declares."""

import json
import threading
from collections import Counter
from pathlib import Path

import pytest

from benchmark import traffic

REPO = Path(__file__).resolve().parents[2]
MIXES = {p.stem: json.loads(p.read_text()) for p in (REPO / "benchmark" / "traffic").glob("*.json")}
PAGE = 128


def test_schedule_is_a_function_of_the_seed_alone():
    a = traffic.poisson_schedule(1.5, 40.0, 7)
    assert a == traffic.poisson_schedule(1.5, 40.0, 7)
    b = traffic.poisson_schedule(1.5, 40.0, 3_000_000_019)
    assert a != b and len(a) == len(b) == 60
    assert all(0.0 < t < 40.0 for t in a) and a == sorted(a)
    gaps = lambda ts: sorted(round(y - x, 9) for x, y in zip([0.0] + ts, ts))  # noqa: E731
    assert gaps(a) == pytest.approx(gaps(b))  # the same gaps in another order


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_gives_the_same_sizes(mix):
    spec = MIXES[mix]
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


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert traffic.percentile(values, 50) == 50 and traffic.percentile(values, 90) == 90
    assert traffic.percentile([3.0], 90) == 3.0 and traffic.percentile([], 50) is None


def _prompt_tokens(mix, file_chars, question_chars, score):
    """The prompt the PROGRAM builds for the largest/smallest request of a
    mix, in ByteTokenizer tokens (+1 BOS)."""
    from sentio_tpu.models.document import Document
    from sentio_tpu.ops.generator import LLMGenerator

    k = mix["questions"]["top_k"][0]
    docs = [Document(text="x" * file_chars, id=f"i{i}",
                     metadata={"source": f"d{i:05d}.txt", "rerank_score": score})
            for i in range(k)]
    return len(LLMGenerator().build_prompt("q" * question_chars, docs).encode()) + 1


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_prompts_land_in_the_declared_prefill_programs(mix):
    """The set of compiled programs must not depend on what is retrieved:
    whatever files come back, the prompt stays inside the mix's declared
    band, whose unmatched suffix (after the 256-token cached template head)
    sits in ONE 512-wide bucket."""
    spec = MIXES[mix]
    (f_lo, f_hi), (q_lo, q_hi) = spec["corpus"]["file_chars"], spec["questions"]["chars"]
    assert len(spec["questions"]["top_k"]) == 1
    # "(score 0.512)" is the shortest a score prints, "(score -0.512)" the usual longest
    lo, hi = _prompt_tokens(spec, f_lo, q_lo, 0.512), _prompt_tokens(spec, f_hi, q_hi, -0.512)
    declared_lo, declared_hi = spec["shapes"]["prompt_tokens"]
    assert (lo, hi) == (declared_lo, declared_hi)
    head = 2 * PAGE  # static template head: 288 tokens, two whole pages cached
    chunk = int(spec["serve_env"].get("PREFILL_CHUNK", "0"))
    suffix_lo, suffix_hi = declared_lo - head, declared_hi - head
    if chunk:  # two segments: a full one and a last one in (256, 512]
        assert chunk == 512 and chunk + 256 < suffix_lo and suffix_hi <= 2 * chunk
        assert declared_lo // PAGE == declared_hi // PAGE == 8  # one prior bucket for the audit
    else:      # one dispatch in the 512 bucket
        assert 256 < suffix_lo and suffix_hi <= 512


def _named_files(request) -> set[int]:
    return {int(w[2:7]) for w in request.payload["question"].split()
            if w.startswith("zq") and w.endswith(("ka", "kb"))}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_no_two_requests_of_a_run_name_the_same_file(mix):
    """Warm-up (three rounds at worst) and a window name disjoint files, so
    no two prompts can open with the same passage whatever the reranker
    does, and the radix cache never matches deeper than the template head."""
    spec = MIXES[mix]
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


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_sparse_retrieval_ranks_the_named_files_first(mix):
    """The program's own BM25 puts exactly the files a question names in
    the first ``top_k`` places; with the dense leg at 0.01 of the sparse one
    in ``weighted_rrf`` (rank constant 60) the fused list keeps them there:
    1/63 for the third beats 1/64 + 0.01/61 for any other file."""
    from sentio_tpu.models.document import Document
    from sentio_tpu.ops.bm25 import BM25Index

    spec = MIXES[mix]
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
