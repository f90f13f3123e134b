"""One general traffic generator, driven by a mix's data file.

Everything a run sends is a function of ``--seed`` and the mix's parameters:
the corpus (single-chunk files of bounded length, so that every prompt lands
in the prefill bucket the mix declares whatever is retrieved), the questions
(each names the key words of ``top_k`` files of its own, so retrieval returns
exactly those and no two prompts share a passage), ``top_k``, and
— for an open loop — the arrival schedule, computed BEFORE the window opens.

Open loop: requests are sent at their due times whether or not earlier ones
have finished, and every latency is taken from the DUE time, so a stall
that delays a send is charged to the system, not hidden. How late the
generator itself ran is reported. Closed loop: ``clients`` callers each send
their next request when the last one has finished.

Every seed gives the same multiset of sizes (file lengths, question lengths,
``top_k`` values, inter-arrival gaps) in another order: seeds must not
change the amount of work.
"""

from __future__ import annotations

import math
import random
import threading
import time
from dataclasses import dataclass, field

WORDS = (
    "batch kernel mesh tile vector cache token prefill decode shard matrix memory page "
    "rank queue router replica tensor layer head window stride buffer stream block "
    "prefix radix slot pool lane scalar gather scatter fusion retrieval index passage "
    "score fuse sparse dense embed rerank select verify answer cite source audit"
).split()


def _spread(rng: random.Random, lo: int, hi: int, n: int) -> list[int]:
    """``n`` values covering [lo, hi] evenly, shuffled: the same multiset
    for every seed."""
    vals = [lo + round((hi - lo) * i / max(n - 1, 1)) for i in range(n)]
    rng.shuffle(vals)
    return vals


def _text(rng: random.Random, lead: str, n_chars: int) -> str:
    """ASCII words after ``lead``, exactly ``n_chars`` long, no trailing space."""
    parts = [lead]
    size = len(lead)
    while size < n_chars:
        w = rng.choice(WORDS)
        parts.append(w)
        size += 1 + len(w)
    text = " ".join(parts)[:n_chars]
    return text[:-1] + "." if text.endswith(" ") else text


def file_key(i: int) -> str:
    """Two words no other file holds: what a question about file ``i`` asks for."""
    return f"zq{i:05d}ka zq{i:05d}kb"


def make_corpus(mix: dict, seed: int) -> list[tuple[str, bytes]]:
    c = mix["corpus"]
    rng = random.Random(f"corpus-{seed}")
    lo, hi = c["file_chars"]
    sizes = _spread(rng, lo, hi, c["files"])
    return [(f"d{i:05d}.txt", _text(rng, file_key(i) + " " + file_key(i), n).encode())
            for i, n in enumerate(sizes)]


@dataclass
class Request:
    index: int
    due_s: float            # offset from the window's start (open loop)
    payload: dict
    # filled by the runner
    t_due: float = 0.0
    late_s: float = 0.0
    result: dict = field(default_factory=dict)


def request_groups(mix: dict) -> int:
    """How many requests the corpus serves before a file is asked about a
    second time: each request names ``top_k`` files of its own."""
    return mix["corpus"]["files"] // max(mix["questions"]["top_k"])


def make_requests(mix: dict, seed: int, n: int, salt: str, first_group: int = 0) -> list[Request]:
    """``n`` requests, question lengths and ``top_k`` spread over the mix's
    ranges. Request ``i`` names both key words of ``top_k`` files that no
    other request names (group ``first_group + i`` modulo
    ``request_groups``). Sparse retrieval ranks exactly those files first,
    so whatever order the reranker then gives them, no two prompts open
    with the same passage, and the radix cache matches the template head
    and nothing deeper (a deeper match is another prefill program, compiled
    inside the window). A short question keeps every key word and loses the
    end of "say about"."""
    q = mix["questions"]
    rng = random.Random(f"{salt}-{seed}")
    lo, hi = q["chars"]
    lengths = _spread(rng, lo, hi, n)
    ks = [q["top_k"][i % len(q["top_k"])] for i in range(n)]
    rng.shuffle(ks)
    width, groups = max(q["top_k"]), request_groups(mix)
    out = []
    for i in range(n):
        base = ((first_group + i) % groups) * width
        keys = " ".join(file_key(base + j) for j in range(ks[i]))
        asks = f"r{rng.randrange(10**6):06d} what does {keys}"
        if len(asks) > lengths[i]:
            raise ValueError(f"a question of {lengths[i]} characters cannot name {ks[i]} files")
        out.append(Request(i, 0.0, {"question": _text(rng, asks + " say about", lengths[i]),
                                    "top_k": ks[i], "mode": mix.get("mode", "fast")}))
    return out


def poisson_schedule(rate_rps: float, seconds: float, seed: int) -> list[float]:
    """Arrival offsets of a Poisson process over [0, seconds). The number of
    arrivals is fixed at round(rate x seconds) and the gaps are a shuffled
    exponential quantile set scaled to fill the window: every seed offers the
    same load with the same gap distribution, in another order."""
    n = max(int(round(rate_rps * seconds)), 1)
    rng = random.Random(f"arrivals-{seed}")
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    rng.shuffle(gaps)
    scale = seconds / (sum(gaps) * (1.0 + 1.0 / n))  # room after the last arrival
    t, out = 0.0, []
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


def run_open_loop(send, requests: list[Request], seconds: float, workers: int,
                  clock=time.perf_counter, sleep=time.sleep) -> float:
    """Send each request at ``t0 + due_s`` from a pool of ``workers``
    threads; returns ``t0``. A request whose worker was still busy goes out
    late, and its latency still counts from its due time."""
    t0 = clock() + 0.05
    lock = threading.Lock()
    cursor = [0]

    def work() -> None:
        while True:
            with lock:
                i = cursor[0]
                if i >= len(requests):
                    return
                cursor[0] += 1
            req = requests[i]
            req.t_due = t0 + req.due_s
            wait = req.t_due - clock()
            if wait > 0:
                sleep(wait)
            req.late_s = max(clock() - req.t_due, 0.0)
            req.result = send(req.payload)

    threads = [threading.Thread(target=work, name=f"bench-open-{i}", daemon=True)
               for i in range(min(workers, len(requests)))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t0


def run_closed_loop(send, requests: list[Request], seconds: float, clients: int,
                    stagger_s: float = 0.0, clock=time.perf_counter,
                    sleep=time.sleep) -> tuple[float, int]:
    """``clients`` callers take requests off one list, each sending its next
    when its last has finished, until the window closes; a request in flight
    at the close runs to its end. Caller ``i`` starts ``i / clients x
    stagger_s`` into the window: answers of one length sent all at once end
    all at once, and the callers would move in waves whose last, cut by the
    window's close, decides the count. Returns (t0, requests sent)."""
    t0 = clock()
    lock = threading.Lock()
    cursor = [0]

    def work(delay: float) -> None:
        sleep(delay)
        while clock() - t0 < seconds:
            with lock:
                i = cursor[0]
                if i >= len(requests):
                    return
                cursor[0] += 1
            req = requests[i]
            req.t_due = clock()
            req.result = send(req.payload)

    threads = [threading.Thread(target=work, args=(stagger_s * i / clients,),
                                name=f"bench-closed-{i}", daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t0, cursor[0]


def burst(send, requests: list[Request]) -> None:
    """All of ``requests`` at once, one thread each (warm-up)."""
    def work(req: Request) -> None:
        req.t_due = time.perf_counter()
        req.result = send(req.payload)

    threads = [threading.Thread(target=work, args=(r,), name=f"bench-warm-{r.index}",
                                daemon=True) for r in requests]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


# ------------------------------------------------------------------ reduction


def token_count(text: str) -> int:
    """Answer tokens behind a streamed piece. The program's ByteTokenizer
    renders every token as exactly one code point (a byte below 128 as its
    character, any other id as U+FFFD) except its five specials, which it
    drops (5 ids of the vocabulary; EOS ends the answer). The stream carries
    no count, so the code points are the most direct source there is."""
    return len(text)


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile (q in 0..100)."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(math.ceil(q / 100.0 * len(ordered)), 1)
    return ordered[rank - 1]


def reduce_requests(requests: list[Request], t0: float, seconds: float) -> dict:
    """Client-side numbers of one window. A request that failed, was
    refused, came back degraded or without a model verdict counts in
    ``failed`` and in no latency."""
    sent = [r for r in requests if r.result]
    ok = [r for r in sent if not r.result["problem"]]
    ttft, tpot, pre, pairs, answer = [], [], [], [], []
    tokens_in_window = 0
    longest_gap = 0.0
    for r in ok:
        pieces = r.result["pieces"]
        first_t, first_text = pieces[0]
        ttft.append((first_t - r.t_due) * 1e3)
        if r.result["t_sources"] is not None:
            pre.append((r.result["t_sources"] - r.result["t_send"]) * 1e3)
        n_total = sum(token_count(text) for _t, text in pieces)
        n_after_first = n_total - token_count(first_text)
        last_t = pieces[-1][0]
        answer.append((last_t - r.t_due) * 1e3)
        # tokens delivered AFTER the first event over the time after it: the
        # pump delivers a whole tick of tokens in one event, so the first
        # event's own tokens were produced before its timestamp
        gap = None
        if n_after_first > 0 and last_t > first_t:
            gap = (last_t - first_t) * 1e3 / n_after_first
            tpot.append(gap)
        pairs.append((ttft[-1], gap))
        longest_gap = max([longest_gap] + [(b[0] - a[0]) * 1e3 for a, b in zip(pieces, pieces[1:])])
        tokens_in_window += sum(token_count(text) for t, text in pieces
                                if t0 <= t < t0 + seconds)
    lateness = [r.late_s * 1e3 for r in sent]
    return {
        "attempted": len(sent),
        "failed": len(sent) - len(ok),
        "problems": sorted({r.result["problem"] for r in sent if r.result["problem"]})[:5],
        "ttft_ms": ttft, "tpot_ms": tpot, "pre_generate_ms": pre, "ttft_tpot_pairs": pairs,
        "answer_ms": answer,
        "answer_tokens_in_window": tokens_in_window,
        "stream_gap_max_ms": longest_gap,
        "answer_tokens_per_request": percentile(
            [sum(token_count(x) for _t, x in r.result["pieces"]) for r in ok], 50),
        # how long after the window's close the last answer ended
        "drain_s": max([r.result["t_done"] - (t0 + seconds) for r in sent if r.result["t_done"]] + [0.0]),
        "generator_late_ms_p50": percentile(lateness, 50),
        "generator_late_ms_max": max(lateness) if lateness else None,
    }
