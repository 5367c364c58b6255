"""Traffic from a data file and a seed: questions, a corpus, an arrival plan.

One general generator reads ``benchmark/traffic/<mix>.json``. The seed moves
the ORDER in which questions are asked and WHERE bursts fall, never WHAT is
asked, how many requests a window offers or how far apart they are: a run is
a fixed list of work in a seeded order, so two seeds differ by the order's
effect and nothing else. The corpus and the pool of questions come from the
mix's ``content_seed``; a plan of ``n`` requests asks question ``r`` of the
pool a fixed number of times (its Zipf share of ``n``, by largest remainder)
and the seed shuffles the plan. A closed loop's caller asks its plan ONCE
(``plan_requests``, sized to fill the window at the speed the cell was
defined at): a faster program ends the list early and a slower one is cut by
the window, but no speed makes a seed choose which questions are measured.
An open loop's gaps are the quantiles of the exponential distribution at the
cell's rate (a Poisson process's gaps, every run the same set) in a seeded
order, and its burst episodes are a fixed number of fixed-size groups whose
places are drawn one to a stratum of the window. With speculative decoding an
answer's cost depends on the weights and on what is asked (PR 23: 0.97 to
1.66 s across seeds when the seed chose them), so a seed that chose content
would choose the work. The burst and Zipf arithmetic follows
``rag_llm_k8s_tpu/sim/tracegen.py`` (Poisson gaps, episodes at a multiple of
the rate, p(r) ~ 1/(r+1)^a); it is copied so that the yardstick does not move
when the simulator does.
"""

from __future__ import annotations

import json
import math
import random

TRAFFIC_KEYS = {
    "loop": str,  # "closed" | "open"
    "clients": int,  # closed loop: callers that each wait for their answer
    "rate_rps": float,  # open loop: base arrival rate, requests per second
    "bursts": dict,  # open loop: {"episodes", "arrivals", "rate_multiplier"}
    "content_seed": int,  # the corpus and the pool of questions
    "question_pool": int,
    "zipf_a": float,
    "plan_requests": int,  # closed loop: the requests each caller makes, once, in the window
    "corpus_pages": int,
    "words_per_page": int,
    "max_new_tokens": int,
    "lead_in_requests": int,  # unmeasured requests before the window opens
    "why": str,
}
BURST_KEYS = {"episodes", "arrivals", "rate_multiplier"}

# the corpus's vocabulary: questions made of these words retrieve by content,
# as a user's would
WORDS = (
    "radar technique tool platform language framework trial assess hold adopt "
    "team delivery pipeline service data model retrieval generation index "
    "vector cluster latency throughput security review practice architecture "
    "migration observability testing deployment container runtime compiler "
    "kernel memory bandwidth schedule batch request cache context"
).split()

STEMS = (
    "What does section {n} say about {a} and {b} for a {c}?",
    "How should a team weigh {a} against {b} when planning a {c}?",
    "Summarize the guidance on {a}, {b} and {c} near section {n}.",
    "Which {a} practices does the corpus recommend for {b} and {c}?",
)


def corpus_pdf(content_seed: int, n_pages: int, words_per_page: int) -> bytes:
    """The uploaded document: a multi-page text PDF (uncompressed content
    streams, one Helvetica font) of prose-like text from ``WORDS``, with a
    section marker a page and an item marker every twelve words so that
    chunks embed apart. Byte for byte what ``utils/synth.py synth_pdf`` wrote
    when the cells were measured (PR 23); copied so the corpus cannot move
    under the yardstick."""
    import numpy as np

    rs = np.random.RandomState(content_seed & 0x7FFFFFFF)
    objs = []
    kids = " ".join(f"{4 + 2 * i} 0 R" for i in range(n_pages))
    objs.append(b"<< /Type /Catalog /Pages 2 0 R >>")
    objs.append(f"<< /Type /Pages /Kids [{kids}] /Count {n_pages} >>".encode())
    objs.append(b"<< /Type /Font /Subtype /Type1 /BaseFont /Helvetica >>")
    for page in range(n_pages):
        words = [WORDS[j] for j in rs.randint(0, len(WORDS), words_per_page)]
        lines = [f"Section {page + 1} of the seeded corpus {content_seed & 0x7FFFFFFF}."]
        for j in range(0, len(words), 12):
            lines.append(" ".join(words[j:j + 12]) + f" item{page}x{j}.")
        body = " ".join(f"({ln} ) Tj T*" for ln in lines)
        content = f"BT /F1 12 Tf 14 TL 72 720 Td {body} ET".encode()
        objs.append(
            f"<< /Type /Page /Parent 2 0 R /Contents {5 + 2 * page} 0 R "
            "/Resources << /Font << /F1 3 0 R >> >> >>".encode())
        objs.append(b"<< /Length %d >>\nstream\n%s\nendstream" % (len(content), content))
    out = [b"%PDF-1.4\n"]
    for n, obj in enumerate(objs, start=1):
        out.append(b"%d 0 obj %s endobj\n" % (n, obj))
    out.append(b"trailer << /Root 1 0 R >>\n%%EOF")
    return b"".join(out)


def load_traffic(path: str) -> dict:
    """Parse and validate one traffic file; unknown keys are an error."""
    with open(path, encoding="utf-8") as f:
        mix = json.load(f)
    unknown = set(mix) - set(TRAFFIC_KEYS)
    if unknown:
        raise ValueError(f"{path}: unknown keys {sorted(unknown)}")
    for key, kind in TRAFFIC_KEYS.items():
        if key in mix and kind is float and isinstance(mix[key], int):
            mix[key] = float(mix[key])
        if key in mix and not isinstance(mix[key], kind):
            raise ValueError(f"{path}: {key} must be {kind.__name__}")
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    if mix["loop"] == "closed" and mix.get("clients", 0) < 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    if mix["loop"] == "open":
        if mix.get("rate_rps", 0.0) <= 0.0:
            raise ValueError(f"{path}: an open loop needs rate_rps > 0")
        bursts = mix.setdefault("bursts", {"episodes": 0, "arrivals": 0, "rate_multiplier": 1.0})
        if set(bursts) != BURST_KEYS:
            raise ValueError(f"{path}: bursts needs exactly {sorted(BURST_KEYS)}")
    if mix["loop"] == "closed" and mix.get("plan_requests", 0) < 1:
        raise ValueError(f"{path}: a closed loop needs plan_requests >= 1")
    for key in ("content_seed", "question_pool", "zipf_a", "corpus_pages", "words_per_page",
                "max_new_tokens"):
        if key not in mix:
            raise ValueError(f"{path}: missing {key}")
    mix.setdefault("lead_in_requests", 2)
    return mix


def zipf_shares(n_ranks: int, a: float) -> list:
    """p(r) ~ 1/(r+1)^a over ``n_ranks`` ranks."""
    weights = [1.0 / (r + 1) ** a for r in range(n_ranks)]
    total = sum(weights)
    return [w / total for w in weights]


def zipf_counts(n: int, n_ranks: int, a: float) -> list:
    """How often each rank is asked in ``n`` requests: its Zipf share of
    ``n``, rounded by largest remainder so the counts sum to ``n``."""
    exact = [n * p for p in zipf_shares(n_ranks, a)]
    counts = [int(x) for x in exact]
    by_remainder = sorted(range(n_ranks), key=lambda r: (counts[r] - exact[r], r))
    for r in by_remainder[: n - sum(counts)]:
        counts[r] += 1
    return counts


def question_pool(content_seed: int, n: int, corpus_pages: int) -> list:
    """``n`` distinct questions in the corpus's own vocabulary, most asked
    first."""
    rng = random.Random(content_seed * 7919 + 1)
    pool, seen = [], set()
    while len(pool) < n:
        a, b, c = rng.sample(WORDS, 3)
        q = STEMS[len(pool) % len(STEMS)].format(
            a=a, b=b, c=c, n=rng.randint(1, corpus_pages))
        if q not in seen:
            seen.add(q)
            pool.append(q)
    return pool


def question_plan(seed: int, mix: dict, n: int, stream: int = 0) -> list:
    """``n`` questions: the same multiset for every seed (documents and
    questions that are asked about more than once, by Zipf), in an order the
    seed chooses. ``stream`` separates the callers of a closed loop."""
    pool = question_pool(mix["content_seed"], mix["question_pool"], mix["corpus_pages"])
    counts = zipf_counts(n, len(pool), mix["zipf_a"])
    plan = [q for q, c in zip(pool, counts) for _ in range(c)]
    random.Random(seed * 104729 + 17 * stream + 3).shuffle(plan)
    return plan


def exponential_gaps(n: int, rate: float) -> list:
    """The ``n`` mid-quantiles of Exp(rate): a Poisson process's gaps as a
    fixed set, so every seed offers the same work in another order."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def open_schedule(seed: int, mix: dict, seconds: float) -> list:
    """Due times (seconds from the window's start, ascending) of an open
    loop: ``round(rate * seconds)`` base arrivals spread over the window by
    shuffled exponential gaps, plus ``episodes`` bursts of ``arrivals``
    requests at ``rate_multiplier`` times the rate, one burst to each equal
    stratum of the window's first nine tenths."""
    rng = random.Random(seed * 15485863 + 11)
    rate = mix["rate_rps"]
    n = max(1, round(rate * seconds))
    gaps = exponential_gaps(n, rate)
    rng.shuffle(gaps)
    scale = seconds / (sum(gaps) + 1.0 / rate)  # the set fills the window
    t, due = 0.0, []
    for g in gaps:
        t += g * scale
        due.append(t)
    b = mix["bursts"]
    episodes, arrivals = int(b["episodes"]), int(b["arrivals"])
    if episodes and arrivals:
        burst_gaps = exponential_gaps(arrivals, rate * b["rate_multiplier"])
        span = 0.9 * seconds / episodes
        for e in range(episodes):
            t = e * span + rng.random() * max(span - sum(burst_gaps), 0.0)
            order = burst_gaps[:]
            rng.shuffle(order)
            for g in order:
                t += g
                due.append(min(t, seconds - 1e-6))
    due.sort()
    return due
