"""The one generator of the benchmark's inputs: corpus, queries, arrivals.

Everything is drawn from ``--seed`` and from the numbers in a cell's
configuration file (corpus statistics) and traffic file (arrivals).  The
statistics are those of the program's ``data/synthetic.generate_dataset``
and ``benchmarks/common.bursty_arrival_times``: log-normal topic sizes, one
vocabulary per topic (the base words spelled through a letter permutation
of the topic's own), Zipf query topics over topics ranked by size, and
Poisson or bursty arrivals.  This copy is vectorised and lives with the
benchmark so that no change to the program can move it.

Steadiness: every seed draws the same multiset of sizes, lengths and
query topic ranks (fixed quantiles of each distribution), and only their
order and the words differ.  Arrival gaps are fixed quantiles too, dealt
in the order of the mix file's ``arrival_seed``: one realisation of the
arrival process for every ``--seed``, since the order of the gaps alone
moves a queue's quantiles by tens of percent.  So two seeds give the same
amount of work, and a run's spread is the system's, not the draw's.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

BASE_WORDS = ("the quick brown fox jumps over lazy dog alpha beta gamma delta "
              "epsilon zeta eta theta iota kappa lambda sigma tau phi chi psi "
              "omega data vector index query cluster memory cache edge device "
              "retrieval augmented generation model token latency storage"
              ).split()
_LETTERS = "abcdefghijklmnopqrstuvwxyz"


def topic_words(topic: int) -> List[str]:
    """A topic's vocabulary: every base word spelled through the topic's
    own letter permutation (as ``data/synthetic._topic_words``)."""
    perm = np.random.default_rng(topic).permutation(len(_LETTERS))
    table = str.maketrans(_LETTERS, "".join(_LETTERS[i] for i in perm))
    return [w.translate(table) for w in BASE_WORDS]


def quantiles(n: int) -> np.ndarray:
    """``n`` evenly spaced probabilities strictly inside (0, 1)."""
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(total: int, n: int, sigma: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Topic sizes summing to ``total``: fixed log-normal quantiles, each
    at least 1, dealt to topics in a seeded order."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf(p) for p in quantiles(n)])
    raw = np.exp(sigma * z)
    sizes = np.maximum(1, np.round(raw / raw.sum() * total)).astype(np.int64)
    while sizes.sum() > total:
        sizes[np.argmax(sizes)] -= 1
    while sizes.sum() < total:
        sizes[np.argmin(sizes)] += 1
    return rng.permutation(sizes)


def normal_lengths(n: int, mean: float, cv: float, lo: int,
                   rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths at fixed quantiles of Normal(mean, cv * mean), at
    least ``lo``, in a seeded order."""
    from statistics import NormalDist
    dist = NormalDist(mean, cv * mean)
    vals = np.array([dist.inv_cdf(p) for p in quantiles(n)])
    return rng.permutation(np.maximum(lo, np.round(vals)).astype(np.int64))


def zipf_ranks(n: int, a: float, n_topics: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` 0-based ranks at fixed quantiles of Zipf(a), with the mass past
    the last rank folded onto it (as ``generate_dataset`` clips its draws),
    in a seeded order."""
    k = np.arange(1, n_topics, dtype=np.float64)
    from scipy.special import zeta
    pmf = k ** -a / zeta(a)
    cdf = np.concatenate([np.cumsum(pmf), [1.0]])
    ranks = np.minimum(np.searchsorted(cdf, quantiles(n)), n_topics - 1)
    return rng.permutation(ranks)


def arrival_times(seconds: float, rate: float, rng: np.random.Generator,
                  burst: int = 1, burst_gap_frac: float = 0.1) -> np.ndarray:
    """Open-loop arrival offsets (s) in [0, seconds) at mean ``rate``.

    ``burst=1``: Poisson, i.e. exponential gaps.  ``burst>1``: ``burst``
    back-to-back requests ``burst_gap_frac`` of a mean gap apart, then a
    lull that keeps the mean rate (``bursty_arrival_times``).  The number
    of requests is ``round(rate * seconds)`` and the gaps are fixed
    exponential quantiles scaled to fill the window, dealt in a seeded
    order: every seed offers the same load.
    """
    n = int(round(rate * seconds))
    gap = 1.0 / rate
    exp_q = -np.log1p(-quantiles(n))              # unit-mean exponential
    if burst <= 1:
        gaps = rng.permutation(exp_q) * gap
    else:
        intra = burst_gap_frac * gap
        lull = burst * gap - (burst - 1) * intra
        scale = np.where(np.arange(n) % burst == 0, lull, intra)
        gaps = rng.permutation(exp_q) * scale
    times = np.cumsum(gaps)
    return times * (seconds * (n - 0.5) / n / times[-1])


def batch_groups(arrivals: np.ndarray, max_batch: int,
                 window_s: float) -> List[tuple]:
    """The batches the serving loop forms, as ``(start, end, ready)``:
    requests ``start:end`` in arrival order, sent together once ``ready``
    (seconds after the window opens) has passed and the batch before them
    is answered.  A batch opens at its first request's arrival and takes
    every request that arrives within ``window_s`` of it, up to
    ``max_batch``; it is ready when its window closes or when it is full,
    whichever comes first.  With ``window_s`` 0 every request is served
    alone, first come first served.  The grouping depends on the arrivals
    alone, never on how fast the system answers, so the batch shapes of a
    run are known before its window opens."""
    t = np.asarray(arrivals, np.float64)
    groups, i = [], 0
    while i < len(t):
        j = i + 1
        while j < len(t) and j - i < max_batch and t[j] <= t[i] + window_s:
            j += 1
        ready = t[j - 1] if j - i == max_batch else t[i] + window_s
        groups.append((i, j, float(ready)))
        i = j
    return groups


@dataclasses.dataclass
class Corpus:
    """Passages and the queries that ask about them."""
    texts: List[str]                 # passage i has chunk id i
    topic_of: np.ndarray             # (n,) topic of each passage
    sizes: np.ndarray                # (n_topics,) passages per topic
    query_texts: List[str]
    query_topic: np.ndarray

    def get_chunks(self, ids) -> List[str]:
        texts = self.texts
        return [texts[int(i)] for i in ids]


def _texts(heads: List[str], topics: np.ndarray, lengths: np.ndarray,
           rng: np.random.Generator) -> List[str]:
    """One text per head: the head, then ``length`` words drawn uniformly
    from its topic's vocabulary."""
    n_base = len(BASE_WORDS)
    vocab = {}
    words = rng.integers(n_base, size=int(lengths.sum()))
    ends = np.cumsum(lengths)
    out = []
    for head, t, end, ln in zip(heads, topics.tolist(), ends.tolist(),
                                lengths.tolist()):
        v = vocab.get(t)
        if v is None:
            v = vocab[t] = np.array(topic_words(t), dtype=object)
        out.append(head + " " + " ".join(v[words[end - ln:end]]))
    return out


def make_corpus(cfg: dict, n_queries: int, seed: int,
                stream: int = 0) -> Corpus:
    """The configuration's corpus from ``seed``, and ``n_queries`` queries
    drawn from query stream ``stream`` (the window and the warm-up use
    different streams of one seed)."""
    rng = np.random.default_rng([seed, 0])
    n = int(cfg["passages"])
    n_topics = int(cfg["topics"])
    sizes = lognormal_sizes(n, n_topics, float(cfg["topic_size_sigma"]), rng)
    topic_of = np.repeat(np.arange(n_topics), sizes)
    lengths = normal_lengths(n, float(cfg["doc_words"]),
                             float(cfg["doc_words_cv"]), 1, rng)
    texts = _texts([f"doc-{i}" for i in range(n)], topic_of, lengths, rng)
    q_texts, q_topic = make_queries(cfg, sizes, n_queries, seed, stream)
    return Corpus(texts=texts, topic_of=topic_of, sizes=sizes,
                  query_texts=q_texts, query_topic=q_topic)


def make_queries(cfg: dict, sizes: np.ndarray, n: int, seed: int,
                 stream: int):
    """``n`` queries: Zipf topic ranks over topics by size, uniform word
    counts in the configured range, words from the topic's vocabulary."""
    rng = np.random.default_rng([seed, 1, stream])
    by_size = np.argsort(-sizes, kind="stable")
    topics = by_size[zipf_ranks(n, float(cfg["query_zipf_a"]), len(sizes),
                                rng)]
    lo, hi = cfg["query_words"]
    lengths = rng.permutation(
        np.round(lo + (hi - lo) * quantiles(n)).astype(np.int64))
    heads = [f"query-{stream}-{i}" for i in range(n)]
    return _texts(heads, topics, lengths, rng), topics
