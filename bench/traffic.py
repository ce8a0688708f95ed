"""Traffic: the bursts of a cell's window and their open-loop arrival times.

A traffic mix is a JSON file of parameters (``bench/traffic/<name>.json``):

* ``burst``            requests that arrive together, as one client's fan-out;
  the daemon's batch limit equals it, so every batch is one burst;
* ``class_weights``    share of stop / frequently used / ordinary words;
* ``catalog_seed``     fixes the window's queries and arrival times;
* ``rate``             bursts per second, Poisson arrivals (see ``schedule``).

Queries are sampled from real document windows (so that their words occur
near each other), 2 to 4 words, each word of a class drawn with the mix's
weights, as in the program's own ``benchmarks/load.py`` mix, but from the
words of the text rather than from lemmas, so that a query carries the
multi-lemma forms a user types.

Why the queries are fixed per cell and not drawn per ``--seed``: the
program compiles a device program for every batch whose pow2 budgets or
family groups differ, which for fresh batches is nearly every batch, and
one program takes minutes to compile.  So the window's bursts are one
catalog, ``rate * seconds`` bursts of distinct queries, no query twice,
each burst served exactly once in a window, and all of their programs are
compiled in set-up.  The bursts arrive in catalog order at the times of
one Poisson draw, both fixed per cell, and ``--seed`` orders the queries
inside each burst: one burst costs a thousand times another, and a
seed-drawn order of bursts moved the median latency by 30-45% between
seeds, so the seed would have been changing the work.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

CLASSES = ("stop", "frequent", "ordinary")
WINDOW_WORDS = 10
WORDS = (2, 4)  # least and most words per query


@dataclass(frozen=True)
class Arrival:
    due: float  # seconds after the window opens
    burst: int  # index in the catalog
    queries: tuple[str, ...]


def bursts_due(traffic: dict, seconds: float) -> int:
    """Bursts that arrive in a window of ``seconds``."""
    return max(1, int(round(float(traffic["rate"]) * seconds)))


def make_catalog(corpus, kind_of, traffic: dict, n_bursts: int) -> list[list[str]]:
    """``n_bursts`` lists of ``burst`` queries, no query twice.  The draw is
    sequential, so a shorter catalog is a prefix of a longer one.
    ``kind_of(word)`` gives a word's class index (0 stop, 1 frequently
    used, 2 ordinary)."""
    rng = np.random.default_rng(int(traffic["catalog_seed"]))
    weights = np.asarray([traffic["class_weights"][c] for c in CLASSES], np.float64)
    weights /= weights.sum()
    lo, hi = WORDS
    need = int(n_bursts) * int(traffic["burst"])
    lengths = corpus.doc_lengths()
    long_docs = np.flatnonzero(lengths >= WINDOW_WORDS + 2)
    seen: set[str] = set()
    queries: list[str] = []
    while len(queries) < need:
        d = int(long_docs[rng.integers(len(long_docs))])
        start = int(rng.integers(0, lengths[d] - WINDOW_WORDS))
        window = corpus.doc_words(d)[start : start + WINDOW_WORDS]
        by_class = [[w for w in window if kind_of(w) == k] for k in range(3)]
        words = []
        for _ in range(int(rng.integers(lo, hi + 1))):
            k = int(rng.choice(3, p=weights))
            pool = by_class[k] or window
            words.append(pool[int(rng.integers(len(pool)))])
        q = " ".join(words)
        if q not in seen:
            seen.add(q)
            queries.append(q)
    b = int(traffic["burst"])
    return [queries[i : i + b] for i in range(0, need, b)]


def schedule(catalog: list[list[str]], traffic: dict, seconds: float, seed: int) -> list[Arrival]:
    """The window's arrivals: every burst of ``catalog`` once, in catalog
    order, at the times of one Poisson draw fixed by ``catalog_seed``
    (scaled to end inside the window); ``seed`` orders the queries inside
    each burst."""
    n = len(catalog)
    fixed = np.random.default_rng([int(traffic["catalog_seed"]), 1])
    gaps = fixed.exponential(1.0, size=n)
    times = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())
    rng = np.random.default_rng(seed)
    return [
        Arrival(due=float(t), burst=b, queries=tuple(catalog[b][j] for j in rng.permutation(len(catalog[b]))))
        for b, t in enumerate(times.tolist())
    ]
