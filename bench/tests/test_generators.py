"""The benchmark's own corpus and traffic generators: deterministic per
seed, and the schedule has the shape the cells promise."""

from __future__ import annotations

import numpy as np
import pytest

from bench.corpus import synthesize
from bench.reference import Reference, word_lemmas
from bench.traffic import WORDS, bursts_due, make_catalog, schedule

FIXED = {"kind": "fixed", "tokens": 40}
LOGNORMAL = {"kind": "lognormal", "mean": 120, "sigma": 0.6, "min": 16}
TRAFFIC = {"burst": 4, "catalog_seed": 5, "rate": 2.0, "class_weights": {"stop": 0.5, "frequent": 0.3, "ordinary": 0.2}}
SECONDS = 6.0


def _corpus(seed, length=FIXED, n_docs=120):
    return synthesize(n_docs=n_docs, vocab_size=800, zipf_a=1.2, phrase_rate=0.04, length=length, seed=seed)


@pytest.mark.parametrize("length", [FIXED, LOGNORMAL], ids=["fixed", "lognormal"])
def test_corpus_is_a_function_of_its_seed(length):
    a, b, c = _corpus(3, length), _corpus(3, length), _corpus(4, length)
    assert a.vocab == b.vocab
    assert np.array_equal(a.tokens, b.tokens) and np.array_equal(a.offsets, b.offsets)
    assert not np.array_equal(a.tokens[: len(c.tokens)], c.tokens[: len(a.tokens)])
    assert a.texts()[0] == "who are you is the album by the who"


def test_lognormal_lengths_keep_their_tail_and_spread():
    c = _corpus(1, LOGNORMAL, n_docs=400)
    lengths = c.doc_lengths()[2:]  # past the two paper examples
    assert lengths.min() >= LOGNORMAL["min"]
    assert lengths.std() > 20  # a distribution, not one length
    assert lengths.max() > 3 * LOGNORMAL["mean"]  # no cap cuts the longest documents
    assert abs(lengths.mean() - LOGNORMAL["mean"]) < 0.25 * LOGNORMAL["mean"]


def test_fixed_lengths_grow_only_by_injected_phrases():
    c = _corpus(2, FIXED)
    assert (c.doc_lengths()[2:] >= FIXED["tokens"]).all()


def test_corpus_round_trips_through_its_file(tmp_path):
    from bench.corpus import Corpus

    a = _corpus(6)
    a.save(tmp_path / "c.npz")
    b = Corpus.load(tmp_path / "c.npz")
    assert a.vocab == b.vocab and a.texts() == b.texts()


def _catalog(seed_corpus=3, traffic=TRAFFIC, n=None):
    c = _corpus(seed_corpus)
    ref = Reference(c, sw_count=20, fu_count=60, max_distance=5)
    return make_catalog(c, lambda w: ref.kind(word_lemmas(w)[0]), traffic, n or bursts_due(traffic, SECONDS))


def test_catalog_is_fixed_by_its_seed_and_distinct():
    a, b = _catalog(), _catalog()
    assert a == b
    assert len(a) == round(TRAFFIC["rate"] * SECONDS) and all(len(s) == TRAFFIC["burst"] for s in a)
    flat = [q for s in a for q in s]
    assert len(set(flat)) == len(flat)
    assert all(WORDS[0] <= len(q.split()) <= WORDS[1] for q in flat)
    assert _catalog(traffic=dict(TRAFFIC, catalog_seed=6)) != a


def test_a_shorter_catalog_is_a_prefix_of_a_longer_one():
    assert _catalog(n=30)[:12] == _catalog(n=12)


@pytest.mark.parametrize("seed", [0, 7, 2**40 + 3])
def test_schedule_is_a_function_of_its_seed(seed):
    cat = _catalog()
    assert schedule(cat, TRAFFIC, SECONDS, seed) == schedule(cat, TRAFFIC, SECONDS, seed)


def test_every_seed_gets_the_same_arrivals_and_bursts():
    cat = _catalog()
    a = schedule(cat, TRAFFIC, SECONDS, 1)
    b = schedule(cat, TRAFFIC, SECONDS, 2)
    assert len(a) == len(b) == len(cat)
    assert [(x.due, x.burst) for x in a] == [(x.due, x.burst) for x in b]
    assert [x.burst for x in a] == list(range(len(cat)))
    assert a[0].due == 0.0 and all(x.due < SECONDS for x in a)
    assert [x.queries for x in a] != [x.queries for x in b]
    assert all(sorted(x.queries) == sorted(y.queries) for x, y in zip(a, b))
    other = schedule(cat, dict(TRAFFIC, catalog_seed=99), SECONDS, 1)
    assert [x.due for x in other] != [x.due for x in a]


def test_schedule_carries_no_deadline():
    cat = _catalog()
    arrival = schedule(cat, TRAFFIC, SECONDS, 3)[0]
    assert set(vars(arrival)) == {"due", "burst", "queries"}
    assert sorted(arrival.queries) == sorted(cat[arrival.burst])


@pytest.mark.parametrize("seed", [0, 1, 2, 2**31 + 5])
def test_no_query_repeats_inside_a_window(seed):
    arrivals = schedule(_catalog(), TRAFFIC, SECONDS, seed)
    flat = [q for a in arrivals for q in a.queries]
    assert len(flat) == len(set(flat)) == bursts_due(TRAFFIC, SECONDS) * TRAFFIC["burst"]
