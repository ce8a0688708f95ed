"""The plain reference against the program, at small sizes on the CPU.

The reference shares no code with the program; these tests are where the
two meet.  Responses must agree exactly (documents, scores, fragments) on
corpora of both length kinds and several corpus seeds, and the control
(the reference with MaxDistance one less) must not.
"""

from __future__ import annotations

import itertools

import pytest

from bench.corpus import synthesize
from bench.reference import Reference, select_keys, word_lemmas
from bench.traffic import make_catalog

SW, FU, MD = 40, 100, 5
PAPER_QUERIES = ["to be or not to be", "who are you who", "to be who you are", "i need you",
                 "time and time again", "who is who"]


def _stack(tmp_path, seed, length, n_docs):
    from repro.index.corpus import DocumentStore
    from repro.search.distributed import ShardedSearchService
    from repro.search.frontend import ServingFrontend

    corpus = synthesize(n_docs=n_docs, vocab_size=1200, zipf_a=1.2, phrase_rate=0.04, length=length, seed=seed)
    svc, _ = ShardedSearchService.bulk_ingest(
        DocumentStore.from_texts(corpus.texts()), tmp_path / "ix", n_shards=4, sw_count=SW,
        fu_count=FU, max_distance=MD, workers=1,
    )
    return corpus, ServingFrontend(svc, max_batch=16, arena_budget_mb=128)


def _queries(corpus, ref, seed):
    mixes = [{"stop": 0.5, "frequent": 0.3, "ordinary": 0.2}, {"stop": 0.2, "frequent": 0.3, "ordinary": 0.5}]
    out = list(PAPER_QUERIES)
    for k, w in enumerate(mixes):
        traffic = {"catalog_seed": seed * 10 + k, "class_weights": w, "burst": 10}
        out += [q for s in make_catalog(corpus, lambda x: ref.kind(word_lemmas(x)[0]), traffic, 6) for q in s]
    return out


def _ranking(resp):
    return [(d.doc_id, d.score, [(f.start, f.end) for f in d.fragments]) for d in resp.docs]


CORPORA = [
    (0, {"kind": "fixed", "tokens": 40}, 240),
    (1, {"kind": "fixed", "tokens": 60}, 200),
    (2, {"kind": "lognormal", "mean": 150, "sigma": 0.6, "min": 16}, 60),
]


@pytest.fixture(scope="module", params=CORPORA, ids=["passages-seed0", "passages-seed1", "articles-seed2"])
def served(request, tmp_path_factory):
    seed, length, n_docs = request.param
    corpus, front = _stack(tmp_path_factory.mktemp("ix"), seed, length, n_docs)
    ref = Reference(corpus, sw_count=SW, fu_count=FU, max_distance=MD)
    yield ref, front, _queries(corpus, ref, seed)
    front.close()


@pytest.mark.parametrize("top_k", [10, 100000], ids=["top10", "all"])
def test_reference_equals_the_program(served, top_k):
    from repro.search.frontend import SearchRequest

    ref, front, queries = served
    responses = []
    for lo in range(0, len(queries), 16):
        responses += front.search_many([SearchRequest(q, top_k=top_k) for q in queries[lo : lo + 16]])
    answered = [q for q, r in zip(queries, responses) if r.docs]
    assert len(answered) >= 10, "the mix must exercise non-empty answers"
    for q, r in zip(queries, responses):
        assert _ranking(r) == ref.answer(q, top_k), q


def test_control_with_a_narrower_window_differs(tmp_path):
    corpus = synthesize(n_docs=240, vocab_size=1200, zipf_a=1.2, phrase_rate=0.04,
                        length={"kind": "fixed", "tokens": 40}, seed=0)
    ref = Reference(corpus, sw_count=SW, fu_count=FU, max_distance=MD)
    narrow = Reference(corpus, sw_count=SW, fu_count=FU, max_distance=MD - 1)
    queries = _queries(corpus, ref, 0)
    differ = sum(ref.answer(q, 10) != narrow.answer(q, 10) for q in queries)
    assert differ >= 5


def test_multi_lemma_words_expand_into_subqueries():
    corpus = synthesize(n_docs=20, vocab_size=200, zipf_a=1.2, phrase_rate=0.2,
                        length={"kind": "fixed", "tokens": 30}, seed=0)
    ref = Reference(corpus, sw_count=SW, fu_count=FU, max_distance=MD)
    assert ref.subqueries("who are you") == [("who", "are", "you"), ("who", "be", "you")]
    assert word_lemmas("running") == ("run",) and word_lemmas("your") == ("you", "your")


def test_key_selection_matches_the_program():
    """The reference's own key cover against the program's, over every
    short subquery of a small lemma alphabet."""
    from repro.core.keys import Subquery
    from repro.core.keys import select_keys as program_select
    from repro.core.lemma import FLList

    freq = {"a": 90, "b": 80, "c": 70, "d": 20, "e": 10}
    fl = FLList.from_frequencies(freq, sw_count=2, fu_count=2)
    for n in range(1, 5):
        for lemmas in itertools.product("abcde", repeat=n):
            want = [(k.components, k.starred) for k in program_select(Subquery(lemmas), fl)]
            assert select_keys(lemmas, fl.number) == want, lemmas
