"""The Combiner (SE2.4) and baselines vs their oracles, incl. the §13 trace."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.baselines import (
    se1_ordinary,
    se21_main_cell,
    se22_intermediate,
    se23_optimized,
    simple_key_cover,
)
from repro.core.combiner import CombinerState, se24_combiner
from repro.core.keys import Subquery, expand_subqueries, select_keys
from repro.core.lemma import Lemmatizer
from repro.core.oracle import key_events, oracle_search, sweep_events
from repro.index import DocumentStore, build_indexes

QUERIES = [
    "who are you who",
    "to be or not to be",
    "the time of war",
    "what do you do all day",
    "time and time again",
]


def _oracle(sub, keys, idx, honor_stars=True):
    post = {k: idx.key_postings(k.components) for k in keys}
    mult = sub.multiplicity()
    out = []
    for d, ev in sorted(key_events(keys, post, honor_stars=honor_stars).items()):
        out.extend(sweep_events(d, ev, mult, max_span=2 * idx.max_distance))
    return sorted(out)


@pytest.mark.parametrize("query", QUERIES)
def test_se24_matches_oracle(query, small_index, lemmatizer):
    for sub in expand_subqueries(query, lemmatizer)[:2]:
        keys = select_keys(sub, small_index.fl)
        expected = _oracle(sub, keys, small_index)
        got, stats = se24_combiner(sub, small_index)
        assert sorted(got) == expected
        assert stats.intermediate_records == 0  # the paper's selling point


@pytest.mark.parametrize("query", QUERIES)
def test_se23_matches_its_oracle(query, small_index, lemmatizer):
    for sub in expand_subqueries(query, lemmatizer)[:2]:
        keys = select_keys(sub, small_index.fl)
        expected = _oracle(sub, keys, small_index, honor_stars=False)
        got, stats = se23_optimized(sub, small_index)
        assert sorted(got) == expected
        assert stats.intermediate_records > 0  # it DOES materialize streams


@pytest.mark.parametrize("query", QUERIES)
def test_se22_matches_its_oracle(query, small_index, lemmatizer):
    for sub in expand_subqueries(query, lemmatizer)[:2]:
        keys = simple_key_cover(sub, small_index.fl)
        expected = _oracle(sub, keys, small_index)
        got, _ = se22_intermediate(sub, small_index)
        assert sorted(got) == expected


def test_se1_superset_of_se24(small_index, lemmatizer):
    """SE1 merges full ordinary posting lists: it can only find MORE."""
    for query in QUERIES:
        for sub in expand_subqueries(query, lemmatizer)[:1]:
            r1, s1 = se1_ordinary(sub, small_index)
            r24, s24 = se24_combiner(sub, small_index)
            assert set(r24) <= set(r1)
            if s24.postings_read and s1.postings_read:
                assert s24.postings_read <= s1.postings_read


def test_paper_trace_section_13():
    """§13 incremental example: MaxDistance=7, WindowSize=14, Start=4;
    query [who][i][need][you]; first emitted result must be (15, 21)."""
    sub = Subquery(("who", "i", "need", "you"))
    state = CombinerState(sub, window_size=14, max_distance=7)
    state.shift(4)
    # postings of key (i, need, who): (19, 20, 15) — Set all three
    state.set(19, "i")
    state.set(20, "need")
    state.set(15, "who")
    # postings of key (you, need*, who*): only the 'you' component Sets
    state.set(21, "you")
    state.set(21, "you")
    state.set(22, "you")
    state.set(22, "you")
    state.process_source(doc_id=0)  # flush buffer 0 -> (15, who)
    assert [r for r in state.results] == []
    state.switch()  # Start = 18
    state.process_source(doc_id=0)  # flush former buffer 1 -> 19,20,21,22
    assert state.results, "trace must emit a result"
    first = state.results[0]
    assert (first.start, first.end) == (15, 21)


def test_duplicate_lemma_multiplicity(small_index, lemmatizer):
    """'to be or not to be' requires two 'to' and two 'be' in a fragment."""
    sub = expand_subqueries("to be or not to be", lemmatizer)[0]
    results, _ = se24_combiner(sub, small_index)
    docs = {d.doc_id: d for d in []}
    for r in results:
        # reconstruct the fragment lemma counts from the corpus
        pass  # structural assertion below via the oracle equality test
    keys = select_keys(sub, small_index.fl)
    assert sorted(results) == _oracle(sub, keys, small_index)


# ---------------------------------------------------------------------------
# property: tight synthetic clusters are always found
# ---------------------------------------------------------------------------

WORDS = ["alpha", "beta", "gamma", "delta"]


@settings(max_examples=25, deadline=None)
@given(
    st.lists(st.integers(0, 3), min_size=3, max_size=4),  # query lemma ids
    st.integers(0, 1000),  # seed
)
def test_tight_clusters_always_found(query_ids, seed):
    """Documents whose query lemmas co-occur within MaxDistance/2 produce a
    key posting for every selected key, so SE2.4 == oracle exactly."""
    rng = np.random.default_rng(seed)
    query = [WORDS[i] for i in query_ids]
    texts = []
    for _ in range(6):
        filler = [f"x{rng.integers(20)}" for _ in range(30)]
        pos = int(rng.integers(5, 20))
        # inject the query words consecutively (distance < MaxDistance/2)
        doc = filler[:pos] + list(rng.permutation(query)) + filler[pos:]
        texts.append(" ".join(doc))
    store = DocumentStore.from_texts(texts)
    idx = build_indexes(store, sw_count=10_000, fu_count=0, max_distance=5)
    sub = Subquery(tuple(query))
    keys = select_keys(sub, idx.fl)
    post = {k: idx.key_postings(k.components) for k in keys}
    expected = oracle_search(sub, keys, post, idx.max_distance)
    got, _ = se24_combiner(sub, idx)
    assert sorted(got) == sorted(expected)
    assert len(got) >= 6  # every injected cluster found


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_random_corpus_se24_equals_oracle(seed):
    """On arbitrary Zipf corpora SE2.4 must equal its oracle (the Step-2
    gate may only skip records that share no reportable fragment with the
    other keys' records)."""
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(15)]
    probs = np.array([1 / (i + 1) ** 1.1 for i in range(15)])
    probs /= probs.sum()
    texts = [
        " ".join(rng.choice(vocab, size=60, p=probs)) for _ in range(8)
    ]
    store = DocumentStore.from_texts(texts)
    idx = build_indexes(store, sw_count=10_000, fu_count=0, max_distance=4)
    q = list(rng.choice(vocab[:6], size=3, replace=True))
    sub = Subquery(tuple(q))
    keys = select_keys(sub, idx.fl)
    post = {k: idx.key_postings(k.components) for k in keys}
    expected = oracle_search(sub, keys, post, idx.max_distance)
    got, _ = se24_combiner(sub, idx)
    assert sorted(got) == sorted(expected)


def test_se24_multi_lemma_position_counts_both_lemmas():
    """Regression (PR 3): a §2 multi-lemma word ("are" -> are, be) satisfies
    TWO subquery lemmas at one position.  The verbatim §10.3 Set-overwrite
    dropped one of them, so SE2.4 missed the minimal fragment whose "be" is
    supplied by the word "are" and emitted a longer stale-start fragment
    instead — diverging from the oracle (and the device engines, which were
    already event-exact).  Pins the atomic-position lemma-set fix."""
    lem = Lemmatizer()
    texts = [
        # positions:  0  1   2   3   4
        "when be of to who are you who",
        # the minimal fragment for [to be who you are] is [3..6]:
        # to(3) who(4) are+be(5) you(6) — "be" comes from the word "are"
    ]
    store = DocumentStore.from_texts(texts, lemmatizer=lem)
    idx = build_indexes(store, sw_count=30, fu_count=30, max_distance=5)
    sub = expand_subqueries("to be who you are", lem)[0]
    assert sub.lemmas == ("to", "be", "who", "you", "are")
    keys = select_keys(sub, idx.fl)
    expected = _oracle(sub, keys, idx)
    got, _ = se24_combiner(sub, idx)
    assert sorted(got) == expected
    frags = {(r.doc_id, r.start, r.end) for r in got}
    assert (0, 3, 6) in frags, "the multi-lemma-position minimal fragment"


def test_se24_step2_gate_covers_key_reach():
    """Regression: Step 2 compares the keys' anchor positions, while a key
    record's components reach MaxDistance either side of its anchor.  Here
    (D=4) the fragment w1(32) w4(33) w5(34) w3(40) has span 2D; its events
    come from a (w1, w4, w5) record anchored at 32 and a (w3, w4*, w5*)
    record anchored at 40.  A gate of ``delta < 2D`` skipped both records
    and lost the fragment the oracle and the device engines report."""
    rng = np.random.default_rng(5575)
    vocab = [f"w{i}" for i in range(15)]
    probs = np.array([1 / (i + 1) ** 1.1 for i in range(15)])
    probs /= probs.sum()
    texts = [" ".join(rng.choice(vocab, size=60, p=probs)) for _ in range(8)]
    idx = build_indexes(
        DocumentStore.from_texts(texts), sw_count=10_000, fu_count=0, max_distance=4
    )
    sub = Subquery(("w3", "w4", "w1", "w5"))
    keys = select_keys(sub, idx.fl)
    post = {k: idx.key_postings(k.components) for k in keys}
    got, _ = se24_combiner(sub, idx)
    assert sorted(got) == sorted(oracle_search(sub, keys, post, idx.max_distance))
    assert (2, 32, 40) in {(r.doc_id, r.start, r.end) for r in got}
