"""§15 device-side result assembly, phase timers and pipelined dispatch.

Pins the contracts the DESIGN.md §15 refactor introduced:

* **Readout equivalence** — ``readout="device"`` (one fixed-shape D2H copy
  of the §15.1 dense result buffer) returns byte-identical fragments, in
  identical order, to the legacy ``readout="host"`` ``np.nonzero`` + dedup
  path, on the fused AND arena serving paths.
* **Two-tier host dedup** — ``_dedup_fragments`` gives identical output on
  its packed-int64 fast tier and its lexsort fallback, and picks the
  fallback (instead of silently overflowing) when the packed key cannot
  hold the value ranges.
* **Phase-timer schema** — one instrumented batch produces exactly the six
  §15.3 phase spans, each non-negative and summing to at most the serial
  batch wall time (no double-counting); clearing the sink removes the
  span recorder.
* **Deferred dispatch** — ``defer=True`` returns a ``PendingBatch`` whose
  idempotent ``result()`` equals the eager call's result.
* **Pipelined frontend** — the §15.2 two-deep driver returns byte-identical
  responses, in admission order, to the serial submit→finish loop.
"""

import time

import numpy as np
import pytest

from repro.core.keys import expand_subqueries
from repro.core.lemma import Lemmatizer
from repro.index import build_indexes, synthesize_corpus
from repro.search import fused
from repro.search.fused import PendingBatch, _dedup_fragments, serve_query_batch

QUERIES = [
    "who are you who",
    "to be or not to be",
    "what do you do all day",
    "the time of war",
    "i need you",
    "time and time again",
]

PHASE_KEYS = {
    "plan_us", "pack_us", "h2d_us", "dispatch_us", "compute_us", "readout_us",
}


@pytest.fixture(scope="module")
def corpus():
    store = synthesize_corpus(n_docs=60, doc_len=120, vocab_size=500, seed=7)
    idx = build_indexes(store, sw_count=60, fu_count=120, max_distance=5)
    lem = Lemmatizer()
    work = [
        [(sub, idx) for sub in expand_subqueries(q, lem)] for q in QUERIES
    ]
    return store, idx, work


def _result_key(res):
    """Everything a FusedBatchResult exposes, materialized for comparison."""
    return (
        [sorted(p) for p in res.per_query],
        res.top_docs.tolist(),
        np.asarray(res.top_scores).round(6).tolist(),
        res.n_fragments.tolist(),
    )


# ---------------------------------------------------------------------------
# device readout == host readout (fused and arena paths)
# ---------------------------------------------------------------------------


def test_device_readout_equals_host_fused(corpus):
    _, idx, work = corpus
    dev = serve_query_batch(work, max_distance=idx.max_distance, readout="device")
    host = serve_query_batch(work, max_distance=idx.max_distance, readout="host")
    assert _result_key(dev) == _result_key(host)
    # §15.1 buffer order: compacted rows come back sorted, already unique
    for qi in range(dev.n_queries):
        frs = dev.per_query[qi]
        assert frs == sorted(set(frs))
        assert dev.n_results(qi) == len(frs)


def test_device_readout_equals_host_arena(corpus):
    from repro.search.arena import PostingArena

    _, idx, work = corpus
    arena = PostingArena(budget_bytes=1 << 30)
    res = arena.acquire(idx, 0)
    residencies = {id(idx): res}
    try:
        got = {
            mode: serve_query_batch(
                work,
                max_distance=idx.max_distance,
                residencies=residencies,
                readout=mode,
            )
            for mode in ("device", "host")
        }
        assert _result_key(got["device"]) == _result_key(got["host"])
    finally:
        arena.release()


def test_unknown_readout_mode_rejected(corpus):
    _, idx, work = corpus
    with pytest.raises(ValueError, match="readout"):
        serve_query_batch(work, max_distance=idx.max_distance, readout="dma")


# ---------------------------------------------------------------------------
# _dedup_fragments: packed fast tier == lexsort fallback, overflow-safe
# ---------------------------------------------------------------------------


def _dedup_reference(q, d, s, e):
    uniq = sorted(set(zip(q, d, s, e)))
    cols = list(zip(*uniq)) if uniq else [[], [], [], []]
    return [list(c) for c in cols]


def test_dedup_fragments_packed_tier_matches_reference():
    rng = np.random.default_rng(3)
    q = rng.integers(0, 7, 200).astype(np.int64)
    d = rng.integers(0, 50, 200).astype(np.int64)
    s = rng.integers(0, 30, 200).astype(np.int64)
    e = s + rng.integers(0, 5, 200).astype(np.int64)
    got = [c.tolist() for c in _dedup_fragments(q, d, s, e)]
    assert got == _dedup_reference(q.tolist(), d.tolist(), s.tolist(), e.tolist())


def test_dedup_fragments_lexsort_tier_on_overflow():
    # doc ids near 2^58: q*doc*n*n no longer fits 63 bits, so the packed
    # tier must NOT be used — the fallback still dedups exactly
    q = np.array([1, 0, 1, 1, 0], np.int64)
    d = np.array([1 << 58, (1 << 58) + 3, 1 << 58, 1 << 58, (1 << 58) + 3], np.int64)
    s = np.array([5, 2, 5, 7, 2], np.int64)
    e = np.array([9, 4, 9, 8, 4], np.int64)
    mods = [int(c.max()) + 1 for c in (q, d, s, e)]
    assert (mods[0] * mods[1] * mods[2] * mods[3] - 1).bit_length() > 63
    got = [c.tolist() for c in _dedup_fragments(q, d, s, e)]
    assert got == _dedup_reference(q.tolist(), d.tolist(), s.tolist(), e.tolist())


def test_dedup_fragments_empty():
    empty = np.empty(0, np.int64)
    got = _dedup_fragments(empty, empty, empty, empty)
    assert all(len(c) == 0 for c in got)


# ---------------------------------------------------------------------------
# §15.3 phase-timer schema: six disjoint brackets, no double-counting
# ---------------------------------------------------------------------------


def test_phase_schema_and_no_double_counting(corpus):
    _, idx, work = corpus
    serve_query_batch(work, max_distance=idx.max_distance)  # jit warm
    phases: dict = {}
    prev = fused.collect_phases(phases)
    t0 = time.perf_counter()
    serve_query_batch(work, max_distance=idx.max_distance)
    wall = time.perf_counter() - t0
    fused.collect_phases(prev)
    assert set(phases) == PHASE_KEYS
    assert all(us >= 0.0 for v in phases.values() for us in v)
    # disjoint brackets: the phase sum cannot exceed the measured wall time
    # (equality up to the unbracketed merge/return tail)
    assert sum(sum(v) for v in phases.values()) <= wall * 1e6 + 1.0


def test_clearing_the_phase_sink_leaves_no_recorder(corpus):
    from repro.runtime import spans

    _, idx, work = corpus
    phases: dict = {}
    assert fused.collect_phases(phases) is None
    assert fused.collect_phases(None) is phases
    serve_query_batch(work, max_distance=idx.max_distance)
    assert phases == {}
    assert spans.set_recorder(None) is None


# ---------------------------------------------------------------------------
# defer=True: PendingBatch equals the eager result, result() is idempotent
# ---------------------------------------------------------------------------


def test_deferred_serve_equals_eager(corpus):
    _, idx, work = corpus
    eager = serve_query_batch(work, max_distance=idx.max_distance)
    pending = serve_query_batch(work, max_distance=idx.max_distance, defer=True)
    assert isinstance(pending, PendingBatch)
    got = pending.result()
    assert _result_key(got) == _result_key(eager)
    assert pending.result() is got  # idempotent: no re-finalize


# ---------------------------------------------------------------------------
# §15.2 pipelined frontend: identical responses, admission order preserved
# ---------------------------------------------------------------------------


def test_pipelined_frontend_matches_serial_in_admission_order(corpus):
    from repro.search.frontend import SearchRequest, ServingFrontend

    store, idx, _ = corpus
    requests = [SearchRequest(q, top_k=16) for q in QUERIES]

    def run(pipeline):
        fe = ServingFrontend(
            idx, lemmatizer=store.lemmatizer, max_batch=2, pipeline=pipeline
        )
        return fe.search_many(requests)

    serial, piped = run(False), run(True)
    assert [r.query for r in piped] == [rq.query for rq in requests]
    for a, b in zip(serial, piped):
        assert a.query == b.query
        assert [
            (d.doc_id, d.score, [(f.start, f.end) for f in d.fragments])
            for d in a.docs
        ] == [
            (d.doc_id, d.score, [(f.start, f.end) for f in d.fragments])
            for d in b.docs
        ]
