"""Device-resident posting arena (DESIGN.md §13): exact fragment equality
with the host-pack path and the se2.4 oracle, transparent fallback under
budget-forced partial residency, generation-keyed invalidation, the Pallas
gather kernel vs its jnp form, descriptor-only host planning, recompile
churn, and the new QueryStats arena counters."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.core.combiner import se24_combiner
from repro.core.keys import Subquery, expand_subqueries, select_keys
from repro.core.postings import QueryStats
from repro.index import DocumentStore, build_indexes, synthesize_corpus
from repro.index.corpus import Document
from repro.index.incremental import IncrementalIndexer, generation_token
from repro.kernels.gather import ARENA_BLOCK, gather_blocks, gather_blocks_ref
from repro.search import fused
from repro.search.arena import (
    PostingArena,
    _device_args,
    _static_kwargs,
    arena_serve_batch,
    plan_arena_batch,
    run_arena_batch,
)
from repro.search.distributed import ShardedSearchService
from repro.search.engine import SearchEngine
from repro.search.frontend import ServingFrontend
from repro.search.vectorized import VectorizedEngine

QUERIES = [
    "who are you who",
    "to be or not to be",
    "what do you do all day",
    "the time of war",
    "i need you",
]


def _residency(idx, arena=None):
    arena = arena or PostingArena()
    return arena, {id(idx): arena.acquire(idx, generation_token(idx))}


def _work(queries, idx, lemmatizer):
    return [[(sub, idx) for sub in expand_subqueries(q, lemmatizer)] for q in queries]


# ---------------------------------------------------------------------------
# the gather kernel: Pallas form == jnp form, exact masking
# ---------------------------------------------------------------------------


def test_gather_blocks_kernel_equals_ref():
    rng = np.random.default_rng(0)
    # field-major arena buffer: [2, rows] (doc row over pos row)
    arena = jnp.asarray(rng.integers(0, 1000, (2, 8 * ARENA_BLOCK)).astype(np.int32))
    src = jnp.asarray(np.array([3, 0, 7, 7], np.int32))
    nv = jnp.asarray(np.array([ARENA_BLOCK, 5, 0, 128], np.int32))
    k = np.asarray(gather_blocks(arena, src, nv))
    r = np.asarray(gather_blocks_ref(arena, src, nv))
    np.testing.assert_array_equal(k, r)
    # masking: rows past n_valid are the -1 sentinel, live rows are copies
    np.testing.assert_array_equal(
        k[:, :ARENA_BLOCK], np.asarray(arena)[:, 3 * ARENA_BLOCK : 4 * ARENA_BLOCK]
    )
    np.testing.assert_array_equal(
        k[:, ARENA_BLOCK : ARENA_BLOCK + 5], np.asarray(arena)[:, :5]
    )
    assert (k[:, ARENA_BLOCK + 5 : 2 * ARENA_BLOCK] == -1).all()
    assert (k[:, 2 * ARENA_BLOCK : 3 * ARENA_BLOCK] == -1).all()


# ---------------------------------------------------------------------------
# exact fragment equality: arena == host pack == se2.4 oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernel", [False, True])
def test_arena_equals_host_pack_and_oracle(small_index, lemmatizer, use_kernel):
    work = _work(QUERIES, small_index, lemmatizer)
    host = fused.serve_query_batch(work, max_distance=small_index.max_distance)
    _, res = _residency(small_index)
    fused.reset_dispatch_count()
    got = fused.serve_query_batch(
        work,
        max_distance=small_index.max_distance,
        residencies=res,
        use_kernel=use_kernel,
    )
    assert fused.dispatch_count() == 1, "fully resident batch = ONE dispatch"
    for qi, (subs, frags) in enumerate(zip(work, got.per_query)):
        assert set(frags) == set(host.per_query[qi])
        oracle = set()
        for sub, _ in subs:
            r, _ = se24_combiner(sub, small_index)
            oracle.update(r)
        assert set(frags) == oracle


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_arena_random_corpora_random_subqueries(seed):
    """Random Zipf corpora + duplicate-lemma subqueries: the arena program's
    on-device dedup/Step-1/Step-2/cover reproduce the scalar Combiner."""
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(15)]
    probs = np.array([1 / (i + 1) ** 1.1 for i in range(15)])
    probs /= probs.sum()
    texts = [" ".join(rng.choice(vocab, size=60, p=probs)) for _ in range(8)]
    store = DocumentStore.from_texts(texts)
    idx = build_indexes(store, sw_count=10_000, fu_count=0, max_distance=4)
    subs = [
        Subquery(tuple(rng.choice(vocab[:6], size=int(rng.integers(1, 5)), replace=True)))
        for _ in range(3)
    ]
    _, res = _residency(idx)
    got = fused.serve_query_batch(
        [[(s, idx)] for s in subs], max_distance=4, residencies=res
    )
    for sub, frags in zip(subs, got.per_query):
        expected, _ = se24_combiner(sub, idx)
        assert set(frags) == set(expected)


def test_vectorized_engine_arena_equals_plain(small_index, lemmatizer):
    batch = [expand_subqueries(q, lemmatizer) for q in QUERIES]
    plain = VectorizedEngine(small_index)
    arena_eng = VectorizedEngine(small_index, arena=PostingArena())
    r0, _ = plain.search_query_batch(batch)
    r1, s1 = arena_eng.search_query_batch(batch)
    for a, b in zip(r0.per_query, r1.per_query):
        assert set(a) == set(b)
    assert s1.device_dispatches == 1


def test_sharded_service_arena_with_dead_shards(small_corpus):
    svc_a = ShardedSearchService(
        small_corpus, n_shards=4, sw_count=60, fu_count=150,
        algorithm="fused", arena=PostingArena(),
    )
    svc_h = ShardedSearchService(
        small_corpus, n_shards=4, sw_count=60, fu_count=150, algorithm="fused"
    )
    for dead in ((), (1,), (0, 3)):
        fused.reset_dispatch_count()
        ra = svc_a.search_batch(QUERIES[:3], top_k=32, dead_shards=dead)
        assert fused.dispatch_count() == 1
        rh = svc_h.search_batch(QUERIES[:3], top_k=32, dead_shards=dead)
        for a, h in zip(ra, rh):
            fa = {(d.doc_id, f.start, f.end) for d in a.docs for f in d.fragments}
            fh = {(d.doc_id, f.start, f.end) for d in h.docs for f in d.fragments}
            assert fa == fh


# ---------------------------------------------------------------------------
# stage 3, the Step-2 gate and the rank cover: against a plain recount over
# the program's own sorted stream, the host pack and the oracle
# ---------------------------------------------------------------------------

_COVER_TEXTS = [
    "to be or not to be that is the question",
    "to be or not",  # every key of "to be or not to be", each lemma once
    "who are you who are you to be",  # "are": lemmas are + be at one position
    "you are who you are and to be is to be",
    "to you who are",  # "are" stands for be and are, and ends a fragment
    "the time of war",  # the corpus's only "war"
]

# (queries, lemma_budget, what the batch must contain)
_COVER_CASES = {
    "multiplicity": (["to be or not to be"], 4, ("mult_2", "step2_fails")),
    "multi_lemma_position": (["to be who you are"], 8, ("two_lemmas_one_pos",)),
    "budget_2": (["to be to be"], 2, ("mult_2",)),
    "single_event": (["war"], 2, ("single_event",)),
}


def _recount_cover(out: dict, plan, max_distance: int) -> tuple:
    """Stage 3 of the arena program recounted in plain Python over its own
    (row, pos, lemma)-sorted ``comp`` stream: per-row lemma counts gate
    Step-2, then every unique event of a passing row takes, per active
    lemma, the mult-th latest position at or before its own.  Returns the
    expected ``emit`` and ``start`` and the facts the batch exhibits."""
    nb = (plan.n_budget - 1).bit_length()
    lb = max((plan.lemma_budget - 1).bit_length(), 1)
    comp = out["comp"].astype(np.int64)
    fin = comp < np.iinfo(np.int32).max
    uniq = fin & np.r_[True, comp[1:] != comp[:-1]]
    pp = comp >> lb
    primary = fin & np.r_[True, pp[1:] != pp[:-1]]
    row, pos, lem = comp >> (lb + nb), pp & (plan.n_budget - 1), comp & (plan.lemma_budget - 1)
    # one work item per query: a row's segment is its query's
    seg_of = {int(q): s for s, q in enumerate(plan.seg_query) if q >= 0}
    occ: dict = {}  # (row, lemma) -> positions, ascending
    lemmas_at: dict = {}  # (row, pos) -> lemma slots
    for r, p, l in zip(row[uniq].tolist(), pos[uniq].tolist(), lem[uniq].tolist()):
        occ.setdefault((r, l), []).append(p)
        lemmas_at.setdefault((r, p), set()).add(l)
    mult = {r: plan.mult[seg_of[int(out["row_query"][r])]] for r in set(row[fin].tolist())}
    passes = {
        r: all(len(occ.get((r, l), ())) >= m for l, m in enumerate(m_r))
        for r, m_r in mult.items()
    }
    emit = np.zeros(comp.shape, bool)
    start = pos.copy()
    for i in np.flatnonzero(uniq):
        r, p = int(row[i]), int(pos[i])
        if not passes[r]:
            continue
        firsts = []
        for l, m in enumerate(mult[r].tolist()):
            if m == 0:
                continue
            before = [q for q in occ.get((r, l), ()) if q <= p]
            if len(before) < m:
                break
            firsts.append(before[-m])
        else:
            if firsts and p - min(firsts) <= 2 * max_distance:
                emit[i], start[i] = True, min(firsts)
    facts = {
        "mult_2": int(plan.mult.max()) >= 2,
        "step2_fails": not all(passes.values()),
        "two_lemmas_one_pos": any(len(ls) > 1 for ls in lemmas_at.values()),
        "single_event": int(fin.sum()) == 1,
    }
    return emit & primary, start.astype(np.int32), facts


@pytest.mark.parametrize("tier", ["pack32", "argsort"])
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("case", sorted(_COVER_CASES))
def test_arena_rank_cover_exact(lemmatizer, case, use_kernel, tier):
    """The stage-3 prefix counts give the exact §9.3 cover: duplicate query
    lemmas, two lemma slots at one position, rows that pass Step-1 and fail
    Step-2, a one-event batch, lemma budgets 2 to 8, in both sort tiers
    (wide doc ids force ``argsort``) and both gather forms."""
    queries, lemma_budget, facts_needed = _COVER_CASES[case]
    stride = 1 << 19 if tier == "argsort" else 1
    store = DocumentStore.from_documents(
        [
            Document(doc_id=i * stride + 3, text=t, lemma_stream=lemmatizer.lemmatize_text(t))
            for i, t in enumerate(_COVER_TEXTS)
        ],
        lemmatizer,
    )
    idx = build_indexes(store, sw_count=10_000, fu_count=0, max_distance=3)
    subs = [s for q in queries for s in expand_subqueries(q, lemmatizer)]
    _, res = _residency(idx)
    res = res[id(idx)]
    items = []
    for qi, sub in enumerate(subs):
        keys = select_keys(sub, idx.fl)
        items.append((qi, sub, keys, [res.lookup(k.components) for k in keys], res))
    plan = plan_arena_batch(items, n_queries=len(subs))
    assert (plan.tier, plan.lemma_budget) == (tier, lemma_budget)

    args, _ = _device_args(plan, use_kernel)
    out = arena_serve_batch(
        *args, **_static_kwargs(plan, max_distance=3, top_k=16, use_kernel=use_kernel)
    )
    out = {k: np.asarray(v) for k, v in out.items()}
    emit, start, facts = _recount_cover(out, plan, max_distance=3)
    assert all(facts[f] for f in facts_needed), facts
    np.testing.assert_array_equal(out["emit"], emit)
    np.testing.assert_array_equal(out["start"], start)

    got = run_arena_batch(plan, max_distance=3, top_k=16, use_kernel=use_kernel)
    host = fused.serve_query_batch([[(s, idx)] for s in subs], max_distance=3, top_k=16)
    np.testing.assert_array_equal(got.n_fragments, host.n_fragments)
    # the top-k width is min(top_k, row budget), which differs between paths
    k = min(got.top_docs.shape[1], host.top_docs.shape[1])
    for r in (got, host):
        assert (r.top_docs[:, k:] == -1).all() and np.isneginf(r.top_scores[:, k:]).all()
    np.testing.assert_array_equal(got.top_docs[:, :k], host.top_docs[:, :k])
    np.testing.assert_allclose(got.top_scores[:, :k], host.top_scores[:, :k], rtol=1e-6)
    for qi, sub in enumerate(subs):
        expected, _ = se24_combiner(sub, idx)
        assert set(got.per_query[qi]) == set(host.per_query[qi]) == set(expected)
    assert sum(map(len, got.per_query)) > 0


# ---------------------------------------------------------------------------
# descriptor planning: no posting reads, provably-empty short-circuits
# ---------------------------------------------------------------------------


def test_arena_plan_is_descriptor_only(small_index, lemmatizer):
    """Arena planning must not touch posting data: stats count the same
    §11 postings the host pack reads, but from upload-time extents."""
    arena, res = _residency(small_index)
    work = _work(QUERIES[:2], small_index, lemmatizer)
    host_stats = QueryStats()
    fused.plan_query_batch(work, stats=host_stats)
    arena_stats = QueryStats()
    fused.serve_query_batch(
        work,
        max_distance=small_index.max_distance,
        residencies=res,
        stats=arena_stats,
    )
    assert arena_stats.postings_read == host_stats.postings_read
    assert arena_stats.bytes_read == host_stats.bytes_read
    assert arena_stats.arena_hits > 0
    assert arena_stats.arena_misses == 0


def test_arena_empty_subquery_short_circuits(small_index):
    arena, res = _residency(small_index)
    stats = QueryStats()
    fused.reset_dispatch_count()
    got = fused.serve_query_batch(
        [[(Subquery(("zzzunknown", "qqqmissing")), small_index)]],
        max_distance=small_index.max_distance,
        residencies=res,
        stats=stats,
    )
    assert got.per_query == [[]]
    assert fused.dispatch_count() == 0
    assert stats.empty_subqueries == 1


def test_arena_stats_fields_merge():
    a, b = QueryStats(), QueryStats()
    a.arena_hits, a.arena_misses, a.h2d_bytes = 2, 1, 100
    b.arena_hits, b.arena_misses, b.h2d_bytes = 3, 4, 50
    a.merge(b)
    assert (a.arena_hits, a.arena_misses, a.h2d_bytes) == (5, 5, 150)


# ---------------------------------------------------------------------------
# residency: LRU budget, partial fallback, generation invalidation
# ---------------------------------------------------------------------------


def test_budget_forced_partial_residency_still_exact(small_index, lemmatizer):
    """A budget too small for every family leaves some non-resident; their
    work items fall back to the host pack and fragments stay identical."""
    work = _work(QUERIES, small_index, lemmatizer)
    host = fused.serve_query_batch(work, max_distance=small_index.max_distance)
    full = PostingArena()
    full.acquire(small_index, 0)
    sizes = sorted(fb.nbytes for fb in full._entries.values())
    # room for roughly half the families
    arena = PostingArena(budget_bytes=sum(sizes[:2]) + 1)
    res = {id(small_index): arena.acquire(small_index, 0)}
    assert 0 < len(arena) < 4, "budget must force PARTIAL residency"
    stats = QueryStats()
    got = fused.serve_query_batch(
        work,
        max_distance=small_index.max_distance,
        residencies=res,
        stats=stats,
    )
    for a, b in zip(got.per_query, host.per_query):
        assert set(a) == set(b)
    assert stats.arena_misses > 0, "non-resident keys must fall back"


def test_generation_bump_evicts_stale_buffers(lemmatizer):
    ix = IncrementalIndexer(sw_count=30, fu_count=60, max_distance=5,
                            lemmatizer=lemmatizer)
    ix.add_documents(["who are you who and what do you do", "to be or not to be"])
    ix.commit()
    arena = PostingArena()
    arena.attach(ix)
    arena.acquire(ix.index, ix.generation_token)
    assert len(arena) == 4
    tok0 = ix.generation_token
    ix.add_documents(["the time of war and the world of war"])
    ix.commit()  # mutation hook fires: stale-token entries evicted eagerly
    assert len(arena) == 0
    assert arena.evictions == 4
    assert ix.generation_token != tok0
    # re-acquiring under the new token serves the NEW live view exactly
    res = {id(ix.index): arena.acquire(ix.index, ix.generation_token)}
    work = _work(QUERIES[:2], ix.index, lemmatizer)
    got = fused.serve_query_batch(work, max_distance=5, residencies=res)
    host = fused.serve_query_batch(work, max_distance=5)
    for a, b in zip(got.per_query, host.per_query):
        assert set(a) == set(b)


def test_frontend_arena_equals_plain_after_mutations(lemmatizer):
    store = synthesize_corpus(n_docs=40, doc_len=80, vocab_size=500, seed=3)
    ix = IncrementalIndexer(sw_count=60, fu_count=120, max_distance=5,
                            lemmatizer=store.lemmatizer)
    ix.add_documents([d.text for d in store.documents[:20]])
    ix.commit()
    fa = ServingFrontend(ix, lemmatizer=store.lemmatizer, arena_budget_mb=256)
    fh = ServingFrontend(ix, lemmatizer=store.lemmatizer)

    def frag_set(resp):
        return {(d.doc_id, f.start, f.end) for d in resp.docs for f in d.fragments}

    for q in QUERIES[:3]:
        assert frag_set(fa.search(q, top_k=32)) == frag_set(fh.search(q, top_k=32))
    ix.add_documents([d.text for d in store.documents[20:]])
    ix.commit()
    ix.delete_document(sorted(ix.documents)[0])
    for q in QUERIES[:3]:
        assert frag_set(fa.search(q, top_k=32)) == frag_set(fh.search(q, top_k=32))
    ix.compact()
    for q in QUERIES[:3]:
        assert frag_set(fa.search(q, top_k=32)) == frag_set(fh.search(q, top_k=32))
    m = fa.metrics()
    assert m["arena_entries"] > 0
    assert m["arena_hits"] > 0


def test_overflow_falls_back_without_double_counting(lemmatizer):
    """Doc ids beyond the int32 composite budget raise ArenaOverflow at
    plan time; the batch must fall back to the host pack with fragments
    intact and the §11 postings accounting charged exactly ONCE."""
    ix = IncrementalIndexer(sw_count=30, fu_count=60, max_distance=5,
                            lemmatizer=lemmatizer)
    ix.add_documents(
        ["who are you who and what do you do", "to be or not to be"],
        doc_ids=[7, 2**28],  # wide doc-id space: composite bits overflow
    )
    ix.commit()
    view = ix.index
    work = _work(QUERIES[:2], view, lemmatizer)
    host_stats = QueryStats()
    host = fused.serve_query_batch(work, max_distance=5, stats=host_stats)
    arena = PostingArena()
    res = {id(view): arena.acquire(view, ix.generation_token)}
    stats = QueryStats()
    got = fused.serve_query_batch(
        work, max_distance=5, residencies=res, stats=stats
    )
    for a, b in zip(got.per_query, host.per_query):
        assert set(a) == set(b)
    assert stats.postings_read == host_stats.postings_read, "no double charge"
    assert stats.arena_hits == 0, "overflow fallback served nothing on device"
    assert stats.arena_misses > 0, "the fallback must be observable per query"


def test_shared_arena_keeps_sources_apart(lemmatizer):
    """One arena shared by two index sources with EQUAL generation tokens
    (every plain IndexSet has token 0) must never serve one corpus's
    buffers for the other's queries."""
    s1 = DocumentStore.from_texts(["who are you who", "to be or not to be"])
    s2 = DocumentStore.from_texts(["you who you who you", "not to be who you"])
    i1 = build_indexes(s1, sw_count=100, fu_count=0, max_distance=5)
    i2 = build_indexes(s2, sw_count=100, fu_count=0, max_distance=5)
    arena = PostingArena()
    r1 = {id(i1): arena.acquire(i1, generation_token(i1))}
    r2 = {id(i2): arena.acquire(i2, generation_token(i2))}
    for idx, res in ((i1, r1), (i2, r2)):
        for q in ("who are you who", "to be or not to be"):
            for sub in expand_subqueries(q, lemmatizer):
                got = fused.serve_query_batch(
                    [[(sub, idx)]], max_distance=5, residencies=res
                )
                exp, _ = se24_combiner(sub, idx)
                assert set(got.per_query[0]) == set(exp), (q, sub.lemmas)


def test_attach_eviction_spares_other_sources(lemmatizer, small_index):
    """A commit on the attached source evicts only ITS stale-token buffers;
    a shared arena's entries for an unrelated static index survive."""
    ix = IncrementalIndexer(sw_count=30, fu_count=60, max_distance=5,
                            lemmatizer=lemmatizer)
    ix.add_documents(["who are you who and what do you do"])
    ix.commit()
    arena = PostingArena(budget_bytes=1 << 30)
    arena.attach(ix)
    arena.acquire(ix.index, ix.generation_token)
    arena.acquire(small_index, generation_token(small_index))  # token 0
    n_total = len(arena)
    ix.add_documents(["to be or not to be"])
    ix.commit()  # must evict ONLY ix's stale generation (4 families)
    assert len(arena) == n_total - 4
    assert (
        arena.acquire(small_index, generation_token(small_index)).families
    ), "the static source's buffers must survive the other source's commit"


def test_detach_removes_mutation_listeners(lemmatizer):
    ix = IncrementalIndexer(sw_count=30, fu_count=60, max_distance=5,
                            lemmatizer=lemmatizer)
    ix.add_documents(["who are you who"])
    ix.commit()
    arena = PostingArena()
    arena.attach(ix)
    assert len(ix._listeners) == 1
    arena.detach()
    arena.detach()  # idempotent
    assert ix._listeners == []
    arena.acquire(ix.index, ix.generation_token)
    n = len(arena)
    ix.add_documents(["to be or not to be"])
    ix.commit()  # detached: no eager eviction (entries age out by LRU)
    assert len(arena) == n


# ---------------------------------------------------------------------------
# recompile churn: identical bucketed budgets reuse ONE compiled program
# ---------------------------------------------------------------------------


def test_no_recompile_for_identical_bucketed_batches(small_index, lemmatizer):
    if fused.compile_count() is None:
        pytest.skip("jax version exposes no jit cache introspection")
    arena, res = _residency(small_index)
    serve = lambda qs: fused.serve_query_batch(
        _work(qs, small_index, lemmatizer),
        max_distance=small_index.max_distance,
        residencies=res,
    )
    serve(QUERIES[:2])  # compile the bucket
    before = fused.compile_count()
    # different batch content, identical bucketed budgets: reversed query
    # order repacks every descriptor but leaves all pow2 budgets unchanged
    serve(list(reversed(QUERIES[:2])))
    assert fused.compile_count() == before, (
        "identically-bucketed batches must reuse one compiled program"
    )


def test_frontend_warmup_precompiles(small_index, lemmatizer):
    if fused.compile_count() is None:
        pytest.skip("jax version exposes no jit cache introspection")
    frontend = ServingFrontend(
        small_index, lemmatizer=lemmatizer, arena_budget_mb=256
    )
    # warm ONE query at the top_k real requests will use: a single-request
    # serve then hits the same bucketed budgets AND static top_k
    report = frontend.warmup(queries=[QUERIES[0]], top_k=16)
    assert report["programs"] >= 1 and report["seconds"] > 0
    before = fused.compile_count()
    frontend.search(QUERIES[0], top_k=16)  # same buckets as the warmed query
    assert fused.compile_count() == before, "warmed traffic must not compile"


# ---------------------------------------------------------------------------
# the slot-stream upload: extents carry exact §11 accounting statistics
# ---------------------------------------------------------------------------


def test_key_extents_match_raw_postings(small_index):
    arena = PostingArena()
    res = arena.acquire(small_index, 0)
    checked = 0
    for fname in ("stop_single", "stop_pair", "pair", "triple"):
        mapping = getattr(small_index, fname)
        for key in list(mapping)[:5]:
            ext = res.lookup(key if isinstance(key, tuple) else (key,))
            rows = np.asarray(mapping[key])
            assert ext is not None and ext.n_rows == len(rows)
            assert ext.n_docs == len(np.unique(rows[:, 0]))
            assert ext.max_doc == int(rows[:, 0].max())
            # slot streams hold the sorted-unique (doc, pos) pairs per slot
            for s, se in enumerate(ext.slots):
                pos = rows[:, 1] if s == 0 else rows[:, 1] + rows[:, 1 + s]
                uniq = np.unique(rows[:, 0].astype(np.int64) * (1 << 32) + pos)
                assert se.n_events == len(uniq)
                assert se.max_pos == int(pos.max())
            checked += 1
    assert checked > 0
