"""Fused, batched, query-at-a-time serving pipeline (DESIGN.md §9).

The serving unit is a *query batch*.  Every (query, subquery, shard) work
item becomes one fixed-shape **segment** of compact event triples
``(doc_slot, pos, lemma)`` — no dense host-side occupancy.  Candidate
(segment, doc) pairs share one global row axis R, packed densely, and a
single jit'd device program runs, for all segments of all queries at once:

    per-event rank cover  ->  §14 scoring  ->  per-query top-k

The cover is the *event-centric* form of the rank identity behind
``core.window.window_cover_rank_batch``: a fragment ending at event position
``e`` starts at ``min over lemmas l of p_l(e)``, the position of the
``mult[l]``-th latest occurrence of ``l`` at or before ``e``.  The device
gathers ``p_l(e)`` from per-(row, lemma) occurrence-position tables, so the
work is O(events) — proportional to real occurrences, like the paper's
Combiner — instead of O(rows * positions) dense occupancy sweeps.  With
``use_kernel=True`` the cover instead scatters occupancy on-device and runs
the Pallas window kernel (the TPU-native dense layout), gathering back to
event granularity; both paths produce identical fragments.

Fragment dedup and result assembly run **on device** (DESIGN.md §15.1): the
program sorts the (query, doc, start, end) fragment keys, drops adjacent
duplicates, and compacts the survivors into a dense result buffer, so the
host readout is ONE fixed-shape D2H copy per batch — no host ``np.nonzero``
/ ``np.unique`` on the serving path (``readout="host"`` keeps the legacy
host dedup as a differential reference).  All shape budgets (events E, rows
R, lemmas L, table depth K, queries Q) are bucketed to powers of two so the
number of distinct compiled programs stays logarithmic in the workload
spread (DESIGN.md §9.2).

Candidate selection for multi-key subqueries additionally runs the
Combiner's Step-1 document alignment as a *pre-filter* over sorted doc-id
lists (``kernels/intersect.py``), and Step 2's counting gate drops candidate
documents that cannot meet any lemma's multiplicity — only surviving
documents enter the row budget.

``serve_query_batch`` is the routing entry over this host-pack path and the
DESIGN.md §13 device-resident posting arena (``search/arena.py``): work
items whose keys are resident ship only descriptors and gather/pack on
device; the rest run through ``plan_query_batch`` exactly as before.
Fragment sets are identical for every routing.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.keys import SelectedKey, Subquery, select_keys
from ..core.postings import QueryStats, SearchResult
from ..index.builder import IndexSet, POSTING_WIDTH
from ..kernels.intersect import PAD, block_offsets, intersect_sorted
from ..kernels.proximity import proximity_window

__all__ = [
    "SegmentEvents",
    "QueryBatchPlan",
    "FusedBatchResult",
    "PendingBatch",
    "bucket_pow2",
    "extract_segment_events",
    "intersect_candidates",
    "plan_query_batch",
    "fused_serve_batch",
    "lower_query_batch",
    "run_query_batch",
    "serve_query_batch",
    "dispatch_count",
    "reset_dispatch_count",
    "collect_phases",
    "compile_count",
]

# Default list size above which the Step-1 pre-filter pays for a device
# round-trip; below it the same block intersection runs as host searchsorted.
INTERSECT_DEVICE_THRESHOLD = 4096

_DISPATCHES = 0


def dispatch_count() -> int:
    """Device programs issued by this module since the last reset (tests and
    the DESIGN.md §9 benches count these to assert one-dispatch-per-batch
    serving)."""
    return _DISPATCHES


def reset_dispatch_count() -> None:
    """Zero the DESIGN.md §9 dispatch counter (see ``dispatch_count``)."""
    global _DISPATCHES
    _DISPATCHES = 0


# ---------------------------------------------------------------------------
# phase attribution + compile accounting (DESIGN.md §13.5 benches)
# ---------------------------------------------------------------------------

# When a sink dict is installed, the serving paths attribute wall time to
# the six phases of a batch, appended per batch in µs (DESIGN.md §15.3):
#
#   plan_us      host posting reads + segment extraction
#   pack_us      host-side batch packing (or arena descriptor planning)
#   h2d_us       ENQUEUE time of the input transfers (async; no barrier)
#   dispatch_us  jit-call SUBMIT time (tracing/cache lookup + enqueue)
#   compute_us   block_until_ready wait for the device program (only
#                recorded when a sink is installed — production serving
#                never inserts this barrier; under the two-deep pipeline it
#                measures the NON-overlapped remainder of device time)
#   readout_us   the fixed-shape D2H result-buffer copy + split
#
# The six sum to the serial batch wall time with no double-counting: every
# timestamp closes one phase and opens the next.  The sink itself adds no
# barriers beyond the compute_us wait.
_PHASE_SINK: dict | None = None


def collect_phases(sink: dict | None) -> dict | None:
    """Install (or clear, with ``None``) the phase-breakdown sink used by
    ``benchmarks/run.py`` to attribute batch latency (plan / pack / h2d /
    dispatch / compute / readout — the DESIGN.md §15.3 attribution).
    Returns the previous sink."""
    global _PHASE_SINK
    prev, _PHASE_SINK = _PHASE_SINK, sink
    return prev


def _phase(sink: dict | None, name: str, t0: float) -> float:
    now = time.perf_counter()
    if sink is not None:
        sink.setdefault(name, []).append((now - t0) * 1e6)
    return now


def compile_count() -> int | None:
    """Compiled-program count across the serving device entry points
    (``fused_serve_batch`` + ``arena_serve_batch``), or ``None`` when the
    jax version exposes no jit-cache introspection.  The recompile-churn
    regression test pins that identically-bucketed batches reuse ONE
    compiled program (DESIGN.md §9.2/§13.4)."""
    from .arena import arena_serve_batch

    total = 0
    for fn in (fused_serve_batch, arena_serve_batch):
        cache_size = getattr(fn, "_cache_size", None)
        if cache_size is None:
            return None
        total += cache_size()
    return total


def bucket_pow2(n: int, lo: int = 1) -> int:
    """Smallest power of two >= max(n, lo) — the jit-cache shape budget of
    DESIGN.md §9.2 (padded batching, logarithmically many compiled programs)."""
    n = max(n, lo)
    return 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# compact event transport (host side)
# ---------------------------------------------------------------------------


@dataclass
class SegmentEvents:
    """Compact event transport for one (subquery, shard) work item — the
    §10.4 ``Set`` calls batched into triples (DESIGN.md §9.1).

    Events are deduplicated and sorted by (doc, pos, lemma).  ``rank`` is the
    event's occurrence index within its (doc, lemma) group — the row of the
    per-(row, lemma) position table it fills; ``primary`` marks the first
    event at each (doc, pos) so positionwise quantities (scores, fragment
    counts) are not double-counted for multi-lemma positions.
    """

    doc_ids: np.ndarray  # [Bd] sorted unique candidate doc ids
    slot: np.ndarray  # [E] int32 index into doc_ids
    pos: np.ndarray  # [E] int32 document position
    lem: np.ndarray  # [E] int32 local lemma id
    rank: np.ndarray  # [E] int32 occurrence index within (doc, lemma)
    primary: np.ndarray  # [E] bool first event of its (doc, pos)
    mult: np.ndarray  # [L] int32 required multiplicity per local lemma
    lemmas: list[str]  # local lemma id -> lemma


def _device_intersect(
    a: np.ndarray, b: np.ndarray, block_a: int = 128, block_b: int = 256
) -> np.ndarray:
    """Membership mask of sorted-unique ``a`` in sorted-unique ``b`` via the
    Pallas block-intersection kernel (scalar-prefetched offsets)."""
    global _DISPATCHES
    na = bucket_pow2(len(a), block_a)
    nb = bucket_pow2(len(b), block_b)
    a_p = np.full((na,), PAD, np.int32)
    a_p[: len(a)] = a
    b_p = np.full((nb,), PAD, np.int32)
    b_p[: len(b)] = b
    offsets = block_offsets(a_p, b_p, block_a, block_b)
    # size the chunk sweep from data statistics: matches of a real a-block
    # end before searchsorted(b, block_last, right)
    n_blocks = na // block_a
    last_idx = np.minimum(np.arange(1, n_blocks + 1) * block_a - 1, len(a) - 1)
    ends = np.searchsorted(b_p[: len(b)], a_p[last_idx], side="right")
    span = np.maximum(ends - offsets, 1)
    n_chunks = bucket_pow2(int(np.ceil(span.max() / block_b)))
    hit = np.asarray(
        intersect_sorted(
            jnp.asarray(a_p),
            jnp.asarray(b_p),
            jnp.asarray(offsets),
            block_a=block_a,
            block_b=block_b,
            n_chunks=n_chunks,
        )
    )
    _DISPATCHES += 1
    return hit[: len(a)] > 0


def intersect_candidates(
    doc_lists: Sequence[np.ndarray],
    device_threshold: int = INTERSECT_DEVICE_THRESHOLD,
) -> np.ndarray:
    """Sorted-unique doc-list intersection across a subquery's keys — the
    Combiner's §10.1 Step-1 document alignment, run once as a batch
    pre-filter (DESIGN.md §9.1).

    Lists at or above ``device_threshold`` go through the Pallas block
    intersection (``kernels/intersect.py``); smaller ones use the identical
    host form (searchsorted) where a device round-trip would not pay off.
    """
    lists = sorted((np.asarray(d) for d in doc_lists), key=len)
    acc = lists[0]
    for other in lists[1:]:
        if not len(acc):
            return acc
        if min(len(acc), len(other)) >= device_threshold:
            hit = _device_intersect(acc, other)
        else:
            i = np.minimum(np.searchsorted(other, acc), len(other) - 1)
            hit = other[i] == acc
        acc = acc[hit]
    return acc


def extract_segment_events(
    subquery: Subquery,
    index: IndexSet,
    keys: Sequence[SelectedKey] | None = None,
    doc_len: int = 512,
    stats: QueryStats | None = None,
    intersect_device_threshold: int = INTERSECT_DEVICE_THRESHOLD,
) -> SegmentEvents | None:
    """Key postings -> compact (doc_slot, pos, lemma) event triples — the
    §10.4 ``Set`` calls batched, plus the §10.1/§10.3 pre-filters
    (DESIGN.md §9.1).

    Returns ``None`` for an empty subquery (no key events, or the Step-1
    candidate intersection is empty) so callers short-circuit instead of
    dispatching an all-padding batch; the skip is counted in
    ``QueryStats.empty_subqueries``.  An index with no live documents (an
    empty shard, or a multi-segment view whose docs are all tombstoned)
    short-circuits before any key-posting lookup — segment-union merges are
    never forced for work items that cannot contribute candidates.
    """
    if index.n_docs == 0:
        if stats is not None:
            stats.empty_subqueries += 1
        return None
    keys = list(keys) if keys is not None else select_keys(subquery, index.fl)
    lemmas = subquery.unique_lemmas()
    lid = {l: i for i, l in enumerate(lemmas)}
    mult_map = subquery.multiplicity()
    mult = np.array([mult_map[l] for l in lemmas], dtype=np.int32)

    # vectorized event extraction: one (doc, pos, lemma) column set per
    # unstarred key slot — no per-posting Python work
    ev_doc, ev_pos, ev_lem = [], [], []
    key_docs: list[np.ndarray] = []
    for key in keys:
        rows = np.asarray(index.key_postings(key.components))
        if stats is not None:
            stats.postings_read += len(rows)
            stats.bytes_read += rows.nbytes
        key_docs.append(
            np.unique(rows[:, 0]) if len(rows) else np.empty((0,), np.int32)
        )
        if not len(rows):
            continue
        comps, stars = key.components, key.starred
        for slot in range(len(comps)):
            if stars[slot]:
                continue
            pos = rows[:, 1] if slot == 0 else rows[:, 1] + rows[:, 1 + slot]
            ev_doc.append(rows[:, 0])
            ev_pos.append(pos)
            ev_lem.append(np.full(len(rows), lid[comps[slot]], np.int32))

    if not ev_doc:
        if stats is not None:
            stats.empty_subqueries += 1
        return None
    doc_a = np.concatenate(ev_doc)
    pos_a = np.concatenate(ev_pos)
    lem_a = np.concatenate(ev_lem)
    ok = pos_a >= 0
    doc_a, pos_a, lem_a = doc_a[ok], pos_a[ok], lem_a[ok]
    if len(pos_a):
        # the position modulus must cover every real position: documents
        # longer than the caller's doc_len hint must not lose fragments
        doc_len = max(doc_len, int(pos_a.max()) + 1)

    # Step-1 pre-filter: a fragment needs every key iterator on the document
    if len(key_docs) >= 2:
        cand = intersect_candidates(key_docs, device_threshold=intersect_device_threshold)
        if len(cand) and len(doc_a):
            i = np.minimum(np.searchsorted(cand, doc_a), len(cand) - 1)
            keep = cand[i] == doc_a
            doc_a, pos_a, lem_a = doc_a[keep], pos_a[keep], lem_a[keep]
        else:
            doc_a = doc_a[:0]

    if not len(doc_a):
        if stats is not None:
            stats.empty_subqueries += 1
        return None

    # dedup events (occupancy semantics: one event per (doc, pos, lemma))
    # and run Step 2's counting gate batched: a candidate doc whose distinct
    # positions of some lemma fall short of its multiplicity can never emit
    # a fragment — drop its rows before the device budget.
    n_lem = len(lemmas)
    comp = (doc_a.astype(np.int64) * doc_len + pos_a) * n_lem + lem_a
    comp = np.unique(comp)  # sorted by (doc, pos, lemma)
    lem_a = (comp % n_lem).astype(np.int32)
    pos_a = ((comp // n_lem) % doc_len).astype(np.int32)
    doc_a = (comp // (n_lem * doc_len)).astype(np.int32)
    docs, slot = np.unique(doc_a, return_inverse=True)
    counts = np.bincount(
        slot * n_lem + lem_a, minlength=len(docs) * n_lem
    ).reshape(len(docs), n_lem)
    ok_doc = (counts >= mult[None, :]).all(axis=1)
    if not ok_doc.all():
        keep = ok_doc[slot]
        doc_a, pos_a, lem_a = doc_a[keep], pos_a[keep], lem_a[keep]
        if not len(doc_a):
            if stats is not None:
                stats.empty_subqueries += 1
            return None
        docs, slot = np.unique(doc_a, return_inverse=True)

    # occurrence rank within (doc, lemma) + primary flag per (doc, pos)
    order = np.lexsort((pos_a, lem_a, slot))
    grp = slot[order].astype(np.int64) * n_lem + lem_a[order]
    new_grp = np.r_[True, grp[1:] != grp[:-1]]
    grp_start = np.maximum.accumulate(
        np.where(new_grp, np.arange(len(order)), 0)
    )
    rank = np.empty(len(order), np.int32)
    rank[order] = (np.arange(len(order)) - grp_start).astype(np.int32)
    pos_key = slot.astype(np.int64) * doc_len + pos_a
    primary = np.r_[True, pos_key[1:] != pos_key[:-1]]

    return SegmentEvents(
        doc_ids=docs.astype(np.int32),
        slot=slot.astype(np.int32),
        pos=pos_a.astype(np.int32),
        lem=lem_a.astype(np.int32),
        rank=rank,
        primary=primary,
        mult=mult,
        lemmas=lemmas,
    )


# ---------------------------------------------------------------------------
# query-batch plan (bucketed, padded, fixed-shape)
# ---------------------------------------------------------------------------


@dataclass
class QueryBatchPlan:
    """Fixed-shape tensors for one fused device dispatch (DESIGN.md §9.2
    bucketed budgets; the §10.4 events of every work item, packed).

    The batch is packed *row-major*: every (segment, candidate-doc) pair of
    every query occupies one row of a single global row axis ``R`` — no
    per-segment doc-slot padding, so total device work tracks the real
    candidate count, not ``segments x max(docs per segment)``.  ``postab``
    is the per-(row, lemma) occurrence-position table the event-centric
    cover gathers from (pad = ``doc_len``, which compares greater than every
    real position).  Padding rows have ``row_doc = -1`` / ``row_query = -1``
    / ``mult = 0`` and provably emit nothing.
    """

    events: np.ndarray  # [E, 3] int32 (row, pos, lemma), pad = -1
    primary: np.ndarray  # [E] int8 first-event-of-(row, pos) flag
    postab: np.ndarray  # [R, L, K] int32 k-th occurrence position, pad = doc_len
    row_doc: np.ndarray  # [R] int32 global doc id per row, pad = -1
    row_query: np.ndarray  # [R] int32 query index per row, pad = -1
    mult: np.ndarray  # [R, L] int32 (0 = unused lemma slot)
    n_queries: int  # live queries (<= query_budget)
    query_budget: int  # bucket_pow2(n_queries), static in the device program
    doc_len: int  # bucketed window budget (<= the caller's doc_len cap)


def plan_query_batch(
    work: Sequence[Sequence[tuple]],
    doc_len: int = 512,
    stats: QueryStats | Sequence[QueryStats] | None = None,
    intersect_device_threshold: int = INTERSECT_DEVICE_THRESHOLD,
) -> QueryBatchPlan | None:
    """Pack a query batch into one device program's inputs.

    ``work[qi]`` lists query ``qi``'s ``(subquery, index-shard)`` items — the
    cross product the per-subquery serving loops used to dispatch one call
    each for.  An item may carry a third element, the §6 keys to use
    (``(subquery, index, keys)``): the query planner passes its pre-selected
    bindings this way so plan execution reads exactly the postings the plan
    costed (``search/planner.py``); two-element items select keys themselves,
    and both forms produce identical events for identical key sets.
    ``stats`` is one accumulator for the batch or one per query.  Returns
    ``None`` when every item is empty (nothing to dispatch).
    """
    def stat_for(qi: int) -> QueryStats | None:
        if stats is None or isinstance(stats, QueryStats):
            return stats
        return stats[qi]

    sink = _PHASE_SINK
    t0 = time.perf_counter()
    segs: list[tuple[int, SegmentEvents]] = []
    for qi, items in enumerate(work):
        for item in items:
            sub, index = item[0], item[1]
            keys = item[2] if len(item) > 2 else None
            se = extract_segment_events(
                sub,
                index,
                keys=keys,
                doc_len=doc_len,
                stats=stat_for(qi),
                intersect_device_threshold=intersect_device_threshold,
            )
            if se is not None:
                segs.append((qi, se))
    t0 = _phase(sink, "plan_us", t0)
    if not segs:
        return None

    n_rows = sum(len(se.doc_ids) for _, se in segs)
    n_events = sum(len(se.slot) for _, se in segs)
    r_budget = bucket_pow2(n_rows, lo=8)
    e_budget = bucket_pow2(n_events, lo=64)
    l_budget = bucket_pow2(max(len(se.lemmas) for _, se in segs), lo=2)
    k_budget = bucket_pow2(max(int(se.rank.max()) for _, se in segs) + 1, lo=4)
    # position budget: bucketed from the last real event, NOT clamped to the
    # caller's doc_len hint — long documents keep their fragments (the event
    # path's cost barely depends on it; only the dense kernel path scatters
    # [R, L, N] occupancy)
    max_pos = max(int(se.pos.max()) for _, se in segs)
    n_budget = bucket_pow2(max_pos + 1, lo=64)

    events = np.full((e_budget, 3), -1, np.int32)
    primary = np.zeros((e_budget,), np.int8)
    postab = np.full((r_budget, l_budget, k_budget), n_budget, np.int32)
    row_doc = np.full((r_budget,), -1, np.int32)
    row_query = np.full((r_budget,), -1, np.int32)
    mult = np.zeros((r_budget, l_budget), np.int32)
    row = ev = 0
    for qi, se in segs:
        nd, ne = len(se.doc_ids), len(se.slot)
        events[ev : ev + ne, 0] = se.slot + row
        events[ev : ev + ne, 1] = se.pos
        events[ev : ev + ne, 2] = se.lem
        primary[ev : ev + ne] = se.primary
        postab[se.slot + row, se.lem, se.rank] = se.pos
        row_doc[row : row + nd] = se.doc_ids
        row_query[row : row + nd] = qi
        mult[row : row + nd, : len(se.mult)] = se.mult
        row += nd
        ev += ne
    _phase(sink, "pack_us", t0)
    return QueryBatchPlan(
        events=events,
        primary=primary,
        postab=postab,
        row_doc=row_doc,
        row_query=row_query,
        mult=mult,
        n_queries=len(work),
        query_budget=bucket_pow2(len(work)),
        doc_len=n_budget,
    )


# ---------------------------------------------------------------------------
# the fused device program
# ---------------------------------------------------------------------------

_I32_SENTINEL = np.int32(np.iinfo(np.int32).max)


def _assemble_fragments(
    q: jax.Array,  # [E] int32 query index per event
    d: jax.Array,  # [E] int32 doc id per event
    s: jax.Array,  # [E] int32 fragment start per event
    e: jax.Array,  # [E] int32 fragment end per event
    valid: jax.Array,  # [E] bool emitting primary events
    query_budget: int,
) -> jax.Array:
    """Device-side fragment dedup + result assembly (DESIGN.md §15.1).

    Sorts the per-event fragment keys ``(q, d, s, e)`` lexicographically
    (invalid events carry the int32 sentinel in every column and sort last),
    drops adjacent duplicates, and compacts the survivors to the head of a
    dense ``[E + Q, 4]`` int32 result buffer — the same dedup the host
    readout's ``np.unique`` over packed ``frag_key`` performs, with the same
    output order (ascending ``(q, doc, start, end)``), but with no host
    ``nonzero``/``unique`` and no bit-packing (four int32 sort keys instead
    of one packed int64, so there is no width budget to overflow).

    The trailing ``Q`` rows carry the per-query unique-fragment counts in
    column 0, so the whole readout is ONE fixed-shape D2H copy: the host
    splits ``buf[:counts.sum()]`` by ``cumsum(counts)`` — rows are already
    grouped by query because the sort key leads with ``q``.
    """
    cap = q.shape[0]
    qk = jnp.where(valid, q, _I32_SENTINEL)
    dk = jnp.where(valid, d, _I32_SENTINEL)
    sk = jnp.where(valid, s, _I32_SENTINEL)
    ek = jnp.where(valid, e, _I32_SENTINEL)
    qs, ds, ss, es = jax.lax.sort((qk, dk, sk, ek), num_keys=4)

    def prev(col: jax.Array) -> jax.Array:
        return jnp.concatenate([jnp.full((1,), -1, col.dtype), col[:-1]])

    fin = qs < _I32_SENTINEL
    dup = (qs == prev(qs)) & (ds == prev(ds)) & (ss == prev(ss)) & (es == prev(es))
    uniq = fin & ~dup
    # compaction scatter: unique survivors go to their prefix-sum slot,
    # everything else to an out-of-bounds destination dropped by the scatter
    dest = jnp.where(
        uniq, jnp.cumsum(uniq.astype(jnp.int32)) - 1, cap + query_budget
    )
    rows = jnp.stack([qs, ds, ss, es], axis=1)
    buf = jnp.full((cap + query_budget, 4), -1, jnp.int32)
    buf = buf.at[dest].set(rows, mode="drop")
    counts = (
        jnp.zeros((query_budget,), jnp.int32)
        .at[jnp.clip(qs, 0, query_budget - 1)]
        .add(uniq.astype(jnp.int32))
    )
    return buf.at[cap:, 0].set(counts)


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_distance",
        "query_budget",
        "window_len",
        "top_k",
        "compute_dtype",
        "use_kernel",
        "interpret",
    ),
)
def fused_serve_batch(
    events: jax.Array,  # [E, 3] int32 (row, pos, lemma), pad = -1
    primary: jax.Array,  # [E] int8 first-event-of-(row, pos) flag
    postab: jax.Array,  # [R, L, K] int32 occurrence positions, pad = window_len
    row_doc: jax.Array,  # [R] int32 global doc id per row, pad = -1
    row_query: jax.Array,  # [R] int32 query index per row, pad = -1
    mult: jax.Array,  # [R, L] int32
    *,
    max_distance: int,
    query_budget: int,
    window_len: int,
    top_k: int = 16,
    compute_dtype: str = "uint8",  # §Perf-3: dense-path occupancy fits u8
    use_kernel: bool = False,
    interpret: bool | None = None,
):
    """One device program for a whole query batch.

    stage 1  per-event rank cover: for every event, gather the mult-th
             latest occurrence position of every lemma from ``postab`` —
             fragment start = min over active lemmas, emit iff the span
             fits ``2 * max_distance`` (O(events), no dense occupancy);
             with ``use_kernel=True``: scatter occupancy [R, L, N] on-device
             instead and run the Pallas window kernel, then gather emit and
             start back to event granularity;
    stage 2  §14 relevance per row (scatter-add of per-event contributions);
    stage 3  per-query top-k via a [Q, R] masked selection over row scores.

    ``top_docs`` is row-level: a document reachable through two subqueries
    of the same query occupies two rows — exact ranking uses the fragment
    readout (DESIGN.md §9.3).  Fragments themselves ARE deduplicated on
    device: ``res`` is the §15.1 dense result buffer
    (``_assemble_fragments`` — sorted unique ``(q, doc, start, end)`` rows
    plus per-query counts), read out as one fixed-shape D2H copy.

    ``interpret`` reaches the Pallas kernel only (``use_kernel=True``);
    ``None`` is the backend check of ``kernels/backend.py``.
    """
    r, l, k = postab.shape
    n = window_len
    q = query_budget
    window = 2 * max_distance + 1

    row = events[..., 0]
    pos = events[..., 1]
    lem = events[..., 2]
    ok = (row >= 0) & (row < r) & (pos >= 0) & (pos < n) & (lem >= 0) & (lem < l)
    row_s = jnp.clip(row, 0, r - 1)

    if use_kernel:
        # ---- dense path: on-device scatter + Pallas window kernel ---------
        cdt = jnp.dtype(compute_dtype)
        flat = (row_s * l + jnp.maximum(lem, 0)) * n + jnp.maximum(pos, 0)
        occ = jnp.zeros((r * l * n,), cdt).at[flat].max(ok.astype(cdt))
        occ = occ.reshape(r, l, n)
        emit_rn, start_rn = proximity_window(
            occ, mult, max_distance, interpret=interpret, compute_dtype=compute_dtype
        )
        pos_s = jnp.clip(pos, 0, n - 1)
        emit = ok & emit_rn[row_s, pos_s]
        start = start_rn[row_s, pos_s]
    else:
        # ---- event-centric rank cover -------------------------------------
        tab = postab[row_s]  # [E, L, K]
        mrow = mult[row_s]  # [E, L]
        active = mrow > 0
        # C_l(pos): occurrences of lemma l at/before this event's position.
        # postab rows are position-sorted, so this is a log2(K)-step binary
        # search per (event, lemma) instead of a K-wide compare-reduce.
        cnt = jnp.zeros(tab.shape[:2], jnp.int32)  # [E, L]
        step = k
        while step > 1:
            step //= 2
            probe = jnp.take_along_axis(
                tab, jnp.minimum(cnt + step - 1, k - 1)[..., None], axis=-1
            )[..., 0]
            cnt = jnp.where(probe <= pos[:, None], cnt + step, cnt)
        # strides sum to k-1, so a full prefix undercounts by one: final probe
        probe = jnp.take_along_axis(
            tab, jnp.minimum(cnt, k - 1)[..., None], axis=-1
        )[..., 0]
        cnt = cnt + (probe <= pos[:, None]).astype(jnp.int32)
        have = cnt >= mrow
        sel = jnp.clip(cnt - mrow, 0, k - 1)
        p_sel = jnp.take_along_axis(tab, sel[..., None], axis=-1)[..., 0]
        p_sel = jnp.where(active & have, p_sel, n)  # inactive -> +inf for min
        start = jnp.min(p_sel, axis=-1)  # [E] largest covering q
        covered = jnp.all(have | ~active, axis=-1) & jnp.any(active, axis=-1)
        emit = ok & covered & (start < n) & (pos - start < window)
        start = jnp.where(emit, start, pos)

    # ---- §14 relevance per row (primary events only: one per position) ----
    span = (pos - start).astype(jnp.float32)
    contrib = jnp.where(emit & (primary > 0), 1.0 / (span + 1.0) ** 2, 0.0)
    scores = jnp.zeros((r,), jnp.float32).at[row_s].add(
        jnp.where(ok, contrib, 0.0)
    )
    scores = jnp.where(row_doc >= 0, scores, -jnp.inf)

    # ---- per-query top-k ---------------------------------------------------
    qids = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0)
    scores_q = jnp.where(row_query[None, :] == qids, scores[None, :], -jnp.inf)
    kk = min(top_k, r)
    top_scores, idx = jax.lax.top_k(scores_q, kk)  # [Q, K]
    top_docs = jnp.where(jnp.isfinite(top_scores), row_doc[idx], -1)

    frag_per_row = (
        jnp.zeros((r,), jnp.int32)
        .at[row_s]
        .add((emit & (primary > 0)).astype(jnp.int32))
    )
    n_fragments = (
        jnp.zeros((q,), jnp.int32)
        .at[jnp.clip(row_query, 0, q - 1)]
        .add(jnp.where(row_query >= 0, frag_per_row, 0))
    )

    # ---- §15.1 device-side result assembly --------------------------------
    ev_q = row_query[row_s]
    ev_d = row_doc[row_s]
    frag_valid = emit & (primary > 0) & (ev_q >= 0) & (ev_d >= 0)
    res = _assemble_fragments(ev_q, ev_d, start, pos, frag_valid, q)

    return {
        "emit": emit,
        "start": start,
        "res": res,
        "top_docs": top_docs,
        "top_scores": top_scores,
        "n_fragments": n_fragments,
    }


# ---------------------------------------------------------------------------
# execution + vectorized readout
# ---------------------------------------------------------------------------


class FusedBatchResult:
    """Per-query exact fragment sets plus the device's slot-level ranking
    (DESIGN.md §9.3: the fragment readout is the exact §10.2 result; the
    device top-k is row-level, for dashboards/serve_step consumers).

    The device readout (§15.1) carries fragments as the compact
    ``frag_rows``/``frag_offsets`` pair — ``per_query`` materializes
    ``SearchResult`` objects lazily on first access, keeping Python object
    construction off the readout-phase critical path.  The host readout and
    the empty/merge paths construct eagerly with ``per_query=...``.
    """

    __slots__ = (
        "top_docs",
        "top_scores",
        "n_fragments",
        "frag_rows",
        "frag_offsets",
        "_per_query",
    )

    def __init__(
        self,
        *,
        top_docs: np.ndarray,  # [Q, K] int32 (-1 pad)
        top_scores: np.ndarray,  # [Q, K] float32
        n_fragments: np.ndarray,  # [Q] pre-dedup emit counts
        per_query: list[list[SearchResult]] | None = None,
        frag_rows: np.ndarray | None = None,  # [F, 3] int32 (doc, start, end)
        frag_offsets: np.ndarray | None = None,  # [Q + 1] int64 cumulative
    ):
        if per_query is None and frag_offsets is None:
            raise ValueError("need per_query or frag_rows/frag_offsets")
        self.top_docs = top_docs
        self.top_scores = top_scores
        self.n_fragments = n_fragments
        self.frag_rows = frag_rows
        self.frag_offsets = frag_offsets
        self._per_query = per_query

    @property
    def n_queries(self) -> int:
        if self._per_query is not None:
            return len(self._per_query)
        return len(self.frag_offsets) - 1

    def n_results(self, qi: int) -> int:
        """Deduped fragment count for query ``qi`` without materializing
        ``SearchResult`` objects (stats accounting on the serving path)."""
        if self._per_query is not None:
            return len(self._per_query[qi])
        return int(self.frag_offsets[qi + 1] - self.frag_offsets[qi])

    @property
    def per_query(self) -> list[list[SearchResult]]:
        """Deduped fragment union per query, sorted by (doc, start, end);
        materialized from ``frag_rows`` on first access and cached."""
        if self._per_query is None:
            rows = self.frag_rows.tolist()
            offs = self.frag_offsets.tolist()
            make = SearchResult._make
            self._per_query = [
                [make(r) for r in rows[offs[qi] : offs[qi + 1]]]
                for qi in range(len(offs) - 1)
            ]
        return self._per_query


class PendingBatch:
    """Handle for an in-flight query batch (DESIGN.md §15.2).

    ``run_query_batch``/``run_arena_batch``/``serve_query_batch`` with
    ``defer=True`` return one of these right after SUBMITTING the device
    program — the H2D copies and the program itself are enqueued but not
    awaited, so the caller can plan/pack/submit the next batch while this
    one computes.  ``result()`` performs the blocking readout (idempotent;
    the result is cached).
    """

    __slots__ = ("_thunk", "_result")

    def __init__(self, thunk):
        self._thunk = thunk
        self._result = None

    def result(self) -> FusedBatchResult:
        if self._thunk is not None:
            self._result = self._thunk()
            self._thunk = None
        return self._result


def empty_batch_result(n_queries: int, top_k: int) -> FusedBatchResult:
    return FusedBatchResult(
        per_query=[[] for _ in range(n_queries)],
        top_docs=np.full((n_queries, top_k), -1, np.int32),
        top_scores=np.full((n_queries, top_k), -np.inf, np.float32),
        n_fragments=np.zeros((n_queries,), np.int64),
    )


def _dedup_fragments(
    q_of: np.ndarray, docs: np.ndarray, starts: np.ndarray, ends: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host-side fragment dedup: sorted unique ``(q, doc, start, end)``
    columns, in the same ascending order the §15.1 device assembly emits.

    Two tiers, mirroring the arena's pack32/argsort split
    (``plan_arena_batch``): when the packed key fits int64 the dedup is one
    ``np.unique`` over ``((q * D + doc) * N + start) * N + end``; otherwise
    — wide doc-id spaces or very long documents, where packing would
    silently alias distinct fragments — it falls back to ``np.lexsort`` +
    adjacent-diff, which has no width budget at all.
    """
    if q_of.size == 0:
        z = np.zeros((0,), np.int64)
        return z, z, z, z
    doc_mod = int(docs.max(initial=0)) + 1
    n_mod = int(max(starts.max(initial=0), ends.max(initial=0))) + 1
    q_mod = int(q_of.max(initial=0)) + 1
    if (q_mod * doc_mod * n_mod * n_mod - 1).bit_length() <= 63:
        frag_key = ((q_of * doc_mod + docs) * n_mod + starts) * n_mod + ends
        uniq = np.unique(frag_key)
        u_end = uniq % n_mod
        u_start = (uniq // n_mod) % n_mod
        u_doc = (uniq // (n_mod * n_mod)) % doc_mod
        u_q = uniq // (n_mod * n_mod * doc_mod)
        return u_q, u_doc, u_start, u_end
    order = np.lexsort((ends, starts, docs, q_of))
    q_s, d_s, s_s, e_s = q_of[order], docs[order], starts[order], ends[order]
    keep = np.ones(q_s.shape, bool)
    keep[1:] = (
        (q_s[1:] != q_s[:-1])
        | (d_s[1:] != d_s[:-1])
        | (s_s[1:] != s_s[:-1])
        | (e_s[1:] != e_s[:-1])
    )
    return q_s[keep], d_s[keep], s_s[keep], e_s[keep]


def _split_result_buffer(
    buf: np.ndarray, n_queries: int, query_budget: int
) -> tuple[np.ndarray, np.ndarray]:
    """Split the fetched §15.1 result buffer into ``(frag_rows,
    frag_offsets)``: the trailing ``query_budget`` rows carry per-query
    counts in column 0; the head rows are the compacted unique fragments,
    already grouped by query in ascending key order."""
    cap = buf.shape[0] - query_budget
    counts = buf[cap : cap + n_queries, 0].astype(np.int64)
    offsets = np.zeros((n_queries + 1,), np.int64)
    np.cumsum(counts, out=offsets[1:])
    frag_rows = buf[: int(offsets[-1]), 1:4]
    return frag_rows, offsets


def lower_query_batch(
    plan: QueryBatchPlan,
    *,
    max_distance: int,
    top_k: int = 16,
    use_kernel: bool = False,
    compute_dtype: str = "uint8",
):
    """Lower ONE fused device program WITHOUT dispatching it (DESIGN.md
    §15.4).  Returns the jax ``Lowered`` object for the exact program
    :func:`run_query_batch` would execute; callers compile it and feed
    ``.as_text()`` to ``launch/hlo_analysis.analyze_hlo`` for the serving
    roofline (``benchmarks/paper_tables.bench_roofline``)."""
    return fused_serve_batch.lower(
        jnp.asarray(plan.events),
        jnp.asarray(plan.primary),
        jnp.asarray(plan.postab),
        jnp.asarray(plan.row_doc),
        jnp.asarray(plan.row_query),
        jnp.asarray(plan.mult),
        max_distance=max_distance,
        query_budget=plan.query_budget,
        window_len=plan.doc_len,
        top_k=top_k,
        compute_dtype=compute_dtype,
        use_kernel=use_kernel,
    )


def run_query_batch(
    plan: QueryBatchPlan,
    *,
    max_distance: int,
    top_k: int = 16,
    use_kernel: bool = False,
    compute_dtype: str = "uint8",
    stats: QueryStats | None = None,
    readout: str = "device",
    defer: bool = False,
) -> FusedBatchResult | PendingBatch:
    """Dispatch ONE device program for the plan and read results out of the
    §15.1 device-assembled dense buffer — one fixed-shape D2H copy
    (``readout="device"``; the fragment sets are exact §10.2 results,
    identical to the scalar Combiner).  ``readout="host"`` instead fetches
    the per-event emit/start arrays and dedups on the host — the legacy
    path, kept as the differential reference (``tests/test_readout.py``).
    ``defer=True`` returns a :class:`PendingBatch` right after submit, so
    the device program runs while the caller prepares the next batch
    (§15.2)."""
    if readout not in ("device", "host"):
        raise ValueError(f"unknown readout mode: {readout!r}")
    global _DISPATCHES
    sink = _PHASE_SINK
    t0 = time.perf_counter()
    inputs = (
        jnp.asarray(plan.events),
        jnp.asarray(plan.primary),
        jnp.asarray(plan.postab),
        jnp.asarray(plan.row_doc),
        jnp.asarray(plan.row_query),
        jnp.asarray(plan.mult),
    )
    if stats is not None:
        stats.h2d_bytes += (
            plan.events.nbytes + plan.primary.nbytes + plan.postab.nbytes
            + plan.row_doc.nbytes + plan.row_query.nbytes + plan.mult.nbytes
        )
    # enqueue time only: the transfers complete asynchronously, overlapped
    # with submit — the premature block_until_ready(inputs) that used to sit
    # here forced a full H2D sync inside the dispatch window
    t0 = _phase(sink, "h2d_us", t0)
    out = fused_serve_batch(
        *inputs,
        max_distance=max_distance,
        query_budget=plan.query_budget,
        window_len=plan.doc_len,
        top_k=top_k,
        compute_dtype=compute_dtype,
        use_kernel=use_kernel,
    )
    _DISPATCHES += 1
    if stats is not None:
        stats.device_dispatches += 1
    _phase(sink, "dispatch_us", t0)

    nq = plan.n_queries

    def finalize() -> FusedBatchResult:
        t1 = time.perf_counter()
        if sink is not None:
            # bench-only barrier: bills device time to compute_us instead of
            # whichever phase bracket happens to enclose the first fetch
            jax.block_until_ready(out)
            t1 = _phase(sink, "compute_us", t1)
        if readout == "device":
            buf = np.asarray(out["res"])
            frag_rows, frag_offsets = _split_result_buffer(
                buf, nq, plan.query_budget
            )
            result = FusedBatchResult(
                frag_rows=frag_rows,
                frag_offsets=frag_offsets,
                top_docs=np.asarray(out["top_docs"])[:nq],
                top_scores=np.asarray(out["top_scores"])[:nq],
                n_fragments=np.asarray(out["n_fragments"])[:nq],
            )
        else:
            # legacy host readout: one nonzero over the event batch (primary
            # events carry one fragment per emitting position), then the
            # two-tier host dedup — differential reference for §15.1
            emit = np.asarray(out["emit"]) & (plan.primary > 0)
            (hits,) = np.nonzero(emit)
            starts = np.asarray(out["start"])[hits].astype(np.int64)
            ends = plan.events[hits, 1].astype(np.int64)
            rows = plan.events[hits, 0]
            docs = plan.row_doc[rows].astype(np.int64)
            q_of = plan.row_query[rows].astype(np.int64)
            live = (q_of >= 0) & (q_of < nq)
            u_q, u_doc, u_start, u_end = _dedup_fragments(
                q_of[live], docs[live], starts[live], ends[live]
            )
            per_query: list[list[SearchResult]] = [[] for _ in range(nq)]
            for qi, d, st, en in zip(
                u_q.tolist(), u_doc.tolist(), u_start.tolist(), u_end.tolist()
            ):
                per_query[qi].append(SearchResult(doc_id=d, start=st, end=en))
            result = FusedBatchResult(
                per_query=per_query,
                top_docs=np.asarray(out["top_docs"])[:nq],
                top_scores=np.asarray(out["top_scores"])[:nq],
                n_fragments=np.asarray(out["n_fragments"])[:nq],
            )
        _phase(sink, "readout_us", t1)
        return result

    if defer:
        return PendingBatch(finalize)
    return finalize()


# ---------------------------------------------------------------------------
# arena/host orchestration (DESIGN.md §13: resident descriptors, host fallback)
# ---------------------------------------------------------------------------


def _merge_results(
    results: Sequence[FusedBatchResult], n_queries: int, top_k: int
) -> FusedBatchResult:
    """Union per-query fragment sets and re-merge the row-level top-k lists
    of a split arena + host execution.  Device-readout results merge at the
    array level — concatenate fragment columns, re-dedup with the two-tier
    host dedup — so a mixed batch never materializes ``SearchResult``
    objects; results that already carry ``per_query`` lists union as sets
    (the same dedup)."""
    if len(results) == 1:
        return results[0]
    scores = np.concatenate([r.top_scores for r in results], axis=1)
    docs = np.concatenate([r.top_docs for r in results], axis=1)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :top_k]
    top_docs = np.take_along_axis(docs, order, axis=1)
    top_scores = np.take_along_axis(scores, order, axis=1)
    n_fragments = sum(r.n_fragments for r in results)
    if all(r.frag_offsets is not None and r._per_query is None for r in results):
        q_col = np.concatenate(
            [
                np.repeat(
                    np.arange(n_queries, dtype=np.int64),
                    np.diff(r.frag_offsets),
                )
                for r in results
            ]
        )
        rows = np.concatenate(
            [r.frag_rows for r in results], dtype=np.int64, casting="unsafe"
        ).reshape(-1, 3)
        u_q, u_d, u_s, u_e = _dedup_fragments(
            q_col, rows[:, 0], rows[:, 1], rows[:, 2]
        )
        counts = np.bincount(u_q, minlength=n_queries)
        offsets = np.zeros((n_queries + 1,), np.int64)
        np.cumsum(counts, out=offsets[1:])
        return FusedBatchResult(
            frag_rows=np.stack([u_d, u_s, u_e], axis=1).astype(np.int32),
            frag_offsets=offsets,
            top_docs=top_docs,
            top_scores=top_scores,
            n_fragments=n_fragments,
        )
    per_query: list[list[SearchResult]] = []
    for qi in range(n_queries):
        union: set[SearchResult] = set()
        for r in results:
            union.update(r.per_query[qi])
        per_query.append(sorted(union))
    return FusedBatchResult(
        per_query=per_query,
        top_docs=top_docs,
        top_scores=top_scores,
        n_fragments=n_fragments,
    )


def serve_query_batch(
    work: Sequence[Sequence[tuple]],
    *,
    max_distance: int,
    top_k: int = 16,
    doc_len: int = 512,
    use_kernel: bool = False,
    compute_dtype: str = "uint8",
    stats: QueryStats | Sequence[QueryStats] | None = None,
    batch_stats: QueryStats | None = None,
    residencies: dict | None = None,
    intersect_device_threshold: int = INTERSECT_DEVICE_THRESHOLD,
    readout: str = "device",
    defer: bool = False,
) -> FusedBatchResult | PendingBatch:
    """Serve one query batch, routing each (subquery, shard) work item over
    the device-resident posting arena when its keys are resident and through
    the host-pack path otherwise (DESIGN.md §13).

    ``work`` is the ``plan_query_batch`` cross product (items are
    ``(subquery, index[, keys])``); ``residencies`` maps ``id(view)`` to the
    :class:`~repro.search.arena.ArenaResidency` acquired for that view (no
    entry = host path for that view's items).  A fully resident batch is ONE
    arena dispatch; a fully host batch is ONE host dispatch; a mixed batch
    runs both and merges — never more than two device programs.

    Exactness contract: the returned per-query fragment sets are identical
    for every routing (arena, host, or mixed) and equal to the §10 oracle —
    the arena program reproduces the host pack's dedup, Step-1/Step-2 gates
    and rank cover bit-for-bit (``tests/test_arena.py``,
    ``tests/test_differential.py``).

    ``readout``/``defer`` forward to ``run_query_batch`` /
    ``run_arena_batch``: with ``defer=True`` the return value is a
    :class:`PendingBatch` whose device program(s) are submitted but not
    awaited — the §15.2 double-buffer hook the frontend pipeline rides.
    """
    from .arena import ArenaOverflow, plan_arena_batch, run_arena_batch

    global _DISPATCHES

    def stat_for(qi: int) -> QueryStats | None:
        if stats is None or isinstance(stats, QueryStats):
            return stats
        return stats[qi]

    sink = _PHASE_SINK
    host_work: list[list[tuple]] = [[] for _ in work]
    arena_items: list[tuple] = []
    arena_fallback: list[tuple[int, tuple]] = []
    t0 = time.perf_counter()
    for qi, items in enumerate(work):
        for item in items:
            sub, view = item[0], item[1]
            res = residencies.get(id(view)) if residencies else None
            if res is None:
                host_work[qi].append(item)
                continue
            keys = (
                list(item[2])
                if len(item) > 2 and item[2] is not None
                else select_keys(sub, view.fl)
            )
            st = stat_for(qi)
            extents = []
            for key in keys:
                ext = res.lookup(key.components)
                if ext is None:
                    break
                extents.append(ext)
            if len(extents) < len(keys):
                if st is not None:
                    # per-key units, like arena_hits: every key of the item
                    # is served by the host pack
                    st.arena_misses += len(keys)
                # carry the selected keys: the host pack accepts 3-tuples,
                # so key selection is not recomputed for the fallback
                host_work[qi].append((sub, view, keys))
                continue

            def account(hit=True, st=st, keys=keys, extents=extents):
                # §11 accounting parity with the host pack: the arena path
                # reads the same rows, just on the device.  ``hit=False``
                # records an overflow fallback — the keys resolved but the
                # batch executed on the host, which does its own counting.
                if st is None:
                    return
                if not hit:
                    st.arena_misses += len(keys)
                    return
                st.arena_hits += len(keys)
                for ext in extents:
                    st.postings_read += ext.n_rows
                    st.bytes_read += ext.n_rows * 4 * POSTING_WIDTH.get(
                        ext.family, 2
                    )

            # provably-empty short-circuits, mirroring the host pack
            # (extract_segment_events returning None):
            if (
                not keys
                or all(e.n_rows == 0 for e in extents)
                or (len(keys) >= 2 and any(e.n_rows == 0 for e in extents))
            ):
                account()
                if st is not None:
                    st.empty_subqueries += 1
                continue
            arena_items.append((qi, sub, keys, extents, res))
            # fallback bookkeeping: the (sub, view, keys) item for host
            # re-queueing (keys carried, not recomputed), the accounting
            # thunk applied ONLY if the arena plan succeeds (on
            # ArenaOverflow the host pack does its own counting — no double
            # charge, no phantom arena_hits)
            arena_fallback.append((qi, (sub, view, keys), account))

    results: list[FusedBatchResult] = []
    if arena_items:
        try:
            aplan = plan_arena_batch(arena_items, n_queries=len(work))
        except ArenaOverflow:
            aplan = None
            for qi, item3, account in arena_fallback:
                account(hit=False)
                host_work[qi].append(item3)
        if aplan is not None:
            for _qi, _item3, account in arena_fallback:
                account()
        # the arena's whole host side — routing + descriptor planning —
        # is the pack phase (there is no plan phase: no posting is read)
        t0 = _phase(sink, "pack_us", t0)
        if aplan is not None:
            results.append(
                run_arena_batch(
                    aplan,
                    max_distance=max_distance,
                    top_k=top_k,
                    use_kernel=use_kernel,
                    stats=batch_stats,
                    phases=sink,
                    readout=readout,
                    defer=defer,
                )
            )
            _DISPATCHES += 1
    if any(host_work):
        hplan = plan_query_batch(
            host_work,
            doc_len=doc_len,
            stats=stats,
            intersect_device_threshold=intersect_device_threshold,
        )
        if hplan is not None:
            results.append(
                run_query_batch(
                    hplan,
                    max_distance=max_distance,
                    top_k=top_k,
                    use_kernel=use_kernel,
                    compute_dtype=compute_dtype,
                    stats=batch_stats,
                    readout=readout,
                    defer=defer,
                )
            )
    n_queries = len(work)
    if not results:
        empty = empty_batch_result(n_queries, top_k)
        return PendingBatch(lambda: empty) if defer else empty
    if defer:
        pending = list(results)
        return PendingBatch(
            lambda: _merge_results(
                [p.result() for p in pending], n_queries, top_k
            )
        )
    return _merge_results(results, n_queries, top_k)
