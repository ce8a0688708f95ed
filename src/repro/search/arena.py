"""Device-resident posting arena: on-device gather/pack for the fused
pipeline (DESIGN.md §13).

The fused pipeline (§9) made the *device* side of serving one program per
query batch, but ``plan_query_batch`` still gathered posting slices, built
occurrence tables and packed padded event arrays **on the host in numpy for
every batch**.  For stop/FU-heavy batches — precisely the case the paper's
multi-component indexes exist to make fast (2009.02684), with the hot path
bounded by index reads rather than per-query assembly (2009.03679) — that
host phase plus the H2D copy dominated end-to-end latency while the device
sat idle.

This module moves the hot posting columns onto the device **once per index
generation** and does the gather/pack there:

* :class:`PostingArena` — a byte-budgeted LRU of device-resident posting
  families.  Per ``(generation token, shard)``, each §3 family's keys are
  transformed into **per-slot event streams**: for every key and component
  slot, the sorted-unique ``(doc, pos)`` pairs the slot contributes — the
  §10.4 ``Set`` events with the query-independent half of the host pack
  (delta resolution, within-slot dedup, the §4 sort) hoisted to upload
  time.  For stop-lemma (f,s,t) keys this also *shrinks* the transport:
  raw rows enumerate occurrence pairs (O(occ³) per document) while the
  distinct positions per slot are O(occ).  Streams are concatenated
  (``index.store.family_rows`` key order, every extent aligned to
  ``ARENA_BLOCK`` rows) into ONE field-major ``[2, rows]`` int32 device
  buffer per family (``kernels/gather.py`` says why field-major).  A
  commit/delete/compact bumps the generation token, so stale buffers become
  unreachable and age out by LRU (or are evicted eagerly through the
  ``IncrementalIndexer.subscribe`` mutation hook).

* :func:`plan_arena_batch` — per batch, the host ships only **descriptors**:
  per (query, subquery, shard) work item, per selected key, the slot
  extents plus (segment id, lemma id, Step-1/emit flags, multiplicities).
  No posting row is touched on the host; planning cost is O(keys), not
  O(postings).

* :func:`arena_serve_batch` — ONE jit'd device program per batch: the
  ``kernels/gather.py`` scalar-prefetch block gather slices the arena, then
  on-device sorts rebuild exactly the host pack's event pipeline — Step-1
  document alignment (distinct-key counting per candidate doc), cross-key
  event dedup, Step-2 multiplicity gate, the event-centric rank cover
  (binary search over the (row, lemma, pos)-sorted stream — the ``postab``
  content of §9.1 without materializing the ``[R, L, K]`` table, so no
  data-dependent K budget exists), then the SAME §14 scoring and per-query
  top-k stages as ``fused_serve_batch``.

Exactness contract: arena-path fragment sets are identical to the host-pack
path and therefore to the §10 oracle — the same dedup, the same Step-1/
Step-2 gates, the same cover identity, pinned by ``tests/test_arena.py`` and
the ``tests/test_differential.py`` §13 case across live mutation and
budget-forced partial residency.  Keys that are not resident (family
evicted under the byte budget) fall back transparently to the host-pack
path, as do batches whose packed int32 composites would overflow
(:class:`ArenaOverflow` — e.g. per-shard doc-id spaces beyond ~2^24).
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.postings import QueryStats, SearchResult
from ..index.builder import POSTING_WIDTH, IndexSet
from ..kernels.gather import ARENA_BLOCK, gather_blocks, gather_blocks_ref
from .fused import _assemble_fragments, bucket_pow2 as _bucket

__all__ = [
    "ARENA_BLOCK",
    "ArenaOverflow",
    "ArenaResidency",
    "KeyExtent",
    "PostingArena",
    "plan_arena_batch",
    "arena_serve_batch",
    "lower_arena_batch",
    "run_arena_batch",
]

# §3 families `IndexSet.key_postings` serves (ordinary/NSW never reach it)
_ARENA_FAMILIES = ("stop_single", "stop_pair", "pair", "triple")

_I32_MAX = np.int32(np.iinfo(np.int32).max)


class ArenaOverflow(RuntimeError):
    """A batch's packed composites would not fit the int32 bit budgets of
    the §13.4 device program (DESIGN.md §13.3).  Callers fall back to the
    host-pack path — exactness is never at stake, only the gather
    locality."""


class SlotExtent(NamedTuple):
    """One (key, slot) event stream's slice of its §3 family buffer
    (DESIGN.md §13.1)."""

    block_start: int  # first arena block of the extent
    n_events: int  # sorted-unique (doc, pos) pairs in the stream
    max_pos: int


class KeyExtent(NamedTuple):
    """One §6 key's arena residency (DESIGN.md §13.1): per-slot stream
    extents plus the upload-time statistics the planner needs to size
    budgets — and keep the §11 postings-read accounting exact — without
    reading a single row."""

    family: str
    n_rows: int  # raw §4 rows (the §11 postings-read accounting unit)
    n_docs: int  # distinct doc ids (slot-0 stream — every row contributes)
    max_doc: int
    slots: tuple  # SlotExtent per component slot


_ZERO_EXTENT = KeyExtent("", 0, 0, 0, ())


@dataclass
class _FamilyBuffer:
    """One resident (token, shard, family) upload."""

    buf: jax.Array  # [2, n_blocks_pow2 * BLOCK] int32 (doc; pos) streams
    extents: dict  # canonical key -> KeyExtent
    nbytes: int
    used_bytes: int  # live event rows; the rest is block and pow2 padding


@dataclass
class ArenaResidency:
    """The resident §3 families of one (generation token, shard) — the
    handle work items carry into ``serve_query_batch`` (DESIGN.md §13.2)."""

    token: object
    shard: int
    families: dict = field(default_factory=dict)  # fname -> _FamilyBuffer

    def lookup(self, components: tuple) -> KeyExtent | None:
        """Arena extent for a canonical key, mirroring
        ``IndexSet.key_postings`` dispatch exactly; ``None`` = the serving
        family is not resident (host fallback), a zero-row extent = the key
        is resident-but-absent (provably empty, no fallback needed)."""
        arity = len(components)
        if arity == 3:
            fams = ("triple",)
        elif arity == 2:
            # stop_pair precedes pair in key_postings; the two key spaces
            # are disjoint (stop/stop vs FU-anchored), so a hit in either is
            # authoritative, but proving ABSENCE needs both resident.
            fams = ("stop_pair", "pair")
        else:
            fams = ("stop_single",)
        for fname in fams:
            fb = self.families.get(fname)
            if fb is not None:
                ext = fb.extents.get(components)
                if ext is not None:
                    return ext
        if all(f in self.families for f in fams):
            return _ZERO_EXTENT
        return None

    def buffer(self, fname: str) -> jax.Array:
        return self.families[fname].buf


def _slot_streams(a: np.ndarray, width: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """Per-slot sorted-unique (doc, pos) event streams of one key's §4 rows
    — the query-independent half of ``extract_segment_events`` hoisted to
    upload time.  Slot ``s``'s position is the anchor position plus the
    slot's signed distance; real token positions are never negative, and
    distinct (doc, pos) pairs are what the host pack's ``np.unique``
    produces for the slot (DESIGN.md §13.1)."""
    doc = a[:, 0].astype(np.int64)
    out = []
    for s in range(width - 1):
        pos = a[:, 1].astype(np.int64)
        if s > 0:
            pos = pos + a[:, 1 + s]
        comp = np.unique((doc << 32) | pos)
        out.append(((comp >> 32).astype(np.int32), (comp & 0xFFFFFFFF).astype(np.int32)))
    return out


class PostingArena:
    """Byte-budgeted LRU of device-resident posting families (DESIGN.md
    §13.1).

    ``acquire`` is the only serving-path entry: it returns (uploading on
    first touch) the :class:`ArenaResidency` for a live index view under its
    generation token.  Warm acquires are dictionary hits; a token bump makes
    old entries unreachable and LRU reclaims them under the byte budget.
    Families that do not fit the budget are simply left non-resident —
    ``serve_query_batch`` routes their work items through the host pack, so
    residency is a pure locality optimization, never a correctness surface.
    """

    def __init__(self, budget_bytes: int = 256 << 20, block: int = ARENA_BLOCK):
        self.budget_bytes = int(budget_bytes)
        self.block = int(block)
        self._entries: OrderedDict[tuple, _FamilyBuffer] = OrderedDict()
        self._bytes = 0
        # entry keys refused under the CURRENT budget: not re-attempted
        # (re-building the host-side concat per batch would reintroduce the
        # per-batch O(postings) host work the arena exists to remove).  A
        # bounded FIFO, shared across callers — a token bump changes the
        # key, so stale refusals age out by generation or by capacity
        self._refused: OrderedDict[tuple, None] = OrderedDict()
        self._refused_cap = 512
        self._unsubscribers: list = []
        self._source_ids = 0  # monotonically unique view identities
        self.hits = 0  # warm family acquires
        self.misses = 0  # family uploads + budget refusals
        self.uploads = 0
        self.upload_bytes = 0  # H2D bytes spent on arena uploads
        self.upload_sec = 0.0  # host build + H2D wall time of those uploads
        self.evictions = 0
        # §14 fault-injection hook (DESIGN.md §14): when set, acquire
        # rounds fire the "arena.acquire" injection point; injected
        # pressure refuses the whole round (host fallback, fragments
        # identical) instead of erroring
        self.injector = None
        self.pressure_events = 0

    # ---- residency --------------------------------------------------------

    def acquire(self, view: IndexSet, token: object, shard: int = 0) -> ArenaResidency:
        """Resident families of ``view`` under ``token`` — uploads what is
        missing (and fits), touches what is warm.  O(families) dict work when
        warm; O(total postings) once per (token, shard) when cold."""
        return self.acquire_many([(view, token, shard)])[0]

    def acquire_many(self, specs: Sequence[tuple]) -> list[ArenaResidency]:
        """Residencies for a whole serving round — ``specs`` lists
        ``(view, token, shard)`` per live shard.  All of the round's entries
        are PINNED against each other's admissions: a budget smaller than
        the round's working set yields stable partial residency (some
        families non-resident, host fallback) instead of shards evicting one
        another's buffers and re-uploading every batch."""
        if self.injector is not None:
            from .resilience import InjectedFault

            try:
                self.injector.fire("arena.acquire")
            except InjectedFault:
                # injected device-memory pressure (§14): refuse the round —
                # empty residencies route every key through the host pack,
                # so fragments are identical, only locality degrades
                self.pressure_events += 1
                return [
                    ArenaResidency(token=token, shard=shard)
                    for _view, token, shard in specs
                ]
        # entry keys carry a per-VIEW identity stamped on first acquire:
        # generation tokens alone are not globally unique (every plain
        # IndexSet has token 0; two indexers can share (epoch, mutations)),
        # so a shared arena must never let one source's buffers answer for
        # another's.  The stamp is a monotone counter (never reused, unlike
        # id()), travels with the view object, and a recreated view (new
        # generation) simply gets a fresh stamp.
        def source_id(view) -> int:
            sid = getattr(view, "_arena_source_id", None)
            if sid is None:
                self._source_ids += 1
                sid = self._source_ids
                try:
                    view._arena_source_id = sid
                except AttributeError:  # __slots__ view: fall back to id()
                    sid = id(view)
            return sid

        sids = [source_id(view) for view, _token, _shard in specs]
        pinned = {
            (sid, token, shard, fname)
            for sid, (_view, token, shard) in zip(sids, specs)
            for fname in _ARENA_FAMILIES
        }
        out = []
        for sid, (view, token, shard) in zip(sids, specs):
            res = ArenaResidency(token=token, shard=shard)
            for fname in _ARENA_FAMILIES:
                key = (sid, token, shard, fname)
                fb = self._entries.get(key)
                if fb is not None:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    res.families[fname] = fb
                    continue
                self.misses += 1
                if key in self._refused:
                    continue
                fb = self._upload_family(view, fname)
                if fb is None:
                    continue
                if not self._admit(key, fb, pinned):
                    self._refused[key] = None
                    while len(self._refused) > self._refused_cap:
                        self._refused.popitem(last=False)
                    continue
                res.families[fname] = fb
            out.append(res)
        return out

    def _admit(self, key: tuple, fb: _FamilyBuffer, pinned: frozenset = frozenset()) -> bool:
        """Insert under the byte budget, evicting LRU entries (never the
        current round's ``pinned`` ones); refuse (and drop) an upload that
        cannot fit even after evicting everything evictable."""
        if fb.nbytes > self.budget_bytes:
            return False
        while self._bytes + fb.nbytes > self.budget_bytes:
            victim = next((k for k in self._entries if k not in pinned), None)
            if victim is None:
                return False
            old = self._entries.pop(victim)
            self._bytes -= old.nbytes
            self.evictions += 1
        self._entries[key] = fb
        self._bytes += fb.nbytes
        return True

    def _upload_family(self, view: IndexSet, fname: str) -> _FamilyBuffer | None:
        from ..index.store import family_rows

        t0 = time.perf_counter()
        width = POSTING_WIDTH[fname]
        mapping = getattr(view, fname)
        keys, arrays, _rows, _starts = family_rows(mapping, width)
        block = self.block
        chunks: list[np.ndarray] = []
        extents: dict = {}
        blk = 0
        used_rows = 0
        for k, a in zip(keys, arrays):
            n = len(a)
            if n == 0:
                extents[k] = KeyExtent(fname, 0, 0, 0, ())
                continue
            doc_col = a[:, 0]
            n_docs = 1 + int(np.count_nonzero(np.diff(doc_col)))
            slots = []
            for doc, pos in _slot_streams(a, width):
                ne = len(doc)
                n_blocks = -(-ne // block)
                pad = np.full((2, n_blocks * block), -1, np.int32)
                pad[0, :ne] = doc
                pad[1, :ne] = pos
                chunks.append(pad)
                used_rows += ne
                slots.append(
                    SlotExtent(blk, ne, int(pos.max()) if ne else 0)
                )
                blk += n_blocks
            extents[k] = KeyExtent(
                family=fname,
                n_rows=n,
                n_docs=n_docs,
                max_doc=int(doc_col[-1]),  # §4 order: doc column is sorted
                slots=tuple(slots),
            )
        # pow2 total blocks: arena buffer SHAPES bucket, so the serving
        # program's jit cache stays stable across generations (§9.2)
        total_blocks = 1 << max(0, (max(blk, 1) - 1).bit_length())
        concat = np.full((2, total_blocks * block), -1, np.int32)
        if chunks:
            cat = np.concatenate(chunks, axis=1)
            concat[:, : cat.shape[1]] = cat
        buf = jnp.asarray(concat).block_until_ready()
        self.uploads += 1
        self.upload_bytes += concat.nbytes
        self.upload_sec += time.perf_counter() - t0
        return _FamilyBuffer(
            buf=buf,
            extents=extents,
            nbytes=concat.nbytes,
            used_bytes=used_rows * concat.itemsize * concat.shape[0],
        )

    # ---- invalidation (generation hooks, DESIGN.md §13.2) ------------------

    def attach(self, source) -> None:
        """Subscribe eager eviction to an index source's mutation hook: on
        every commit/committed-delete/compact, entries whose token is no
        longer live for the source are dropped immediately instead of aging
        out by LRU.  Token-keyed residency is already correct without this
        (stale tokens are unreachable); attaching just returns the bytes
        sooner.  Attach one arena to one source (or sources sharing a token
        namespace); ``detach()`` removes the subscriptions (an arena that
        outlives its usefulness must detach, or the indexer's listener list
        keeps it alive)."""
        from ..index.incremental import IncrementalIndexer

        indexers = getattr(source, "indexers", None)
        if indexers is None and isinstance(source, IncrementalIndexer):
            indexers = [source]
        if not indexers:
            return

        # evict ONLY tokens this source previously served (tracked across
        # mutations), never unrelated sources' entries that happen to carry
        # a colliding token value — entry keys are (sid, token, shard,
        # family) and a shared arena may hold other sources' buffers
        prev_tokens = {ix.generation_token for ix in indexers}

        def _on_mutation(_ix) -> None:
            nonlocal prev_tokens
            live = {ix.generation_token for ix in indexers}
            stale = prev_tokens - live
            for key in [k for k in self._entries if k[1] in stale]:
                fb = self._entries.pop(key)
                self._bytes -= fb.nbytes
                self.evictions += 1
            prev_tokens = live

        for ix in indexers:
            self._unsubscribers.append(ix.subscribe(_on_mutation))

    def detach(self) -> None:
        """Remove every mutation subscription made by ``attach`` (DESIGN.md
        §13.2) — idempotent; the arena keeps working, invalidation reverts
        to token-keyed LRU aging."""
        for unsub in self._unsubscribers:
            unsub()
        self._unsubscribers = []

    def release(self) -> None:
        """Drop every resident buffer and refusal record (DESIGN.md §13.2)
        — the normal eviction path, so counters stay consistent.  For
        consumers done serving (benches, shutdown); the arena remains
        usable and re-uploads on the next acquire."""
        self.evictions += len(self._entries)
        self._entries.clear()
        self._bytes = 0
        self._refused.clear()

    # ---- introspection ----------------------------------------------------

    @property
    def size_bytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        return len(self._entries)

    def metrics(self) -> dict:
        lookups = self.hits + self.misses
        return {
            "arena_bytes": self._bytes,
            "arena_used_bytes": sum(fb.used_bytes for fb in self._entries.values()),
            "arena_entries": len(self._entries),
            "arena_hit_rate": self.hits / lookups if lookups else 0.0,
            "arena_hits": self.hits,
            "arena_misses": self.misses,
            "arena_uploads": self.uploads,
            "arena_upload_bytes": self.upload_bytes,
            "arena_upload_sec": self.upload_sec,
            "arena_evictions": self.evictions,
            "arena_pressure_events": self.pressure_events,
        }


# ---------------------------------------------------------------------------
# §13.3 descriptor planning (host side: O(keys), zero posting reads)
# ---------------------------------------------------------------------------


@dataclass
class ArenaBatchPlan:
    """Fixed-shape descriptor tensors for one arena device dispatch — the
    §13.3 descriptor ABI.  Everything here is O(work items + arena blocks);
    no posting row is ever materialized on the host.

    Descriptors reference (key, slot) event-stream extents.  Every key
    contributes its slot-0 stream as the Step-1 membership witness
    (``kd=1``: every §4 row has a slot-0 event, so the stream's doc set IS
    the key's doc set); streams of unstarred slots additionally emit events
    (``emit=1``).  Two ABI forms ride in one plan: the block-aligned form
    steers the Pallas gather kernel's DMA, the dense form packs extents
    back-to-back for the jnp gather so the event budget tracks real rows.
    """

    # one gather GROUP per (residency, family) pair — distinct shards keep
    # distinct device buffers even for the same family name
    families: tuple  # static: group labels (fname per group)
    buffers: list  # per group: device buffer (resident, NOT per-batch H2D)
    # block-aligned form, consumed by the Pallas gather (use_kernel=True):
    src: list  # per group: [Gg] int32 arena block index per output block
    nv: list  # per group: [Gg] int32 live rows per output block
    blk_meta: list  # per group: [Gg, 5] int32 (seg, lem, kd, emit, key)
    # dense form, consumed by the jnp gather (no block padding):
    d_src: list  # per group: [Dg] int32 first arena ROW of each descriptor
    d_n: list  # per group: [Dg] int32 events per descriptor
    d_dest: list  # per group: [Dg] int32 dense output offset (cumsum of d_n)
    d_meta: list  # per group: [Dg, 5] int32 (seg, lem, kd, emit, key)
    e_budget: list  # per group: pow2 dense event budget
    n_keys: np.ndarray  # [S] int32
    mult: np.ndarray  # [S, L] int32
    seg_query: np.ndarray  # [S] int32
    n_queries: int
    query_budget: int
    n_budget: int  # position budget (pow2)
    row_budget: int  # candidate-row budget (pow2)
    lemma_budget: int  # pow2
    key_budget: int  # keys-per-work-item budget (pow2)
    doc_bits: int  # bit width of the largest doc id in the batch
    tier: str  # "pack32" (one fused sort) or "argsort" (wide doc ids)
    block: int
    n_events: int  # gathered stream events (pre-padding), for accounting


def plan_arena_batch(
    items: Sequence[tuple],
    *,
    n_queries: int,
    block: int = ARENA_BLOCK,
) -> ArenaBatchPlan | None:
    """Pack arena-resident work items into one device program's descriptors
    — the §13.3 descriptor ABI (the host-side half of the §10.4 event
    pipeline, reduced to extent arithmetic).

    ``items`` are ``(query_index, subquery, keys, extents, residency)``
    tuples whose keys ALL resolved to arena extents (``serve_query_batch``
    does the split and the empty-work short-circuits).  Returns ``None``
    when nothing would be gathered; raises :class:`ArenaOverflow` when the
    packed int32 composites cannot hold this batch.
    """
    if not items:
        return None
    # gather groups keyed by (residency identity, family): items from
    # different shards never share a group even for the same family name
    fam_desc: dict[tuple, list] = {}
    group_buf: dict[tuple, object] = {}
    n_keys = np.zeros(len(items), np.int32)
    seg_query = np.full(len(items), -1, np.int32)
    max_l = 1
    max_pos = 0
    max_doc = 0
    row_bound = 0
    n_events = 0
    mult_rows: list[np.ndarray] = []
    for seg, (qi, sub, keys, extents, res) in enumerate(items):
        lemmas = sub.unique_lemmas()
        lid = {l: i for i, l in enumerate(lemmas)}
        mult_map = sub.multiplicity()
        mult_rows.append(np.array([mult_map[l] for l in lemmas], np.int32))
        max_l = max(max_l, len(lemmas))
        seg_query[seg] = qi
        n_keys[seg] = len(keys)
        for key_local, (key, ext) in enumerate(zip(keys, extents)):
            # group order must be DETERMINISTIC across rounds (it shapes the
            # static argument tuple of arena_serve_batch, i.e. the jit cache
            # key): order by (shard, family); id() only breaks the
            # pathological tie of two residencies claiming one shard
            gkey = (res.shard, ext.family, id(res))
            group_buf.setdefault(gkey, res.buffer(ext.family))
            max_doc = max(max_doc, ext.max_doc)
            row_bound += ext.n_docs
            unstarred = {s for s, _ in key.active_components()}
            for slot, se in enumerate(ext.slots):
                kd = 1 if slot == 0 else 0
                emit = 1 if slot in unstarred else 0
                if not (kd or emit) or se.n_events == 0:
                    continue
                if emit:
                    max_pos = max(max_pos, se.max_pos)
                n_events += se.n_events
                fam_desc.setdefault(gkey, []).append(
                    (
                        se.block_start,
                        se.n_events,
                        seg,
                        lid[key.components[slot]] if emit else 0,
                        kd,
                        emit,
                        key_local,
                    )
                )
    if not fam_desc:
        return None

    # ---- int32 composite bit budgets (x64 stays off on device) -----------
    n_budget = _bucket(max_pos + 1, lo=64)
    lemma_budget = _bucket(max_l, lo=2)
    s_budget = _bucket(len(items))
    key_budget = _bucket(int(n_keys.max()))
    row_budget = _bucket(min(max(row_bound, 1), max(n_events, 1)), lo=8)
    rb = max((row_budget - 1).bit_length(), 1)
    nb = (n_budget - 1).bit_length()
    lb = max((lemma_budget - 1).bit_length(), 1)
    sb = max((s_budget - 1).bit_length(), 1)
    kb = max((key_budget - 1).bit_length(), 1)
    db = max(int(max_doc).bit_length(), 1)
    if rb + nb + lb > 30:
        raise ArenaOverflow(
            f"dedup composite bits {rb}+{nb}+{lb} > 30 (rows={row_budget}, "
            f"positions={n_budget}, lemmas={lemma_budget})"
        )
    # one fused (seg, doc, key, kd, emit, pos, lemma) sort when everything
    # fits int32; wide doc-id spaces drop pos/lemma from the sort key and
    # pay payload gathers instead; wider still -> host-pack fallback
    if sb + db + kb + 2 + nb + lb <= 30:
        tier = "pack32"
    elif sb + db + kb + 2 <= 30:
        tier = "argsort"
    else:
        raise ArenaOverflow(
            f"row-group bits {sb}+{db}+{kb}+2 > 30 (doc ids up to {max_doc}; "
            f"wider per-shard doc spaces take the host path)"
        )

    group_keys = sorted(fam_desc, key=lambda gk: gk[:2])
    families = tuple(gk[1] for gk in group_keys)
    buffers = [group_buf[gk] for gk in group_keys]
    src: list = []
    nv: list = []
    blk_meta: list = []
    d_src: list = []
    d_n_d: list = []
    d_dest: list = []
    d_meta_d: list = []
    e_budget: list = []
    for gk in group_keys:
        descs = fam_desc[gk]
        d_bstart = np.asarray([d[0] for d in descs], np.int64)
        d_n = np.asarray([d[1] for d in descs], np.int64)
        d_meta = np.asarray([d[2:] for d in descs], np.int32)  # [D, 5]
        nblk = np.maximum(1, -(-d_n // block))
        g = _bucket(int(nblk.sum()))
        total = int(nblk.sum())
        # vectorized block expansion: block j of descriptor d reads arena
        # block bstart[d] + j and holds min(block, n[d] - j*block) live rows
        desc_of = np.repeat(np.arange(len(descs)), nblk)
        starts = np.zeros(len(descs), np.int64)
        np.cumsum(nblk[:-1], out=starts[1:])
        within = np.arange(total, dtype=np.int64) - np.repeat(starts, nblk)
        pad = g - total
        src.append(np.concatenate(
            [(d_bstart[desc_of] + within).astype(np.int32), np.zeros(pad, np.int32)]
        ))
        nv.append(np.concatenate(
            [
                np.minimum(block, d_n[desc_of] - within * block).astype(np.int32),
                np.zeros(pad, np.int32),
            ]
        ))
        blk_meta.append(np.concatenate(
            [d_meta[desc_of], np.tile(np.array([[-1, 0, 0, 0, 0]], np.int32), (pad, 1))]
        ))
        # dense form: descriptor extents packed back to back, descriptor
        # table pow2-padded (zero-row pads), event budget = bucket(real rows)
        d = _bucket(len(descs))
        dest = np.zeros(len(descs), np.int64)
        np.cumsum(d_n[:-1], out=dest[1:])
        e_budget.append(_bucket(int(d_n.sum()), lo=block))
        d_src.append(np.concatenate(
            [(d_bstart * block).astype(np.int32), np.zeros(d - len(descs), np.int32)]
        ))
        d_n_d.append(np.concatenate(
            [d_n.astype(np.int32), np.zeros(d - len(descs), np.int32)]
        ))
        d_dest.append(np.concatenate(
            [dest.astype(np.int32), np.full(d - len(descs), int(d_n.sum()), np.int32)]
        ))
        d_meta_d.append(np.concatenate(
            [d_meta, np.tile(np.array([[-1, 0, 0, 0, 0]], np.int32), (d - len(descs), 1))]
        ))

    mult = np.zeros((s_budget, lemma_budget), np.int32)
    for seg, row in enumerate(mult_rows):
        mult[seg, : len(row)] = row
    n_keys_p = np.zeros(s_budget, np.int32)
    n_keys_p[: len(items)] = n_keys
    seg_query_p = np.full(s_budget, -1, np.int32)
    seg_query_p[: len(items)] = seg_query

    return ArenaBatchPlan(
        families=families,
        buffers=buffers,
        src=src,
        nv=nv,
        blk_meta=blk_meta,
        d_src=d_src,
        d_n=d_n_d,
        d_dest=d_dest,
        d_meta=d_meta_d,
        e_budget=e_budget,
        n_keys=n_keys_p,
        mult=mult,
        seg_query=seg_query_p,
        n_queries=n_queries,
        query_budget=_bucket(n_queries),
        n_budget=n_budget,
        row_budget=row_budget,
        lemma_budget=lemma_budget,
        key_budget=key_budget,
        doc_bits=db,
        tier=tier,
        block=block,
        n_events=n_events,
    )


# ---------------------------------------------------------------------------
# §13.4 the arena device program (gather -> pack -> cover -> score -> top-k)
# ---------------------------------------------------------------------------


def _binary_search(a: jax.Array, v: jax.Array, right: bool) -> jax.Array:
    """``searchsorted`` over sorted int32 ``a`` as a static log2(n) gather
    loop — the device form of the §9.3 binary search, measurably faster on
    CPU than ``jnp.searchsorted`` and trivially TPU-mappable (each step is
    one gather + compare over the query tensor)."""
    n = a.shape[0]
    lo = jnp.zeros(v.shape, jnp.int32)
    step = 1 << max(0, (n - 1).bit_length())
    while step > 1:
        step //= 2
        probe = a[jnp.minimum(lo + step - 1, n - 1)]
        go = (probe <= v) if right else (probe < v)
        lo = jnp.where(go, lo + step, lo)
    probe = a[jnp.minimum(lo, n - 1)]
    go = (probe <= v) if right else (probe < v)
    return lo + go.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "families",
        "e_budgets",
        "block",
        "max_distance",
        "query_budget",
        "n_budget",
        "row_budget",
        "lemma_budget",
        "s_budget",
        "key_budget",
        "doc_bits",
        "tier",
        "top_k",
        "use_kernel",
    ),
)
def arena_serve_batch(
    buffers: tuple,  # per-family arena buffer, order = `families`
    gather_args: tuple,  # per-family descriptor arrays (form picked by
    #   use_kernel: block-aligned (src, nv, meta[G,5]) for the Pallas
    #   gather; dense (src_row, n, dest, meta[D,5]) for the jnp form)
    n_keys: jax.Array,  # [S] int32
    mult: jax.Array,  # [S, L] int32
    seg_query: jax.Array,  # [S] int32
    *,
    families: tuple,
    e_budgets: tuple,  # per-family dense event budgets (jnp form)
    block: int,
    max_distance: int,
    query_budget: int,
    n_budget: int,
    row_budget: int,
    lemma_budget: int,
    s_budget: int,
    key_budget: int,
    doc_bits: int,
    tier: str,
    top_k: int = 16,
    use_kernel: bool = False,
):
    """One device program for an arena-resident query batch (DESIGN.md
    §13.4) — the on-device form of ``extract_segment_events`` +
    ``plan_query_batch`` + ``fused_serve_batch``:

    stage 0  block gather: ``kernels/gather.py`` slices every descriptor's
             arena extent into one (doc, pos) event workspace (Pallas
             scalar-prefetch kernel with ``use_kernel=True``, its dense jnp
             form otherwise — identical fragments either way);
    stage 1  one packed sort groups events by (segment, doc): dense
             candidate-row ids + Step-1 document alignment (distinct-key
             counting over each key's slot-0 stream keeps docs present in
             EVERY key iterator);
    stage 2  cross-key event dedup to one (doc, pos, lemma) + the Step-2
             multiplicity gate — exactly the host pack's ``np.unique`` +
             ``bincount`` gates;
    stage 3  event-centric rank cover: binary search over the (row, lemma,
             pos)-sorted stream replaces the §9 ``postab`` gather (same
             rank identity, no ``[R, L, K]`` materialization);
    stage 4  §14 scoring + per-query top-k — the same stages as
             ``fused_serve_batch``.

    Returns the §15.1 dense result buffer ``res`` (sorted unique
    ``(q, doc, start, end)`` rows plus per-query counts — the device
    readout's ONE fixed-shape D2H copy) alongside the per-event
    ``emit``/``start`` (aligned to the returned sorted ``comp`` stream) and
    the row maps the legacy host readout decodes fragments from.  Fragment
    sets are byte-identical to the host-pack path.
    """
    nb = (n_budget - 1).bit_length()
    lb = max((lemma_budget - 1).bit_length(), 1)
    kb = max((key_budget - 1).bit_length(), 1)
    db = doc_bits
    window = 2 * max_distance + 1

    # ---- stage 0: gather the (doc, pos) event streams ---------------------
    # Per-event streams are kept 1-D or field-major ([5, E], [L, E]): the
    # TPU tiles an array's two minor dims by (8, 128), so an event-major
    # [E, 5] or [E, L] temporary would pad its narrow minor dim to 128 lanes
    # (25-32x the HBM) and exhaust the chip at corpus scale.
    doc_l, pos_l, seg_l, lem_l, kd_l, em_l, key_l = [], [], [], [], [], [], []
    for fi, _fname in enumerate(families):
        if use_kernel:
            # block-aligned Pallas gather (scalar-prefetched DMA steering)
            f_src, f_nv, meta_b = gather_args[fi]
            doc_f, pos_f = gather_blocks(buffers[fi], f_src, f_nv, block=block)
            meta = jnp.repeat(meta_b.T, block, axis=1)  # [5, G*B]
        else:
            # dense jnp gather: descriptor extents pack back to back, so the
            # event budget tracks REAL rows (no per-extent block padding)
            d_srcrow, d_n, d_dest, d_meta = gather_args[fi]
            iota = jnp.arange(e_budgets[fi], dtype=jnp.int32)
            desc = _binary_search(d_dest, iota, right=True) - 1
            desc = jnp.clip(desc, 0, d_dest.shape[0] - 1)
            within = iota - d_dest[desc]
            alive = within < d_n[desc]
            srcrow = jnp.clip(d_srcrow[desc] + within, 0, buffers[fi].shape[1] - 1)
            doc_f = jnp.where(alive, buffers[fi][0, srcrow], jnp.int32(-1))
            pos_f = jnp.where(alive, buffers[fi][1, srcrow], jnp.int32(-1))
            meta = d_meta.T[:, desc]  # [5, E]
        doc_l.append(doc_f)
        pos_l.append(pos_f)
        seg_l.append(meta[0])
        lem_l.append(meta[1])
        kd_l.append(meta[2])
        em_l.append(meta[3])
        key_l.append(meta[4])
    doc = jnp.concatenate(doc_l)
    pos = jnp.concatenate(pos_l)
    seg = jnp.concatenate(seg_l)
    lem = jnp.concatenate(lem_l)
    kd = jnp.concatenate(kd_l)
    emit_f = jnp.concatenate(em_l)
    key = jnp.concatenate(key_l)
    e = doc.shape[0]
    valid0 = (doc >= 0) & (seg >= 0)

    # ---- stage 1: one packed sort -> (seg, doc) rows + Step-1 gate --------
    # Composite layout (high -> low): seg | doc | key | kd-inverted | emit
    # | pos | lemma.  kd streams (slot 0) sort to the head of each
    # (seg, doc, key) group, so group-first & kd counts every key exactly
    # once per candidate doc — the §10.1 Step-1 iterator alignment as a
    # segmented count.  Invalid elements carry the int32 sentinel and sort
    # last.  ``tier`` picks one fused sort (everything fits 30 bits) or an
    # argsort + payload gathers (wide per-shard doc-id spaces).
    pos_c = jnp.where(emit_f > 0, pos, 0)
    head = ((((seg << db) | doc) << kb) | key) << 1 | (1 - kd)
    if tier == "pack32":
        pack = ((((head << 1) | emit_f) << nb) | pos_c) << lb | lem
        pack = jnp.where(valid0, pack, _I32_MAX)
        pack = jnp.sort(pack)
        fin1 = pack < _I32_MAX
        lem_s = pack & (lemma_budget - 1)
        pos_s = (pack >> lb) & (n_budget - 1)
        em_s = ((pack >> (lb + nb)) & 1) > 0
        head_s = pack >> (lb + nb + 1)
    else:  # "argsort"
        hkey = jnp.where(valid0, head, _I32_MAX)
        perm = jnp.argsort(hkey)
        head_s = hkey[perm]
        fin1 = head_s < _I32_MAX
        pos_s = pos_c[perm]
        em_s = emit_f[perm] > 0
        lem_s = lem[perm]
    kd_s = (head_s & 1) == 0  # kd-inverted bit
    sd = head_s >> (kb + 1)  # (seg, doc) group id
    grp_key = head_s >> 1  # (seg, doc, key) group id
    prev_sd = jnp.concatenate([jnp.array([-1], jnp.int32), sd[:-1]])
    prev_gk = jnp.concatenate([jnp.array([-1], jnp.int32), grp_key[:-1]])
    new_row = fin1 & (sd != prev_sd)
    row_id = jnp.where(fin1, jnp.cumsum(new_row.astype(jnp.int32)) - 1, row_budget)
    row_idc = jnp.clip(row_id, 0, row_budget - 1)
    # row boundaries: row_id is sorted, so per-row ranges come from binary
    # search instead of scatters (rows are contiguous runs of the sort)
    r_iota = jnp.arange(row_budget, dtype=jnp.int32)
    row_lo = _binary_search(row_id, r_iota, right=False)
    row_hi = _binary_search(row_id, r_iota, right=True)
    row_used = row_lo < row_hi
    row_lo_c = jnp.minimum(row_lo, e - 1)
    row_seg = jnp.where(row_used, sd[row_lo_c] >> db, 0)
    row_doc = jnp.where(row_used, sd[row_lo_c] & ((1 << db) - 1), -1)
    row_seg_c = jnp.clip(row_seg, 0, s_budget - 1)
    # Step-1: distinct keys present per (seg, doc) == the work item's key
    # count (single-key items skip the gate, as the host pack does)
    kd_first = fin1 & kd_s & (grp_key != prev_gk)
    cum_kd = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(kd_first.astype(jnp.int32))]
    )
    key_count = cum_kd[row_hi] - cum_kd[row_lo]
    need = n_keys[row_seg_c]
    row_pass = row_used & ((need < 2) | (key_count >= need))

    # ---- stage 2: dedup to one (doc, pos, lemma) + Step-2 gate ------------
    keep = fin1 & em_s & (pos_s < n_budget) & row_pass[row_idc]
    comp = (((row_idc << nb) | pos_s) << lb) | lem_s
    comp = jnp.where(keep, comp, _I32_MAX)
    comp = jnp.sort(comp)
    fin = comp < _I32_MAX
    prev = jnp.concatenate([jnp.array([-1], jnp.int32), comp[:-1]])
    uniq = fin & (comp != prev)
    lem2 = comp & (lemma_budget - 1)
    pos2 = (comp >> lb) & (n_budget - 1)
    row2 = jnp.clip(comp >> (lb + nb), 0, row_budget - 1)

    # ---- stage 3: the (row, lemma, pos)-sorted stream IS the §9.1 postab --
    cov = (((row2 << lb) | lem2) << nb) | pos2
    cov = jnp.where(uniq, cov, _I32_MAX)
    cov = jnp.sort(cov)
    # per-(row, lemma) group bounds once (small), reused by Step-2 and the
    # per-event cover; `cov` holds deduped events only, so range sizes are
    # exactly the distinct-position counts the host pack bincounts
    # One loop step per lemma slot over 1-D [R] / [E] streams: a [R, L] or
    # [E, L] tensor would pad its narrow lemma dim to 128 lanes on the TPU
    # (stage 0), and XLA lays the transposed [L, E] gathers out the same way.
    mult_t = mult.T  # [L, S] (0 = unused slot, trivially passes)

    def lemma_rows(l):
        """Per-row ``(group base key, first index in cov, mult)`` of lemma
        slot ``l``."""
        grp = ((r_iota << lb) | l) << nb
        return grp, _binary_search(cov, grp, right=False), mult_t[l][row_seg_c]

    def row_gate(l, ok):
        grp, lo, m = lemma_rows(l)
        cnt = _binary_search(cov, grp | (n_budget - 1), right=True) - lo
        return ok & (cnt >= m)

    ok_row = jax.lax.fori_loop(0, lemma_budget, row_gate, row_used)
    live = uniq & ok_row[row2]

    # event-centric rank cover (§9.3 identity): for event (row, pos) and
    # lemma l, cnt = occurrences of l at or before pos; the fragment start
    # is the mult-th latest, gathered straight from the sorted stream
    def event_cover(l, carry):
        start, all_have, any_active = carry
        _grp, lo_r, m_r = lemma_rows(l)
        grp_e = ((row2 << lb) | l) << nb
        hi_e = _binary_search(cov, grp_e | pos2, right=True)
        lo_e = lo_r[row2]
        cnt = hi_e - lo_e
        mult_e = m_r[row2]
        active = mult_e > 0
        have = cnt >= mult_e
        sel = jnp.clip(lo_e + cnt - mult_e, 0, e - 1)
        p_sel = cov[sel] & (n_budget - 1)
        p_sel = jnp.where(active & have, p_sel, n_budget)
        return (
            jnp.minimum(start, p_sel),
            all_have & (have | ~active),
            any_active | active,
        )

    start, all_have, any_active = jax.lax.fori_loop(
        0,
        lemma_budget,
        event_cover,
        (
            jnp.full((e,), n_budget, jnp.int32),
            jnp.ones((e,), jnp.bool_),
            jnp.zeros((e,), jnp.bool_),
        ),
    )
    covered = all_have & any_active
    emit = live & covered & (start < n_budget) & (pos2 - start < window)
    start = jnp.where(emit, start, pos2)

    # ---- stage 4: §14 scoring + per-query top-k (as fused_serve_batch) ----
    pp = comp >> lb
    prev_pp = jnp.concatenate([jnp.array([-1], jnp.int32), pp[:-1]])
    primary = fin & (pp != prev_pp)
    emit_primary = emit & primary
    span = (pos2 - start).astype(jnp.float32)
    contrib = jnp.where(emit_primary, 1.0 / (span + 1.0) ** 2, 0.0)
    # per-row reductions via prefix sums over the row-sorted stream (`comp`
    # groups rows contiguously) — no [E]->[R] scatters on the hot path
    crow = jnp.where(fin, comp >> (lb + nb), row_budget)
    c_lo = _binary_search(crow, r_iota, right=False)
    c_hi = _binary_search(crow, r_iota, right=True)
    cum_scores = jnp.concatenate(
        [jnp.zeros((1,), jnp.float32), jnp.cumsum(contrib)]
    )
    scores = cum_scores[c_hi] - cum_scores[c_lo]
    scores = jnp.where(ok_row & (row_doc >= 0), scores, -jnp.inf)
    row_query = jnp.where(row_used, seg_query[row_seg_c], -1)
    qids = jax.lax.broadcasted_iota(jnp.int32, (query_budget, 1), 0)
    scores_q = jnp.where(row_query[None, :] == qids, scores[None, :], -jnp.inf)
    kk = min(top_k, row_budget)
    top_scores, idx = jax.lax.top_k(scores_q, kk)
    top_docs = jnp.where(jnp.isfinite(top_scores), row_doc[idx], -1)

    cum_frag = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(emit_primary.astype(jnp.int32))]
    )
    frag_per_row = cum_frag[c_hi] - cum_frag[c_lo]
    n_fragments = (
        jnp.zeros((query_budget,), jnp.int32)
        .at[jnp.clip(row_query, 0, query_budget - 1)]
        .add(jnp.where(row_query >= 0, frag_per_row, 0))
    )

    # §15.1 device-side result assembly over the deduped event stream —
    # identical dedup + output order to the fused host pack's buffer
    ev_q = row_query[row2]
    ev_d = row_doc[row2]
    frag_valid = emit_primary & (ev_q >= 0) & (ev_d >= 0)
    res = _assemble_fragments(ev_q, ev_d, start, pos2, frag_valid, query_budget)

    return {
        "emit": emit_primary,
        "start": start,
        "comp": comp,
        "row_doc": row_doc,
        "row_query": row_query,
        "res": res,
        "top_docs": top_docs,
        "top_scores": top_scores,
        "n_fragments": n_fragments,
    }


def _device_args(plan: ArenaBatchPlan, use_kernel: bool):
    """Assemble ONE arena program's device arguments from a plan.

    Returns ``(args, h2d_bytes)`` where ``args`` matches the positional
    signature of :func:`arena_serve_batch` and ``h2d_bytes`` counts the
    descriptor bytes enqueued host-to-device (the resident posting buffers
    themselves never move — that's the point of the arena, §13.1).  Shared
    by :func:`run_arena_batch` and :func:`lower_arena_batch` so the HLO
    captured for the §15.4 roofline is the program that actually serves.
    """
    groups = range(len(plan.families))
    if use_kernel:
        gather_args = tuple(
            (
                jnp.asarray(plan.src[g]),
                jnp.asarray(plan.nv[g]),
                jnp.asarray(plan.blk_meta[g]),
            )
            for g in groups
        )
        h2d = sum(
            plan.src[g].nbytes + plan.nv[g].nbytes + plan.blk_meta[g].nbytes
            for g in groups
        )
    else:
        gather_args = tuple(
            (
                jnp.asarray(plan.d_src[g]),
                jnp.asarray(plan.d_n[g]),
                jnp.asarray(plan.d_dest[g]),
                jnp.asarray(plan.d_meta[g]),
            )
            for g in groups
        )
        h2d = sum(
            plan.d_src[g].nbytes * 3 + plan.d_meta[g].nbytes for g in groups
        )
    args = (
        tuple(plan.buffers[g] for g in groups),
        gather_args,
        jnp.asarray(plan.n_keys),
        jnp.asarray(plan.mult),
        jnp.asarray(plan.seg_query),
    )
    h2d += plan.n_keys.nbytes + plan.mult.nbytes + plan.seg_query.nbytes
    return args, h2d


def _static_kwargs(
    plan: ArenaBatchPlan,
    *,
    max_distance: int,
    top_k: int,
    use_kernel: bool,
) -> dict:
    """Static (jit-cache-keyed) kwargs of :func:`arena_serve_batch` for a
    plan — the shape/config half of the program's signature."""
    return dict(
        families=plan.families,
        e_budgets=tuple(plan.e_budget),
        block=plan.block,
        max_distance=max_distance,
        query_budget=plan.query_budget,
        n_budget=plan.n_budget,
        row_budget=plan.row_budget,
        lemma_budget=plan.lemma_budget,
        s_budget=len(plan.n_keys),
        key_budget=plan.key_budget,
        doc_bits=plan.doc_bits,
        tier=plan.tier,
        top_k=top_k,
        use_kernel=use_kernel,
    )


def lower_arena_batch(
    plan: ArenaBatchPlan,
    *,
    max_distance: int,
    top_k: int = 16,
    use_kernel: bool = False,
):
    """Lower ONE arena device program WITHOUT dispatching it (DESIGN.md
    §15.4).  Returns the jax ``Lowered`` object; callers compile it and feed
    ``.as_text()`` to ``launch/hlo_analysis.analyze_hlo`` for the serving
    roofline (``benchmarks/paper_tables.bench_roofline``)."""
    args, _ = _device_args(plan, use_kernel)
    return arena_serve_batch.lower(
        *args,
        **_static_kwargs(
            plan,
            max_distance=max_distance,
            top_k=top_k,
            use_kernel=use_kernel,
        ),
    )


def run_arena_batch(
    plan: ArenaBatchPlan,
    *,
    max_distance: int,
    top_k: int = 16,
    use_kernel: bool = False,
    stats: QueryStats | None = None,
    phases: dict | None = None,
    readout: str = "device",
    defer: bool = False,
):
    """Dispatch ONE arena device program and read results out (DESIGN.md
    §13.4).  The readout mirrors ``run_query_batch``: ``readout="device"``
    splits the §15.1 device-assembled result buffer (one fixed-shape D2H
    copy); ``readout="host"`` keeps the legacy ``np.nonzero`` +
    two-tier dedup over the event stream as the differential reference.
    ``defer=True`` returns a :class:`~repro.search.fused.PendingBatch`
    right after submit (§15.2).  Fragment sets are byte-identical to the
    host-pack path (``tests/test_arena.py``)."""
    from .fused import (
        FusedBatchResult,
        PendingBatch,
        _dedup_fragments,
        _split_result_buffer,
    )

    if readout not in ("device", "host"):
        raise ValueError(f"unknown readout mode: {readout!r}")
    t0 = time.perf_counter()
    args, h2d = _device_args(plan, use_kernel)
    if stats is not None:
        stats.h2d_bytes += h2d
    # enqueue time only — the premature block_until_ready(args[1:]) that
    # used to sit here forced a full descriptor H2D sync into the dispatch
    # window (the fused path's twin of the same bug)
    if phases is not None:
        phases.setdefault("h2d_us", []).append((time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter()
    out = arena_serve_batch(
        *args,
        **_static_kwargs(
            plan,
            max_distance=max_distance,
            top_k=top_k,
            use_kernel=use_kernel,
        ),
    )
    if stats is not None:
        stats.device_dispatches += 1
    if phases is not None:
        phases.setdefault("dispatch_us", []).append((time.perf_counter() - t0) * 1e6)

    nq = plan.n_queries

    def finalize():
        t1 = time.perf_counter()
        if phases is not None:
            # bench-only barrier: device time goes to compute_us, not to
            # whichever phase bracket encloses the first fetch
            jax.block_until_ready(out)
            now = time.perf_counter()
            phases.setdefault("compute_us", []).append((now - t1) * 1e6)
            t2 = now
        else:
            t2 = t1
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
            nb = (plan.n_budget - 1).bit_length()
            lb = max((plan.lemma_budget - 1).bit_length(), 1)
            emit = np.asarray(out["emit"])
            (hits,) = np.nonzero(emit)
            comp = np.asarray(out["comp"])[hits].astype(np.int64)
            starts = np.asarray(out["start"])[hits].astype(np.int64)
            ends = (comp >> lb) & (plan.n_budget - 1)
            rows = comp >> (lb + nb)
            row_doc = np.asarray(out["row_doc"]).astype(np.int64)
            row_query = np.asarray(out["row_query"]).astype(np.int64)
            docs = row_doc[rows]
            q_of = row_query[rows]
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
        if phases is not None:
            phases.setdefault("readout_us", []).append(
                (time.perf_counter() - t2) * 1e6
            )
        return result

    if defer:
        return PendingBatch(finalize)
    return finalize()
