"""Program spans on the profiler's clock (DESIGN.md §15.3).

* The span helper: a span that names a batch sets it for its body, nested
  spans carry it, the recorder sees every span, and ``collect_phases``
  keeps the six §15.3 phases of them.
* A ``ServiceDaemon`` over a tiny index, served inside
  ``jax.profiler.start_trace``: every span of the serving path appears in
  the trace with its batch id, on the daemon's thread line, nested in the
  batch's ``daemon.launch`` or ``daemon.retire``.
* A slate of more than ``max_batch`` requests: a span's ``slot`` is the
  request's index in the slate, across chunks and duplicates.
"""

from __future__ import annotations

from collections import defaultdict

import jax
import pytest

from repro.runtime import spans
from repro.runtime.spans import span
from repro.search import fused
from repro.search.arena import PostingArena
from repro.search.frontend import SearchRequest, ServingFrontend
from repro.search.service import ServiceDaemon

QUERIES = [
    "who are you who",
    "to be or not to be",
    "what do you do all day",
    "the time of war",
    "i need you",
    "time and time again",
    "you are who you are",
    "all of the time",
]
# each slate is one batch: the first replica serves it from the arena,
# the second (no arena) through the host pack
BATCH = 4

LAUNCH_PHASES = {"frontend.plan", "planner.plan", "serve.plan", "serve.pack", "serve.h2d", "serve.dispatch"}
RETIRE_PHASES = {"serve.device_wait", "serve.readout", "frontend.rank"}
ALL_SPANS = {"daemon.launch", "daemon.retire"} | LAUNCH_PHASES | RETIRE_PHASES


def test_a_batch_span_sets_the_batch_for_its_body():
    assert spans._BATCH.get() is None
    with span("daemon.launch", batch=7):
        assert spans._BATCH.get() == 7
        with span("serve.pack") as inner:
            assert spans._BATCH.get() == 7
        with span("daemon.retire", batch=8):
            assert spans._BATCH.get() == 8
        assert spans._BATCH.get() == 7
    assert spans._BATCH.get() is None
    assert "batch" not in inner.meta  # the span's own arguments are left as given


def test_the_recorder_sees_every_span():
    seen = []
    prev = spans.set_recorder(lambda name, sec: seen.append((name, sec)))
    try:
        with span("daemon.launch", batch=0):
            with span("serve.pack", path="arena"):
                pass
    finally:
        spans.set_recorder(prev)
    assert [n for n, _ in seen] == ["serve.pack", "daemon.launch"]
    assert all(sec >= 0.0 for _, sec in seen)


def test_the_phase_sink_keeps_only_the_six_phases():
    phases: dict = {}
    prev = fused.collect_phases(phases)
    try:
        with span("daemon.launch", batch=0):
            for name in fused._PHASE_KEYS:
                with span(name):
                    pass
            with span("frontend.rank", slot=0):
                pass
    finally:
        fused.collect_phases(prev)
    assert set(phases) == set(fused._PHASE_KEYS.values())
    assert all(len(v) == 1 and v[0] >= 0.0 for v in phases.values())


def _trace(serve, where):
    """Run ``serve()`` inside a trace: ``(events, what it returned)``, the
    serving path's spans as ``(line, name, stats, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(where), profiler_options=opts)
    try:
        out = serve()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = where.glob("plugins/profile/*/*.xplane.pb")
    events = []
    for p, plane in enumerate(ProfileData.from_file(str(xplane)).planes):
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in ALL_SPANS:
                    stats = {key: v for key, v in e.stats}
                    events.append(((p, k), e.name, stats, e.start_ns, e.start_ns + e.duration_ns))
    return events, out


def _serve_on(daemon, requests):
    """Queue every request before the daemon's first launch, then serve
    them; returns the tickets."""

    def serve():
        try:
            with daemon._work:
                tickets = [daemon.submit(r) for r in requests]
            daemon.start()
            for t in tickets:
                t.result(timeout=120)
        finally:
            daemon.stop()
        return tickets

    return serve


def _outer(events):
    """``(name, batch) -> [(start, end)]`` of the daemon's spans."""
    outer = defaultdict(list)
    for _, name, stats, a, b in events:
        if name in ("daemon.launch", "daemon.retire"):
            outer[name, stats["batch"]].append((a, b))
    return outer


def _tickets_by_batch(events, tickets):
    """Each batch's tickets, found from its ``daemon.launch``'s
    ``first_seq`` and ``n``: the ``n`` queued tickets from ``first_seq`` on."""
    out = {}
    for _, name, stats, *_ in events:
        if name == "daemon.launch":
            mine = [t for t in tickets if t.seq >= stats["first_seq"]][: stats["n"]]
            assert mine[0].seq == stats["first_seq"] and len(mine) == stats["n"]
            out[stats["batch"]] = mine
    return out


@pytest.fixture(scope="module")
def traced(small_index, tmp_path_factory):
    """The spans of two batches served by a started daemon inside a trace,
    one per replica."""
    arena = PostingArena(budget_bytes=128 << 20)
    # compile every program outside the trace, on frontends the daemon
    # does not use (the daemon's start with empty result caches)
    requests = [SearchRequest(q, top_k=5) for q in QUERIES]
    ServingFrontend(small_index, arena=arena).search_many(requests[:BATCH])
    ServingFrontend(small_index).search_many(requests[BATCH:])
    daemon = ServiceDaemon(
        [ServingFrontend(small_index, arena=arena), ServingFrontend(small_index)],
        batch_limit=BATCH,
    )
    return _trace(_serve_on(daemon, requests), tmp_path_factory.mktemp("trace"))


@pytest.fixture(scope="module")
def traced_chunked(small_index, tmp_path_factory):
    """The spans of one slate of ``BATCH + 1`` requests, one a duplicate,
    served by ``submit_many`` on a frontend whose ``max_batch`` is half a
    batch, so the slate runs as two chunks."""
    arena = PostingArena(budget_bytes=128 << 20)
    requests = [SearchRequest(q, top_k=5) for q in QUERIES[:BATCH]]
    requests.insert(2, requests[0])
    ServingFrontend(small_index, arena=arena, max_batch=BATCH // 2).search_many(requests)
    front = ServingFrontend(small_index, arena=arena, max_batch=BATCH // 2)
    events, _ = _trace(lambda: front.submit_many(requests)(), tmp_path_factory.mktemp("chunked"))
    return events


def test_every_span_of_the_serving_path_is_traced(traced):
    events, tickets = traced
    assert {name for _, name, *_ in events} == ALL_SPANS
    # the first batch ran the arena program, the second the host pack
    assert all(t.result().stats.arena_hits > 0 for t in tickets[:BATCH])
    assert all(t.result().stats.arena_hits == 0 for t in tickets[BATCH:])
    assert {stats["path"] for _, name, stats, *_ in events if name == "serve.pack"} == {"arena", "host"}


def test_each_span_carries_its_batch(traced):
    events, tickets = traced
    by_batch = _tickets_by_batch(events, tickets)
    assert len(by_batch) == len(QUERIES) // BATCH
    assert sorted(t.seq for mine in by_batch.values() for t in mine) == sorted(t.seq for t in tickets)
    assert all(e[2].get("batch") in by_batch for e in events)
    for b, mine in by_batch.items():
        for name in ("planner.plan", "frontend.rank"):
            slots = sorted(s["slot"] for _, n, s, *_ in events if n == name and s["batch"] == b)
            assert slots == list(range(len(mine)))


def test_phases_nest_in_their_batch_launch_or_retire_on_one_line(traced):
    events, _ = traced
    assert len({line for line, *_ in events}) == 1
    outer = _outer(events)
    assert all(len(v) == 1 for v in outer.values())
    for _, name, stats, a, b in events:
        if name in LAUNCH_PHASES or name in RETIRE_PHASES:
            parent = "daemon.launch" if name in LAUNCH_PHASES else "daemon.retire"
            ((pa, pb),) = outer[parent, stats["batch"]]
            assert pa <= a <= b <= pb, (name, stats)


def test_a_slot_is_the_request_index_in_the_slate(traced_chunked):
    events = traced_chunked
    # slot 2 repeats slot 0's query: it is neither planned nor ranked
    for name in ("planner.plan", "frontend.rank"):
        assert sorted(s["slot"] for _, n, s, *_ in events if n == name) == [0, 1, 3, 4]
    assert len([n for _, n, *_ in events if n == "serve.dispatch"]) == 2
