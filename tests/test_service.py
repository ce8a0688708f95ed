"""End-to-end tests for the §16 continuous-batching serving daemon.

Concurrent clients over the in-process transport (and the JSON-lines TCP
transport) against the resilient sharded stack under seeded chaos
schedules (``FaultInjector.from_seed`` — the same §14 schedules the chaos
harness replays): every served response must be SE2.4-oracle-exact over
the full corpus or explicitly flagged partial with exact coverage of
whole shards — never silently wrong — no matter how requests interleave,
batch or queue.  Plus: replica-routing consistency across a mid-run
commit and compact (one §12.5 generation lineage, so no replica can serve
a stale cache entry as fresh), and a lossless TCP round trip (wire docs
identical to the in-process response).
"""

from __future__ import annotations

import threading

import pytest

from tests.test_chaos import (
    CHAOS_SEEDS,
    N_SHARDS,
    TOP_K,
    _build_stack,
    _oracle_union,
    _ranking,
    _response_frags,
)

from repro.index import IncrementalIndexer
from repro.runtime.clock import ManualClock
from repro.search.frontend import SearchRequest, ServingFrontend
from repro.search.service import (
    ServiceDaemon,
    request_over_tcp,
    response_to_wire,
    serve_tcp,
)


def _assert_exact_or_flagged_frags(resp, oracle):
    """The §14 invariant, assertable without the live excluded-shard set
    (responses may be checked after later rounds changed it): a response
    is the full oracle, or it is flagged partial AND exactly the oracle
    minus whole shards (with exact ranking over what it covers)."""
    got = _response_frags(resp)
    if got == oracle:
        return
    assert resp.stats.partial, (resp.query, "divergent response not flagged")
    dead = {
        s
        for s in range(N_SHARDS)
        if any(f[0] % N_SHARDS == s for f in oracle)
        and not any(f[0] % N_SHARDS == s for f in got)
    }
    expected = {f for f in oracle if f[0] % N_SHARDS not in dead}
    assert got == expected, (resp.query, sorted(dead), "not whole-shard coverage")
    assert [(d.doc_id, d.score) for d in resp.docs] == _ranking(expected), (
        resp.query,
        "degraded ranking is not the exact ranking of the covered set",
    )


# ---------------------------------------------------------------------------
# concurrent clients x seeded chaos, in-process transport
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chaos_seed", CHAOS_SEEDS)
def test_concurrent_clients_under_chaos_exact_or_flagged(chaos_seed, tmp_path):
    """N threaded clients against the started daemon while the seeded §14
    fault schedule fires (crashes, a kill + snapshot recovery, stragglers,
    bit-flips): every one of the N*rounds*queries responses is oracle-exact
    or flagged with whole-shard coverage."""
    svc, queries, oracles = _build_stack(tmp_path, chaos_seed=chaos_seed)
    daemon = ServiceDaemon(ServingFrontend(svc), max_queue=512).start()
    n_clients, rounds = 4, 3
    results: list[list] = [[] for _ in range(n_clients)]
    errors: list[BaseException] = []

    def client(c: int) -> None:
        try:
            for _ in range(rounds):
                tickets = [
                    daemon.submit(SearchRequest(q, top_k=TOP_K)) for q in queries
                ]
                for q, t in zip(queries, tickets):
                    results[c].append((q, t.result(timeout=120.0)))
        except BaseException as e:  # surface in the main thread
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,)) for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    daemon.stop()
    assert not errors, errors
    served = [pair for per_client in results for pair in per_client]
    assert len(served) == n_clients * rounds * len(queries)
    for q, resp in served:
        _assert_exact_or_flagged_frags(resp, oracles[q])
    m = daemon.metrics()
    assert m["submitted"] == m["completed"] + m["shed_queue"]
    assert m["shed_queue"] == 0  # queue was large enough: nothing dropped


def test_deterministic_chaos_replay_through_daemon(tmp_path):
    """The same chaos stack driven by the virtual-clock replay: two runs
    of one seed produce identical response traces through the daemon
    (the §14 determinism contract lifted to the service layer)."""

    def run(subdir):
        clock = ManualClock()
        svc, queries, _ = _build_stack(
            tmp_path / subdir, chaos_seed=CHAOS_SEEDS[0], clock=clock
        )
        daemon = ServiceDaemon(
            ServingFrontend(svc, clock=clock), clock=clock, max_queue=512
        )
        schedule = [
            (i * 0.001, SearchRequest(q, top_k=TOP_K))
            for i, q in enumerate(queries * 6)
        ]
        tickets = daemon.replay(schedule, service_time_sec=0.004)
        return [
            (
                sorted(_response_frags(t.result(timeout=0))),
                t.result(timeout=0).stats.shards_degraded,
                t.result(timeout=0).stats.partial,
                t.batch_size,
            )
            for t in tickets
        ]

    assert run("a") == run("b")


# ---------------------------------------------------------------------------
# replica routing across a mid-run commit/compact
# ---------------------------------------------------------------------------


def test_replica_routing_consistent_across_commit_and_compact(small_corpus):
    """Two frontend replicas over ONE incremental source: after a mid-run
    commit and a later compact, every response from EITHER replica equals
    the fresh single-frontend reference for the live index state — the
    shared §12.5 generation lineage makes pre-mutation cache entries
    unreachable on both replicas, so routing never changes results."""
    ix = IncrementalIndexer(
        sw_count=60, fu_count=150, max_distance=5,
        lemmatizer=small_corpus.lemmatizer,
    )
    ix.add_documents([d.text for d in small_corpus.documents])
    ix.commit()
    clock = ManualClock()
    replicas = [
        ServingFrontend(ix, lemmatizer=small_corpus.lemmatizer,
                        max_batch=2, clock=clock)
        for _ in range(2)
    ]
    daemon = ServiceDaemon(replicas, clock=clock, max_queue=64)
    queries = ["who are you who", "to be or not to be", "the who", "you do"]

    def serve_all():
        tickets = [daemon.submit(SearchRequest(q, top_k=50)) for q in queries]
        daemon.drain()
        return [t.result(timeout=0) for t in tickets]

    def reference():
        fe = ServingFrontend(ix, lemmatizer=small_corpus.lemmatizer)
        return {q: fe.search(q, top_k=50) for q in queries}

    def frags(resp):
        return sorted((d.doc_id, f.start, f.end) for d in resp.docs for f in d.fragments)

    # round 1: both replicas warm their caches on the initial generation
    for resp, (q, want) in zip(serve_all(), reference().items()):
        assert frags(resp) == frags(want), q

    # mid-run commit: new docs, token bump on the shared lineage
    ix.add_documents(["who are you who are you", "to be or not to be at all"])
    ix.commit()
    want = reference()
    got = serve_all() + serve_all()  # twice: hit both replicas for sure
    for resp in got:
        assert frags(resp) == frags(want[resp.query]), (resp.query, "stale post-commit")
        assert resp.stats.shards_degraded == 0

    # mid-run compact (delete + rewrite): token bumps again
    victim = next(r for r in got if r.docs).docs[0].doc_id
    ix.delete_document(victim)
    ix.compact()
    want = reference()
    for resp in serve_all() + serve_all():
        assert frags(resp) == frags(want[resp.query]), (resp.query, "stale post-compact")
        assert victim not in [d.doc_id for d in resp.docs]
    m = daemon.metrics()
    assert all(n > 0 for n in m["per_replica_batches"]), m


# ---------------------------------------------------------------------------
# TCP transport: lossless round trip, concurrent connections
# ---------------------------------------------------------------------------


def test_tcp_round_trip_is_lossless_and_concurrent(small_index, lemmatizer):
    """The JSON-lines wire image of a response equals response_to_wire of
    the in-process reference (docs, scores, fragments, flags), for several
    concurrent client connections; the metrics op reports the daemon's
    counters over the same socket."""
    frontend = ServingFrontend(small_index, lemmatizer=lemmatizer, max_batch=8)
    daemon = ServiceDaemon(frontend, max_queue=64)
    server = serve_tcp(daemon)  # ephemeral port
    try:
        queries = ["who are you who", "to be or not to be", "what do you do all day"]
        reference = ServingFrontend(small_index, lemmatizer=lemmatizer)
        want = {
            q: response_to_wire(reference.search(q, top_k=8)) for q in queries
        }

        outs: dict[str, dict] = {}
        errors: list[BaseException] = []

        def client(q: str) -> None:
            try:
                outs[q] = request_over_tcp(
                    server.address, {"query": q, "top_k": 8}
                )
            except BaseException as e:
                errors.append(e)

        threads = [threading.Thread(target=client, args=(q,)) for q in queries]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert not errors, errors
        for q in queries:
            got = outs[q]
            assert got["docs"] == want[q]["docs"], q
            assert not got["partial"] and not got["shed"]
            assert got["batch_size"] >= 1

        m = request_over_tcp(server.address, {"op": "metrics"})["metrics"]
        assert m["completed"] == len(queries)
        assert m["submitted"] == m["completed"] + m["shed_queue"]
        assert request_over_tcp(server.address, {"op": "ping"}) == {"pong": True}
    finally:
        server.shutdown()
        server.server_close()
        daemon.stop()


def test_tcp_deadline_and_bad_requests(small_index, lemmatizer):
    """deadline_ms crosses the wire into the §5 partial machinery (a zero
    budget returns an empty flagged response), and malformed lines get an
    error reply instead of killing the connection."""
    daemon = ServiceDaemon(
        ServingFrontend(small_index, lemmatizer=lemmatizer), max_queue=16
    )
    server = serve_tcp(daemon)
    try:
        out = request_over_tcp(
            server.address, {"query": "who are you who", "top_k": 8, "deadline_ms": 0}
        )
        assert out["partial"] and out["docs"] == []
        err = request_over_tcp(server.address, {"op": "nope"})
        assert "error" in err
    finally:
        server.shutdown()
        server.server_close()
        daemon.stop()


# ---------------------------------------------------------------------------
# a failed device program fails its requests instead of hanging them
# ---------------------------------------------------------------------------


class _FailingFrontend:
    """A replica whose batch submit, or its blocking readout, raises — what
    a device program that fails to compile or run looks like to the
    daemon."""

    max_batch = 2

    def __init__(self, where: str):
        self.where = where

    def submit_many(self, requests):
        if self.where == "submit":
            raise RuntimeError("device program failed")

        def finalize():
            raise RuntimeError("device program failed")

        return finalize


@pytest.mark.parametrize("where", ["submit", "readout"])
@pytest.mark.parametrize("mode", ["drain", "thread"])
def test_daemon_failure_fails_every_pending_ticket(where, mode):
    """An exception inside ``pump`` completes every queued and in-flight
    ticket with it (``result`` re-raises at once), later submits fail the
    same way, and the conservation identity still holds."""
    clock = ManualClock()
    daemon = ServiceDaemon([_FailingFrontend(where)], clock=clock, max_queue=16)
    tickets = [daemon.submit(f"q{i}") for i in range(5)]
    if mode == "drain":
        with pytest.raises(RuntimeError, match="device program failed"):
            daemon.drain()
    else:
        daemon.start()
    for t in tickets:
        # a real timeout guards the threaded mode only: the failure is set
        # by the daemon thread as soon as the first batch raises
        with pytest.raises(RuntimeError, match="device program failed"):
            t.result(timeout=30.0)
        assert t.done()
    late = daemon.submit("after the failure")
    with pytest.raises(RuntimeError, match="device program failed"):
        late.result(timeout=0)
    daemon.stop()
    m = daemon.metrics()
    assert m["failed"] == 6 and m["completed"] == 0
    assert m["submitted"] == (
        m["completed"] + m["shed_queue"] + m["failed"] + m["queued"]
        + m["inflight_requests"]
    )
