"""The trace reduction on a small recorded trace, and the roofline's bytes
and peaks.

``data/cpu_trace.xplane.pb`` was recorded on the CPU backend: three rounds
of a jitted ``arena_serve_batch`` stand-in (a sort) and a second program
(a cumulative sum), each round inside a ``bench.pump`` annotation and
followed by a 20 ms sleep inside ``bench.wait_arrival``.
"""

from __future__ import annotations

from pathlib import Path

import pytest

from bench.roofline import batch_posting_bytes, device_peaks, least_seconds
from bench.trace import breakdown, reduce_trace, serving_seconds

TRACE = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return reduce_trace(TRACE)


def test_reduction_finds_the_device_operations(summary):
    assert summary is not None and summary["devices"] == 1
    assert 0 < summary["busy_s"] < 0.2
    assert set(summary["programs_s"]) == {"jit_arena_serve_batch", "jit_other_program"}


def test_serving_time_counts_only_the_serving_programs(summary):
    serving = serving_seconds(summary)
    assert serving == pytest.approx(summary["programs_s"]["jit_arena_serve_batch"])
    assert serving < sum(summary["programs_s"].values())
    # operations of one program never overlap on this backend: the union
    # of intervals equals the sum of program times
    assert summary["busy_s"] == pytest.approx(sum(summary["programs_s"].values()), rel=1e-6)


def test_idle_gaps_are_named_by_what_the_host_was_doing(summary):
    b = breakdown(summary)
    gaps = dict(b["idle_gaps"])
    assert set(gaps) <= {"bench.wait_arrival", "bench.pump", "host"}
    assert gaps["bench.wait_arrival"] >= 2 * 0.02  # two sleeps lie between busy stretches
    assert gaps["bench.wait_arrival"] > gaps.get("bench.pump", 0.0)
    ops = dict(b["device_ops"])
    assert max(ops, key=ops.get).startswith("sort")
    assert len(b["device_ops"]) <= 10


def test_posting_bytes_equal_the_planners_on_a_tiny_index():
    from repro.index import build_indexes
    from repro.index.corpus import DocumentStore
    from repro.search.planner import QueryPlanner

    store = DocumentStore.from_texts(
        ["who are you who is the album by the who", "to be or not to be that is it",
         "who is who in the world of war", "i need you to be who you are"] * 5
    )
    index = build_indexes(store, sw_count=30, fu_count=8, max_distance=5)
    planner = QueryPlanner(index, lemmatizer=store.lemmatizer)
    plan = planner.plan("to be or not")
    assert plan.est_bytes > 0
    keys = [b.key for sp in plan.executable() for b in sp.bindings]
    assert len(keys) == len(set(keys)), "a query whose keys are distinct"
    assert batch_posting_bytes([plan]) == plan.est_bytes
    # a batch reads each key's rows once, however many requests use it
    assert batch_posting_bytes([plan, planner.plan("to be or not")]) == plan.est_bytes
    other = planner.plan("who is who")
    assert batch_posting_bytes([plan, other]) == plan.est_bytes + other.est_bytes


def test_peaks_are_keyed_by_device_kind():
    v5e = device_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert least_seconds(819_000_000, v5e) == pytest.approx(1e-3)
    with pytest.raises(KeyError):
        device_peaks("TPU v99")
