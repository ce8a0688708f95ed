"""The program spans of a trace, per batch on the serving thread, and the
device's idle time inside a batch's service.

``data/cpu_spans.xplane.pb`` was recorded on the CPU backend the way
``bench/run.py`` traces a window, at a tiny size: a ``ServiceDaemon`` on
its own thread (the harness's ``bench.pump`` subclass) over a 50-document
index with a 128 MB posting arena, three bursts of four queries (every
program compiled beforehand), and the client thread sleeping in
``bench.wait_arrival`` between them; HLO protos left out
(``enable_hlo_proto = False``).  ``data/cpu_trace.summary.json`` is
``reduce_trace`` and ``breakdown`` of ``data/cpu_trace.xplane.pb``, and two
readers on it, as the benchmark read them before the spans existed.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import readings, spans
from bench.readings import RunRecord
from bench.trace import breakdown, reduce_trace

DATA = Path(__file__).parent / "data"
SPANS_TRACE = DATA / "cpu_spans.xplane.pb"
OLD_TRACE = DATA / "cpu_trace.xplane.pb"
PHASE_READERS = {
    "frontend.plan": spans.frontend_plan_ms,
    "serve.pack": spans.serve_pack_ms,
    "serve.device_wait": spans.serve_device_wait_ms,
    "serve.readout": spans.serve_readout_ms,
    "frontend.rank": spans.frontend_rank_ms,
}


@pytest.fixture(scope="module")
def summary():
    return {**reduce_trace(SPANS_TRACE), **spans.reduce_spans(SPANS_TRACE)}


def _run(summary, window_s=0.25):
    return RunRecord(window_s=window_s, setup_s=0.0, trace=summary, trace_window_s=window_s)


def test_spans_are_read_per_batch_from_the_serving_line_only(summary):
    per_batch = summary["spans"]
    assert sorted(per_batch) == [0, 1, 2]
    for s in per_batch.values():
        assert {"daemon.launch", "daemon.retire", "frontend.plan", "planner.plan", "serve.pack",
                "serve.h2d", "serve.dispatch", "serve.device_wait", "serve.readout",
                "frontend.rank"} == set(s)
        # the harness's spans, on the client's line and around the daemon's
        # steps, carry no batch and are not program spans
        assert not any(name.startswith("bench.") for name in s)
        assert s["frontend.plan"] + s["serve.pack"] + s["serve.h2d"] + s["serve.dispatch"] <= s["daemon.launch"]
        assert s["serve.device_wait"] + s["serve.readout"] + s["frontend.rank"] <= s["daemon.retire"]
        assert s["planner.plan"] <= s["frontend.plan"]


def test_idle_in_service_is_the_service_time_the_device_is_not_busy(summary):
    idle = summary["idle_in_service_s"]
    service = sum(s["daemon.launch"] + s["daemon.retire"] for s in summary["spans"].values())
    # the arena program runs inside the retire's device wait (on this
    # backend the dispatch runs it at once): the device is busy for part of
    # the service, and every idle second of service is an idle second
    assert 0.0 < idle < service
    assert service - idle <= summary["busy_s"] + 1e-9


@pytest.mark.parametrize(
    "service, busy, covered",
    [
        ([(0, 10)], [], 0),
        ([(0, 10)], [(2, 4), (6, 7)], 3),
        ([(0, 10), (20, 30)], [(5, 25)], 10),
        ([(0, 10)], [(-5, 1), (9, 15)], 2),
        ([(5, 6)], [(0, 2), (3, 4), (8, 9)], 0),
    ],
)
def test_covered_time_of_merged_intervals(service, busy, covered):
    assert spans._covered(service, busy) == covered


def test_phase_readers_average_the_complete_batches(summary):
    run = _run(summary)
    per_batch = list(summary["spans"].values())
    for phase, reader in PHASE_READERS.items():
        want = 1e3 * sum(sum(s.get(n, 0.0) for n in spans.PHASES[phase]) for s in per_batch) / len(per_batch)
        assert reader(run) == pytest.approx(want)
    # a batch still in flight when the trace stopped has no retire: left out
    cut = dict(summary, spans={**summary["spans"], 3: {"daemon.launch": 9.0, "frontend.plan": 9.0}})
    assert spans.frontend_plan_ms(_run(cut)) == pytest.approx(spans.frontend_plan_ms(run))
    # the five phases cover nearly all of a batch's launch and retire
    service = 1e3 * sum(s["daemon.launch"] + s["daemon.retire"] for s in per_batch) / len(per_batch)
    assert 0.8 * service <= sum(r(run) for r in PHASE_READERS.values()) <= service


def test_idle_in_service_share_lies_within_the_idle_share(summary):
    run = _run(summary)
    got = spans.idle_in_service_pct(run)
    assert got == pytest.approx(100.0 * summary["idle_in_service_s"] / 0.25)
    assert 0.0 < got <= readings.idle_pct(run)


def test_a_trace_without_program_spans_reads_nothing():
    """A program without the spans (the trace below predates them): the
    reduction finds no serving line and every reader returns None."""
    got = spans.reduce_spans(OLD_TRACE)
    assert got == {"spans": {}, "idle_in_service_s": None}
    run = _run({**reduce_trace(OLD_TRACE), **got})
    assert all(reader(run) is None for reader in PHASE_READERS.values())
    assert spans.idle_in_service_pct(run) is None
    # and a summary that lacks the keys altogether
    bare = _run(reduce_trace(OLD_TRACE))
    assert spans.frontend_plan_ms(bare) is None and spans.idle_in_service_pct(bare) is None


def test_existing_reduction_and_readers_read_as_before():
    """``reduce_trace``, ``breakdown`` and the trace readers give, on the
    recorded CPU trace, exactly what they gave before the program had
    spans."""
    before = json.loads((DATA / "cpu_trace.summary.json").read_text())
    summary = reduce_trace(OLD_TRACE)
    run = RunRecord(window_s=1.0, setup_s=0.0, trace=summary, trace_window_s=0.25,
                    batches=[{"launched": 0.0, "completed": 0.01, "size": 4, "burst": b} for b in range(3)])
    now = {
        "reduce_trace": summary,
        "breakdown": breakdown(summary),
        "readers": {"device.program_ms.lat": readings.program_ms(run), "device.idle_pct.lat": readings.idle_pct(run)},
    }
    assert json.dumps(now, sort_keys=True) == json.dumps(before, sort_keys=True)
