"""The program's spans in a JAX profiler trace (``*.xplane.pb``), per batch,
on the serving thread, and the device's idle time while that thread serves.

The program names each phase of serving with a span
(``src/repro/runtime/spans.py``); the daemon's ``daemon.launch`` and
``daemon.retire`` spans carry a batch id, and every span inside them
carries the same ``batch`` statistic.  Every Python thread's line in the
trace is named alike, so the serving thread is found by what it holds: the
host line(s) with ``daemon.launch`` spans.

* spans              ``{batch: {span name: seconds}}`` over the spans with
                     a ``batch`` statistic on the serving line; a span that
                     recurs in a batch (``planner.plan`` and
                     ``frontend.rank``, once per request) is summed;
* idle_in_service_s  the time in which the serving line is inside
                     ``daemon.launch`` or ``daemon.retire`` and the first
                     device runs no operation.

Both are empty (``{}`` and None) in a trace of a program without these
spans, and so is every reader below: a reader returns None where the run
holds nothing for it to read.  The readers average over the batches whose
``daemon.launch`` and ``daemon.retire`` both lie inside the trace.
"""

from __future__ import annotations

from collections import defaultdict

from bench.trace import _device_events, _stats, _union

LAUNCH, RETIRE = "daemon.launch", "daemon.retire"
# the per-layer split of a batch: the serving spans each metric sums
PHASES = {
    "frontend.plan": ("frontend.plan",),
    "serve.pack": ("serve.plan", "serve.pack", "serve.h2d", "serve.dispatch"),
    "serve.device_wait": ("serve.device_wait",),
    "serve.readout": ("serve.readout",),
    "frontend.rank": ("frontend.rank",),
}


def _covered(intervals: list[tuple[float, float]], busy: list[tuple[float, float]]) -> float:
    """Length of the part of ``intervals`` (merged, sorted) that ``busy``
    (merged, sorted) covers."""
    total, j = 0.0, 0
    for a, b in intervals:
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < b:
            total += min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
    return total


def span_summary(pd, first_busy: list[tuple[float, float]]) -> dict:
    """``spans`` and ``idle_in_service_s`` of a loaded trace, given the
    first device's busy intervals (merged, in ns)."""
    lines = [
        line
        for plane in pd.planes
        if not plane.name.startswith("/device:")
        for line in plane.lines
        if any(e.name == LAUNCH for e in line.events)
    ]
    spans: dict = defaultdict(lambda: defaultdict(float))
    service = []
    for line in lines:
        for e in line.events:
            batch = _stats(e).get("batch")
            if batch is None:
                continue
            spans[int(batch)][e.name] += e.duration_ns / 1e9
            if e.name in (LAUNCH, RETIRE):
                service.append((e.start_ns, e.start_ns + e.duration_ns))
    idle = None
    if lines:
        service = _union(service)
        idle = (sum(b - a for a, b in service) - _covered(service, first_busy)) / 1e9
    return {"spans": {b: dict(v) for b, v in sorted(spans.items())}, "idle_in_service_s": idle}


def reduce_spans(path) -> dict:
    """:func:`span_summary` of the trace file at ``path``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    per_device = _device_events(pd)
    first = next(iter(per_device.values()), ([], []))[0]
    return span_summary(pd, _union([(a, b) for a, b, _op, _prog in first]))


# ---- readers ---------------------------------------------------------------


def complete_batches(summary: dict | None) -> list[dict]:
    """The span seconds of each batch launched and retired inside the trace."""
    spans = (summary or {}).get("spans") or {}
    return [s for s in spans.values() if LAUNCH in s and RETIRE in s]


def phase_ms(run, phase: str) -> float | None:
    """Mean milliseconds per batch under the spans of ``phase``."""
    batches = complete_batches(run.trace)
    if not batches:
        return None
    return 1e3 * sum(sum(s.get(n, 0.0) for n in PHASES[phase]) for s in batches) / len(batches)


def frontend_plan_ms(run) -> float | None:
    return phase_ms(run, "frontend.plan")


def serve_pack_ms(run) -> float | None:
    return phase_ms(run, "serve.pack")


def serve_device_wait_ms(run) -> float | None:
    return phase_ms(run, "serve.device_wait")


def serve_readout_ms(run) -> float | None:
    return phase_ms(run, "serve.readout")


def frontend_rank_ms(run) -> float | None:
    return phase_ms(run, "frontend.rank")


def idle_in_service_pct(run) -> float | None:
    """Share of the traced window in which the serving thread is inside a
    batch's launch or retire and the device is idle."""
    idle = (run.trace or {}).get("idle_in_service_s")
    if idle is None or not run.trace_window_s:
        return None
    return 100.0 * idle / run.trace_window_s
