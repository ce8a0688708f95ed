"""Reduce a JAX profiler trace (``*.xplane.pb``) to the benchmark's device
numbers.

Device operations are read from the device planes (``/device:...``): the
events of their ``XLA Ops`` line, attributed to programs by the ``XLA
Modules`` line.  A trace with no device plane (the CPU backend) carries its
operations on host threads instead, as events with an ``hlo_module``
statistic; the same reduction reads those, which is how it is tested
without a chip.

* busy      the union of operation intervals, per device, averaged over
            the devices;
* programs  device time per program (module) name: the program's
            executions where the trace has a module line, else the union
            of its operations' intervals;
* ops       device time per operation name (an operation nested in a
            loop counts in the loop's time too);
* gaps      the intervals between busy stretches, each named by the host
            annotation (``bench.*``) that covers its midpoint.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path

SERVING_PROGRAMS = ("arena_serve_batch", "fused_serve_batch")


def find_xplane(log_dir) -> Path | None:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _op_name(name: str) -> str:
    """``%while.34`` from a TPU event's full instruction text
    (``%while.34 = (s32[]...) while(...)``); other names as they are."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _device_events(pd) -> dict[str, tuple[list, list]]:
    """``{device: (ops, modules)}``: operations as ``(start_ns, end_ns, op,
    program)`` and program executions as ``(start_ns, end_ns, program)``
    (empty where the trace has no module line)."""
    per_device: dict[str, tuple[list, list]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        ops_line = lines.get("XLA Ops")
        if ops_line is None:
            continue
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ())
        )
        events = []
        for e in ops_line.events:
            start, end = e.start_ns, e.start_ns + e.duration_ns
            program = _stats(e).get("hlo_module", "")
            if not program:
                program = next((n for a, b, n in modules if a <= start < b), "")
            events.append((start, end, _op_name(e.name), str(program)))
        per_device[plane.name] = (events, modules)
    if per_device:
        return per_device
    events = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                program = _stats(e).get("hlo_module")
                if program is not None and not e.name.startswith("end: "):
                    events.append((e.start_ns, e.start_ns + e.duration_ns, e.name, str(program)))
    return {"host-backend": (events, [])} if events else {}


def _host_spans(pd) -> list[tuple[float, float, str]]:
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return spans


def reduce_trace(path) -> dict | None:
    """Device busy time, program and operation times, and idle gaps of one
    trace; None when it holds no device operation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    per_device = _device_events(pd)
    if not per_device:
        return None

    busy_ns = []
    programs: dict[str, float] = defaultdict(float)
    ops: dict[str, float] = defaultdict(float)
    first_busy = None
    n = len(per_device)
    for events, modules in per_device.values():
        spans = []
        per_program: dict[str, list] = defaultdict(list)
        for a, b, op, program in events:
            spans.append((a, b))
            ops[op] += (b - a) / n
            per_program[program].append((a, b))
        if modules:
            # one event per execution of a program: executions never overlap
            for a, b, name in modules:
                programs[name] += (b - a) / n
        else:
            # operations nest (a loop and its body): a program's time is
            # the union of its operations' intervals
            for program, iv in per_program.items():
                programs[program] += sum(b - a for a, b in _union(iv)) / n
        merged = _union(spans)
        busy_ns.append(sum(b - a for a, b in merged))
        if first_busy is None:
            first_busy = merged
    gaps = []
    if first_busy:
        # innermost annotation first
        host = sorted(_host_spans(pd), key=lambda t: t[1] - t[0])
        for (a0, b0), (a1, _b1) in zip(first_busy, first_busy[1:]):
            mid = (b0 + a1) / 2
            name = next((n for s, e, n in host if s <= mid < e), "host")
            gaps.append((name, (a1 - b0) / 1e9))
    if not any(busy_ns):
        return None
    return {
        "devices": len(per_device),
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "programs_s": {k: v / 1e9 for k, v in programs.items()},
        "ops_s": {k: v / 1e9 for k, v in ops.items()},
        "gaps": gaps,
    }


def serving_seconds(summary: dict) -> float:
    """Device time of the serving programs (``arena_serve_batch``,
    ``fused_serve_batch``) in a reduced trace."""
    return sum(
        s for name, s in summary["programs_s"].items() if any(p in name for p in SERVING_PROGRAMS)
    )


def breakdown(summary: dict, top: int = 10) -> dict:
    """The longest device operations and the longest idle gaps, grouped by
    what the host was doing."""
    gap_by: dict[str, float] = defaultdict(float)
    for name, s in summary["gaps"]:
        gap_by[name] += s
    ops = sorted(summary["ops_s"].items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in sorted(gap_by.items(), key=lambda kv: -kv[1])[:top]],
    }
