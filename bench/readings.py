"""What one run measured, and the arithmetic that turns it into metrics.

The harness fills a :class:`RunRecord`; end-to-end metrics and the
per-layer readers in ``bench/metrics/`` read it.  A reader returns None
when the run holds nothing for it to read (no trace, no batch), and the
harness then leaves its metric out.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Request:
    due: float  # seconds after the window opened
    launched: float | None = None  # when its batch was launched
    completed: float | None = None  # when its response was complete; None if it never was

    @property
    def latency(self) -> float | None:
        return None if self.completed is None else self.completed - self.due


@dataclass
class RunRecord:
    window_s: float
    setup_s: float
    closed_s: float = 0.0  # when the run stopped waiting for responses
    requests: list[Request] = field(default_factory=list)
    batches: list[dict] = field(default_factory=list)  # launched, completed, size, burst
    compiles_in_window: int | None = None
    trace: dict | None = None  # bench.trace.reduce_trace summary
    trace_window_s: float | None = None
    posting_bytes_per_burst: dict[int, int] = field(default_factory=dict)
    peaks: dict | None = None


# ---- end to end -----------------------------------------------------------


def completed_in_window(run: RunRecord) -> int:
    return sum(1 for r in run.requests if r.completed is not None and r.completed <= run.window_s)


def qps(run: RunRecord) -> float:
    return completed_in_window(run) / run.window_s


def latency_percentile(run: RunRecord, q: float) -> float:
    """The ``q``-th percentile of latency in ms over every request due in
    the window; a request that never completed counts as waited for until
    the run stopped waiting, which is a lower bound."""
    lat = [r.latency if r.latency is not None else run.closed_s - r.due for r in run.requests]
    return float(np.percentile(np.asarray(lat, np.float64), q)) * 1e3


# ---- per layer -------------------------------------------------------------


def queue_wait_ms(run: RunRecord) -> float | None:
    """Mean time from a request's due time to the launch of its batch."""
    w = [r.launched - r.due for r in run.requests if r.launched is not None]
    return float(np.mean(w)) * 1e3 if w else None


def service_ms(run: RunRecord) -> float | None:
    """Mean time from a batch's launch to its completion: planning, the
    device program and the host readout and ranking."""
    s = [b["completed"] - b["launched"] for b in run.batches if b.get("completed") is not None]
    return float(np.mean(s)) * 1e3 if s else None


def batch_occupancy(run: RunRecord) -> float | None:
    if not run.batches:
        return None
    return sum(b["size"] for b in run.batches) / len(run.batches)


def traced_batches(run: RunRecord) -> list[dict]:
    if run.trace_window_s is None:
        return []
    return [b for b in run.batches if b["launched"] < run.trace_window_s]


def program_ms(run: RunRecord) -> float | None:
    """Device time of the serving programs per batch launched in the
    traced window."""
    from .trace import serving_seconds

    batches = traced_batches(run)
    if run.trace is None or not batches:
        return None
    dev = serving_seconds(run.trace)
    return dev / len(batches) * 1e3 if dev > 0 else None


def idle_pct(run: RunRecord) -> float | None:
    if run.trace is None or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace_window_s)


def roofline_pct(run: RunRecord) -> float | None:
    """Least time of the traced batches (their posting bytes at the chip's
    memory bandwidth) over the serving programs' device time."""
    from .roofline import least_seconds
    from .trace import serving_seconds

    batches = traced_batches(run)
    if run.trace is None or not batches or run.peaks is None:
        return None
    dev = serving_seconds(run.trace)
    if dev <= 0:
        return None
    need = sum(run.posting_bytes_per_burst[b["burst"]] for b in batches)
    return 100.0 * least_seconds(need, run.peaks) / dev


def compiles_in_window(run: RunRecord) -> float | None:
    return None if run.compiles_in_window is None else float(run.compiles_in_window)
