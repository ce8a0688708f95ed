"""Program spans on the profiler's clock (DESIGN.md §15.3).

Every timed phase of serving runs inside :func:`span`, which enters a
``jax.profiler.TraceAnnotation``: under ``jax.profiler.start_trace`` JAX
records it in the same ``.xplane.pb`` as the device operations, so program
spans and device events share one clock; with no profiler active a span
costs about a microsecond and records nothing.

A span that names a ``batch`` sets the current batch id for its body (a
``contextvars.ContextVar``); every span nested inside adds ``batch=<id>``
to its metadata, so the layers below the daemon need no batch argument.

:func:`set_recorder` installs an optional in-memory recorder, called with
``(name, seconds)`` as each span closes; ``fused.collect_phases`` is its
one user, and keeps the six §15.3 phases of what it is given.
"""

from __future__ import annotations

import contextvars
import time
from typing import Callable

import jax

__all__ = ["span", "set_recorder"]

_BATCH: contextvars.ContextVar[int | None] = contextvars.ContextVar("span_batch", default=None)
_RECORDER: Callable[[str, float], None] | None = None


def set_recorder(recorder: Callable[[str, float], None] | None):
    """Install (or clear, with ``None``) the span recorder; returns the
    previous one."""
    global _RECORDER
    prev, _RECORDER = _RECORDER, recorder
    return prev


class span:
    """``with span("serve.pack", path="arena"):`` — one program span.

    ``meta`` becomes the trace event's statistics; ``batch=`` also sets
    the current batch id for the body."""

    __slots__ = ("name", "meta", "_annotation", "_token", "_recorder", "_t0")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta

    def __enter__(self):
        meta = self.meta
        if "batch" in meta:
            self._token = _BATCH.set(meta["batch"])
        else:
            self._token = None
            batch = _BATCH.get()
            if batch is not None:
                meta = dict(meta, batch=batch)
        self._annotation = jax.profiler.TraceAnnotation(self.name, **meta)
        self._annotation.__enter__()
        self._recorder = _RECORDER
        if self._recorder is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._recorder is not None:
            self._recorder(self.name, time.perf_counter() - self._t0)
        self._annotation.__exit__(*exc)
        if self._token is not None:
            _BATCH.reset(self._token)
        return False
