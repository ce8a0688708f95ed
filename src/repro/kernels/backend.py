"""Backend check that decides how every Pallas kernel in this package runs.

A ``pallas_call`` compiles through Mosaic on a TPU; the CPU backend has no
Mosaic, so there the same kernels run in the Pallas interpreter (the test
configuration: ``JAX_PLATFORMS=cpu``).  Each kernel wrapper takes
``interpret=None`` and resolves it here, so no path on an accelerator ever
runs the interpreter by default.
"""

from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` when given; otherwise True iff JAX's default backend is
    the CPU (interpret mode), False on a TPU, where every ``pallas_call``
    compiles."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
