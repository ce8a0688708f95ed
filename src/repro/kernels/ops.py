"""Public jit'd entry points for the Pallas kernels.

The kernels compile on a TPU and run in the Pallas interpreter on a CPU
backend (``kernels/backend.py``); callers never choose the mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .intersect import PAD, block_offsets, intersect_sorted
from .proximity import proximity_window
from .ref import (
    embedding_bag_ref,
    fragment_scores_ref,
    intersect_ref,
    proximity_window_ref,
)

__all__ = [
    "proximity_window",
    "proximity_window_ref",
    "intersect_sorted",
    "intersect_ref",
    "block_offsets",
    "embedding_bag_ref",
    "fragment_scores_ref",
    "proximity_search_scores",
    "PAD",
]


@functools.partial(
    jax.jit,
    static_argnames=("max_distance", "use_kernel", "compute_dtype"),
)
def proximity_search_scores(
    occ: jax.Array,  # [B, L, N] occupancy per candidate window
    mult: jax.Array,  # [B, L]
    max_distance: int,
    use_kernel: bool = False,
    compute_dtype: str = "int32",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused cover + §14 relevance: returns (emit, start, scores[B]).

    ``compute_dtype`` narrows the occupancy/prefix-count rows (kernel and jnp
    ref agree — §Perf-3); int32 reproduces the historical behaviour exactly.
    """
    cdt = jnp.dtype(compute_dtype)
    if use_kernel:
        emit, start = proximity_window(
            occ, mult, max_distance, compute_dtype=compute_dtype
        )
    else:
        emit, start = proximity_window_ref(occ.astype(cdt), mult, max_distance)
    scores = fragment_scores_ref(emit, start)
    return emit, start, scores
