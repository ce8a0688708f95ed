"""Pallas TPU kernel: sorted posting-list intersection (the Combiner's Step 1).

The paper aligns iterators on a document with an O(log n)-per-step heap.  The
TPU-native analogue is *block intersection with scalar-prefetched
indirection*: the host computes, per 128-element block of the probe list
``a``, the block offset into the build list ``b`` that could contain matches
(a ``searchsorted`` — the galloping skip of ``KeyIterator.skip_to_doc``).
The kernel then loads that ``b`` tile into VMEM and does a broadcast-compare
on the VPU — the same trick block-sparse attention uses for its block tables.

Multiple ``b`` tiles per ``a`` block (``n_chunks`` grid axis) OR-accumulate
into the output, so arbitrarily dense matches stay correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

__all__ = ["intersect_sorted", "block_offsets"]

PAD = np.int32(2**31 - 1)


def block_offsets(a: np.ndarray, b: np.ndarray, block_a: int, block_b: int) -> np.ndarray:
    """Host-side indirection: for each ``a`` block, the aligned start tile
    in ``b`` (rounded down to a ``block_b`` multiple)."""
    starts = a[::block_a]
    off = np.searchsorted(b, starts, side="left")
    off = (off // block_b) * block_b
    max_off = max(0, len(b) - block_b)
    return np.minimum(off, max_off).astype(np.int32)


def _intersect_kernel(off_ref, a_ref, b_ref, out_ref):
    j = pl.program_id(1)
    a = a_ref[...]  # [1, BA]
    btile = b_ref[...]  # [1, BB]
    hit = jnp.any(a[0][:, None] == btile[0][None, :], axis=1)
    hit = hit & (a[0] != PAD)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] = out_ref[...] | hit[None, :].astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("block_a", "block_b", "n_chunks", "interpret")
)
def intersect_sorted(
    a: jax.Array,  # [NA] sorted int32, padded with PAD
    b: jax.Array,  # [NB] sorted int32, padded with PAD
    offsets: jax.Array,  # [NA / block_a] int32 from `block_offsets`
    block_a: int = 128,
    block_b: int = 256,
    n_chunks: int = 2,
    interpret: bool | None = None,
) -> jax.Array:
    """1/0 membership of each ``a`` element in ``b``.

    ``n_chunks`` extra ``b`` tiles after the prefetched offset bound the
    match span per block; ``block_offsets`` guarantees matches start inside
    tile 0, and sortedness bounds them within ``n_chunks * block_b`` unless
    a single ``a`` block spans more duplicates than that (callers size
    ``n_chunks`` from data statistics; tests sweep it).
    """
    na = a.shape[0]
    nb = b.shape[0]
    grid = (na // block_a, n_chunks)

    def b_index(i, j, off_ref):
        # tile index into b: prefetched block offset + chunk j
        return (0, jnp.minimum(off_ref[i] // block_b + j, nb // block_b - 1))

    out = pl.pallas_call(
        _intersect_kernel,
        # scalar prefetch: offsets land in SMEM before the grid runs
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_a), lambda i, j, off: (0, i)),
                pl.BlockSpec((1, block_b), b_index),
            ],
            out_specs=pl.BlockSpec((1, block_a), lambda i, j, off: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, na), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(offsets, a[None, :], b[None, :])
    return out[0]
