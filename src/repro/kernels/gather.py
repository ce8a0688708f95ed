"""Pallas TPU kernel: arena block gather (the device-resident posting fetch).

The device-resident posting arena (``search/arena.py``, DESIGN.md §13) keeps
each §3 posting family's concatenated rows in ONE device buffer, every key's
extent aligned to a ``BLOCK``-row boundary.  The buffer is field-major,
``[2, rows]`` (a doc row over a pos row): the TPU tiles an array's two minor
dims, and Mosaic takes its operands in (8, 128) tiles, so a row-major
``[rows, 2]`` buffer would be copied to 64x its size before every call.
Serving a batch then only needs
to *slice* the arena: the host ships a per-output-block indirection table
(``src_block``: which arena block fills output block ``i``; ``n_valid``: how
many of its rows are live) and the kernel copies block ``src_block[i]`` of
the arena into output block ``i``, masking the tail rows of each extent with
the ``-1`` sentinel.

This is the same scalar-prefetch indirection pattern as
``kernels/intersect.py`` (and block-sparse attention's block tables): the
indirection arrays land in SMEM via ``PrefetchScalarGridSpec`` *before* the
grid runs, so the ``BlockSpec`` index map can steer each grid step's DMA —
the gather IS the address computation, no gathered element ever round-trips
through the host.  ``gather_blocks_ref`` is the jnp form of the identical
computation (the default on CPU, where a per-block interpret-mode grid walk
costs more than one fused XLA gather); both produce bit-identical outputs
and the differential tests pin them against each other.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

__all__ = ["ARENA_BLOCK", "gather_blocks", "gather_blocks_ref"]

# Arena extent alignment (rows).  128 matches the TPU lane count, so one
# arena block is one lane-wide tile per field.
ARENA_BLOCK = 128


def _gather_kernel(src_ref, nv_ref, arena_ref, out_ref):
    i = pl.program_id(0)
    rows = arena_ref[...]  # [W, BLOCK] the steered arena block
    iota = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    live = iota < nv_ref[i]
    out_ref[...] = jnp.where(live, rows, jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gather_blocks(
    arena: jax.Array,  # [W, NB * block] int32 device-resident posting rows
    src_block: jax.Array,  # [G] int32 arena block index per output block
    n_valid: jax.Array,  # [G] int32 live rows in each output block
    block: int = ARENA_BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    """Copy arena block ``src_block[i]`` into output block ``i`` (``[W, G *
    block]`` int32), masking rows past ``n_valid[i]`` with ``-1``.

    Exactness: output row ``i * block + j`` equals arena row
    ``src_block[i] * block + j`` when ``j < n_valid[i]`` and the ``-1``
    sentinel row otherwise — identical to ``gather_blocks_ref``.
    """
    g = src_block.shape[0]
    w = arena.shape[0]
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # src_block + n_valid land in SMEM first
            grid=(g,),
            in_specs=[
                pl.BlockSpec((w, block), lambda i, src, nv: (0, src[i])),
            ],
            out_specs=pl.BlockSpec((w, block), lambda i, src, nv: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((w, g * block), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(src_block, n_valid, arena)


def gather_blocks_ref(
    arena: jax.Array,
    src_block: jax.Array,
    n_valid: jax.Array,
    block: int = ARENA_BLOCK,
) -> jax.Array:
    """jnp reference of :func:`gather_blocks` (one fused XLA gather; the
    default arena fetch on CPU).  Bit-identical to the kernel."""
    g = src_block.shape[0]
    within = jnp.arange(g * block, dtype=jnp.int32) % block
    blk = jnp.arange(g * block, dtype=jnp.int32) // block
    src = src_block[blk] * block + within
    rows = jnp.take(arena, jnp.clip(src, 0, arena.shape[1] - 1), axis=1)
    live = within < n_valid[blk]
    return jnp.where(live[None, :], rows, jnp.int32(-1))
