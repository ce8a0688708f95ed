"""Pallas TPU kernel: parallel minimal-fragment cover (the Combiner's Step 3).

Hardware mapping (see DESIGN.md §2):

* the Position table's 64-bit masks become dense int32 occupancy rows in
  VMEM — one row per subquery lemma, one lane per document position;
* Bit Scan Forward disappears: a bitmask's sortedness is the lane order;
* the Source/Processed queues become prefix counts (``C``) computed with
  log2(N) doubling shifts on the VPU;
* the §10.2 shrink loop becomes a static ``2*MaxDistance+1``-step window
  scan, each step one shifted vector compare over all lemma rows.

Grid: one program per document.  Block shapes keep the whole (padded)
document in VMEM: ``occ`` is [L, N] (streamed in the compute dtype, widened
to int32 in VMEM) with N a multiple of 128 lanes, L <= 8 sublanes — ~32 KB
for N=1024 in int32, far under the ~16 MB VMEM budget, so
multiple docs pipeline cleanly (double buffering hides the HBM streams).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret

__all__ = ["proximity_window_kernel", "proximity_window"]


def _shift_right(x: jax.Array, o: int) -> jax.Array:
    """x[..., p] -> x[..., p-o] with zero fill (static o)."""
    if o == 0:
        return x
    n = x.shape[-1]
    pad = jnp.zeros(x.shape[:-1] + (o,), x.dtype)
    return jnp.concatenate([pad, x[..., : n - o]], axis=-1)


def proximity_window_kernel(
    occ_ref,  # [1, L, N] occupancy in the (possibly narrow) stream dtype
    mult_ref,  # [1, L, 1] int32
    emit_ref,  # [1, 1, N] int32 out
    start_ref,  # [1, 1, N] int32 out
    *,
    window: int,
):
    # widen in VMEM: the HBM stream stays in the narrow dtype, while the
    # vector unit computes in int32 (Mosaic has no 8-bit vector arithmetic).
    # int32 prefix counts never wrap (N < 2**31), so the cover test
    # ``c - cq + oq`` (occurrences in one candidate window) is exact — the
    # same quantity the narrow-dtype jnp ref gets by unsigned wraparound
    # cancelling over window differences (core/window.py, §Perf-3).
    occ = occ_ref[0].astype(jnp.int32)  # [L, N]
    mult = mult_ref[0]  # [L, 1]
    n = occ.shape[-1]

    # prefix counts via doubling shifts (log2 N steps, VPU adds)
    c = occ
    k = 1
    while k < n:
        c = c + _shift_right(c, k)
        k <<= 1

    # every per-position quantity stays 2-D [1, N]: no 1-D <-> 2-D reshapes
    active = mult > 0  # [L, 1]
    is_event = jnp.any((occ > 0) & active, axis=0, keepdims=True)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    found = jnp.zeros((1, n), jnp.bool_)
    o_star = jnp.zeros((1, n), jnp.int32)
    for o in range(window):  # static unroll: window = 2*MaxDistance+1 <= 64
        cq = _shift_right(c, o)
        oq = _shift_right(occ, o)
        cnt = c - cq + oq  # occurrences in [e-o, e]
        cover = jnp.all((cnt >= mult) | ~active, axis=0, keepdims=True)
        cover = cover & (pos >= o)
        o_star = jnp.where(cover & ~found, o, o_star)
        found = found | cover

    emit_ref[0] = (found & is_event).astype(jnp.int32)
    start_ref[0] = pos - o_star


@functools.partial(
    jax.jit, static_argnames=("max_distance", "interpret", "compute_dtype")
)
def proximity_window(
    occ: jax.Array,  # [B, L, N] occupancy (any integer dtype)
    mult: jax.Array,  # [B, L] int32
    max_distance: int,
    interpret: bool | None = None,
    compute_dtype: str = "int32",
) -> tuple[jax.Array, jax.Array]:
    """Batched minimal-fragment cover via ``pl.pallas_call``.

    Returns ``(emit bool [B, N], start int32 [B, N])`` — identical semantics
    to ``kernels.ref.proximity_window_ref``.  ``compute_dtype`` narrows the
    occupancy rows streamed from HBM (uint8 quarters the stream per doc, see
    DESIGN.md §2); it must fit the window length, like the jnp ref.  The
    kernel widens them to int32 in VMEM.  ``interpret=None`` runs the
    Pallas interpreter on a CPU backend and compiles everywhere else
    (``kernels/backend.py``).

    Every block's last two dims equal the array's (``(L, N)``, ``(L, 1)``,
    ``(1, N)``), which meets the TPU tiling rule for any ``L`` and any
    ``N`` that is a multiple of 128.
    """
    b, l, n = occ.shape
    window = 2 * max_distance + 1
    cdt = jnp.dtype(compute_dtype)
    if cdt != jnp.int32 and window > jnp.iinfo(cdt).max:
        raise ValueError(
            f"compute_dtype {compute_dtype} cannot hold window counts up to {window}"
        )
    kernel = functools.partial(proximity_window_kernel, window=window)
    emit, start = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, l, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, n), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, n), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(occ.astype(cdt), mult.astype(jnp.int32)[:, :, None])
    return emit[:, 0].astype(jnp.bool_), start[:, 0]
