"""Compile the serving path's device programs for a TPU v5e that is
described, not attached.

The TPU compiler (Mosaic for the Pallas kernels, XLA for the rest) runs on
the host: a program it refuses here — a block shape that breaks the (8, 128)
tiling rule, an unsupported vector dtype or reshape, more VMEM than a kernel
may use — would fail the same way on the chip.  Interpret-mode tests cannot
see any of that.  Shapes are the serving path's real widths; nothing runs,
so these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _kernel_text(lowered) -> str:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not compiled in"
    return text


@pytest.mark.parametrize("dtype", ["int32", "uint8"])
def test_proximity_window_compiles(one_chip, dtype):
    from repro.kernels.proximity import proximity_window

    occ = _spec(one_chip, (512, 8, 1024), dtype)
    mult = _spec(one_chip, (512, 8), np.int32)
    _kernel_text(
        proximity_window.lower(
            occ, mult, max_distance=5, interpret=False, compute_dtype=dtype
        )
    )


def test_gather_blocks_compiles(one_chip):
    """An arena family buffer of 2^26 rows, the largest a 100,000-passage
    corpus puts on one chip.  The kernel must read it in place: a layout
    copy for the Mosaic operand would be a temporary the size of the arena
    or larger (64x for a row-major ``[rows, 2]`` buffer)."""
    from repro.kernels.gather import ARENA_BLOCK, gather_blocks

    rows = 1 << 26
    arena = _spec(one_chip, (2, rows), np.int32)  # field-major (doc; pos)
    src = _spec(one_chip, (256,), np.int32)
    nv = _spec(one_chip, (256,), np.int32)
    compiled = gather_blocks.lower(arena, src, nv, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 256 * ARENA_BLOCK * 4


def test_intersect_sorted_compiles(one_chip):
    from repro.kernels.intersect import intersect_sorted

    a = _spec(one_chip, (8192,), np.int32)
    b = _spec(one_chip, (16384,), np.int32)
    off = _spec(one_chip, (8192 // 128,), np.int32)
    _kernel_text(intersect_sorted.lower(a, b, off, n_chunks=4, interpret=False))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_fused_serve_batch_compiles(one_chip, use_kernel):
    """The host-pack device program at the frontend's default warm-up
    bucket: E=4096 events, R=512 rows, L=4 lemmas, K=64 table depth, Q=8
    queries, N=256 positions."""
    from repro.search.fused import fused_serve_batch

    e, r, l, k, q, n = 4096, 512, 4, 64, 8, 256
    lowered = fused_serve_batch.lower(
        _spec(one_chip, (e, 3), np.int32),
        _spec(one_chip, (e,), np.int8),
        _spec(one_chip, (r, l, k), np.int32),
        _spec(one_chip, (r,), np.int32),
        _spec(one_chip, (r,), np.int32),
        _spec(one_chip, (r, l), np.int32),
        max_distance=5,
        query_budget=q,
        window_len=n,
        top_k=10,
        compute_dtype="uint8",
        use_kernel=use_kernel,
        interpret=False,
    )
    if use_kernel:
        _kernel_text(lowered)
    else:
        lowered.compile()
