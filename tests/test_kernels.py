"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.intersect import PAD, block_offsets, intersect_sorted
from repro.kernels.ops import proximity_search_scores
from repro.kernels.proximity import proximity_window
from repro.kernels.ref import (
    embedding_bag_ref,
    fragment_scores_ref,
    intersect_ref,
    proximity_window_ref,
)


@pytest.mark.parametrize("b,l,n", [(1, 4, 128), (3, 8, 256), (2, 2, 512), (5, 8, 128)])
@pytest.mark.parametrize("max_distance", [2, 5, 7])
@pytest.mark.parametrize("dtype", [np.int32, np.uint8])
def test_proximity_kernel_sweep(b, l, n, max_distance, dtype):
    rng = np.random.default_rng(b * 1000 + l * 10 + max_distance)
    occ = (rng.random((b, l, n)) < 0.1).astype(dtype)
    mult = np.zeros((b, l), np.int32)
    active = rng.integers(1, l + 1)
    mult[:, :active] = rng.integers(1, 3, (b, active))
    emit_k, start_k = proximity_window(
        jnp.asarray(occ), jnp.asarray(mult), max_distance,
        compute_dtype=np.dtype(dtype).name,
    )
    emit_r, start_r = proximity_window_ref(
        jnp.asarray(occ.astype(np.int32)), jnp.asarray(mult), max_distance
    )
    np.testing.assert_array_equal(np.asarray(emit_k), np.asarray(emit_r))
    np.testing.assert_array_equal(
        np.where(np.asarray(emit_r), np.asarray(start_k), 0),
        np.where(np.asarray(emit_r), np.asarray(start_r), 0),
    )


@pytest.mark.parametrize("na,nb,univ", [(128, 256, 1000), (512, 512, 800), (256, 1024, 10**6)])
@pytest.mark.parametrize("n_chunks", [2, 4])
def test_intersect_kernel_sweep(na, nb, univ, n_chunks):
    rng = np.random.default_rng(na + nb)
    a_real = np.sort(rng.choice(univ, min(na - 16, univ - 1), replace=False)).astype(np.int32)
    a = np.concatenate([a_real, np.full(na - len(a_real), PAD, np.int32)])
    b_real = np.sort(rng.choice(univ, min(nb - 32, univ - 1), replace=False)).astype(np.int32)
    b = np.concatenate([b_real, np.full(nb - len(b_real), PAD, np.int32)])
    off = block_offsets(a, b, 128, 256)
    got = intersect_sorted(jnp.asarray(a), jnp.asarray(b), jnp.asarray(off),
                           n_chunks=n_chunks)
    ref = intersect_ref(jnp.asarray(a), jnp.asarray(b))
    if n_chunks * 256 >= nb:  # full coverage guaranteed
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    else:  # partial tiles may under-report but never false-positive
        assert np.all(np.asarray(got) <= np.asarray(ref))


def test_embedding_bag_ref_matches_loop():
    rng = np.random.default_rng(0)
    table = rng.normal(size=(50, 8)).astype(np.float32)
    ids = rng.integers(-1, 50, (6, 5)).astype(np.int32)
    w = rng.normal(size=(6, 5)).astype(np.float32)
    got = np.asarray(embedding_bag_ref(jnp.asarray(table), jnp.asarray(ids), jnp.asarray(w)))
    for i in range(6):
        exp = np.zeros(8, np.float32)
        for j in range(5):
            if ids[i, j] >= 0:
                exp += table[ids[i, j]] * w[i, j]
        np.testing.assert_allclose(got[i], exp, rtol=1e-5)


def test_fragment_scores():
    emit = jnp.asarray([[False, True, False, True]])
    start = jnp.asarray([[0, 0, 0, 2]])
    s = np.asarray(fragment_scores_ref(emit, start))
    # spans: 1 (pos1, start0) and 1 (pos3, start2) -> 2 * 1/4
    np.testing.assert_allclose(s, [0.5])


def test_fused_scores_kernel_vs_ref():
    rng = np.random.default_rng(7)
    occ = (rng.random((4, 8, 128)) < 0.12).astype(np.int32)
    mult = np.tile([1, 1, 2, 0, 0, 0, 0, 0], (4, 1)).astype(np.int32)
    for use_kernel in (False, True):
        emit, start, scores = proximity_search_scores(
            jnp.asarray(occ), jnp.asarray(mult), 5, use_kernel=use_kernel
        )
        if use_kernel:
            np.testing.assert_allclose(np.asarray(scores), ref_scores, rtol=1e-6)
        else:
            ref_scores = np.asarray(scores)
