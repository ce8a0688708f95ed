"""Device-side, fixed-shape serving & index-build steps for ``paper_search``.

These are the jit-compiled programs the dry-run lowers for the paper's own
architecture — the full query pipeline after host-side key lookup:

  ``serve_step``:  postings -> (scatter) per-cluster occupancy -> parallel
                   window cover -> §14 relevance -> per-query top-k docs.
  ``build_step``:  token streams -> windowed stop-lemma triple extraction
                   (the (f,s,t) index build cost model) -> key histogram.

Shapes: B queries x P postings x C candidate clusters x L lemmas x N window
positions — all static budgets (real serving packs variable work into these,
exactly like padded batching in LM serving).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..core.window import window_cover_batch

__all__ = ["serve_step", "build_step"]




@functools.partial(
    jax.jit,
    static_argnames=("max_distance", "top_k", "n_clusters", "window_len", "compute_dtype"),
)
def serve_step(
    postings: jax.Array,  # [B, P, 3] int32: (cluster, rel_pos, lemma) pad=-1
    cluster_doc: jax.Array,  # [B, C] int32 doc id per cluster (pad=-1)
    mult: jax.Array,  # [B, L] int32 subquery multiplicities
    *,
    max_distance: int,
    n_clusters: int,
    window_len: int,
    top_k: int = 16,
    compute_dtype: str = "uint8",  # §Perf-3: occupancy/prefix counts fit u8
):
    """One fixed-shape serving step: scatter §10.4 posting events into
    per-cluster occupancy, run the vectorized §10.2 window cover, score §14
    relevance and select per-query top-k docs (see module docstring)."""
    b, p, _ = postings.shape
    l = mult.shape[1]
    c, n = n_clusters, window_len
    cdt = jnp.dtype(compute_dtype)

    # ---- stage 1: scatter postings into per-cluster occupancy -------------
    cl = postings[..., 0]
    pos = postings[..., 1]
    lem = postings[..., 2]
    ok = (cl >= 0) & (pos >= 0) & (pos < n) & (lem >= 0)
    flat = (jnp.maximum(cl, 0) * l + jnp.maximum(lem, 0)) * n + jnp.maximum(pos, 0)
    occ_flat = jnp.zeros((b, c * l * n), cdt)
    occ = jax.vmap(
        lambda of, fl, okk: of.at[fl].max(okk.astype(cdt))
    )(occ_flat, flat, ok)
    occ = occ.reshape(b, c, l, n)

    # ---- stage 2: parallel window cover (the Combiner, vectorized) --------
    occ2 = occ.reshape(b * c, l, n)
    mult2 = jnp.repeat(mult, c, axis=0).astype(cdt)
    emit, start = window_cover_batch(occ2, mult2, window=2 * max_distance + 1)

    # ---- stage 3: §14 relevance + per-query top-k docs ---------------------
    span = jnp.arange(n, dtype=jnp.float32)[None, :] - start.astype(jnp.float32)
    contrib = jnp.where(emit, 1.0 / (span + 1.0) ** 2, 0.0)
    scores = contrib.sum(axis=-1).reshape(b, c)
    scores = jnp.where(cluster_doc >= 0, scores, -1.0)
    top_scores, top_idx = jax.lax.top_k(scores, min(top_k, c))
    top_docs = jnp.take_along_axis(cluster_doc, top_idx, axis=1)
    n_fragments = emit.reshape(b, c, n).sum(axis=(1, 2))
    return {
        "top_docs": top_docs,
        "top_scores": top_scores,
        "n_fragments": n_fragments,
    }


@functools.partial(
    jax.jit,
    static_argnames=("max_distance", "top_k", "n_clusters", "window_len", "compute_dtype"),
)
def serve_step_sharded(
    postings: jax.Array,  # [NS, B, P_loc, 3] int32, cluster ids shard-local
    cluster_doc: jax.Array,  # [NS, B, C_loc] int32
    mult: jax.Array,  # [B, L]
    *,
    max_distance: int,
    n_clusters: int,  # C_loc (per shard)
    window_len: int,
    top_k: int = 16,
    compute_dtype: str = "uint8",
):
    """Document-sharded serving (§Perf-3 iteration 3, the deployed layout).

    Each device owns one cluster shard's postings end-to-end: local scatter,
    local cover, local top-k.  The only collective is the final tree merge of
    per-shard top-k lists (KBs).  This is exactly DESIGN.md §4's
    document-parallel layout — B stays replicated, clusters are the grid.
    """
    mesh = jax.sharding.get_abstract_mesh()
    ns, b = postings.shape[0], postings.shape[1]
    kk = min(top_k, n_clusters)

    def local(post, cdoc, m):
        out = serve_step(
            post[0], cdoc[0], m,
            max_distance=max_distance, n_clusters=n_clusters,
            window_len=window_len, top_k=kk, compute_dtype=compute_dtype,
        )
        return (
            out["top_docs"][None],
            out["top_scores"][None],
            out["n_fragments"][None],
        )

    if mesh.axis_names:
        axes = tuple(mesh.axis_names)
        inner = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axes), P(axes), P()),
            out_specs=(P(axes), P(axes), P(axes)),
            check_vma=False,
        )
        docs, scores, nfrag = inner(postings, cluster_doc, mult)
    else:
        # host fallback (tests): vmap over the shard axis
        docs, scores, nfrag = jax.vmap(
            lambda pp, cc: tuple(x[0] for x in local(pp[None], cc[None], mult))
        )(postings, cluster_doc)
    # tree merge: [NS, B, K] -> global top-k per query (tiny all-gather)
    docs_t = docs.transpose(1, 0, 2).reshape(b, -1)
    scores_t = scores.transpose(1, 0, 2).reshape(b, -1)
    top_scores, idx = jax.lax.top_k(scores_t, min(top_k, scores_t.shape[-1]))
    top_docs = jnp.take_along_axis(docs_t, idx, axis=1)
    return {
        "top_docs": top_docs,
        "top_scores": top_scores,
        "n_fragments": nfrag.sum(axis=0),
    }


@functools.partial(jax.jit, static_argnames=("max_distance", "n_buckets"))
def build_step(
    tokens: jax.Array,  # [B, N] int32 lemma FL-numbers
    is_stop: jax.Array,  # [B, N] bool
    *,
    max_distance: int,
    n_buckets: int = 65536,
):
    """Windowed (f,s,t) co-occurrence extraction over token streams.

    For every center position and offset pair (d1, d2), d1 < d2, both within
    ±MaxDistance: a triple posting exists when all three positions hold stop
    lemmas.  Postings are hash-bucketed (the shard-local histogram a real
    builder uses to size posting lists before the big segmented sort).
    """
    b, n = tokens.shape
    d = max_distance
    t32 = tokens.astype(jnp.uint32)

    def shift(x, o):
        if o == 0:
            return x
        if o > 0:
            pad = jnp.zeros((b, o), x.dtype)
            return jnp.concatenate([pad, x[:, : n - o]], axis=1)
        pad = jnp.zeros((b, -o), x.dtype)
        return jnp.concatenate([x[:, -o:], pad], axis=1)

    hist = jnp.zeros((n_buckets,), jnp.int32)
    total = jnp.zeros((), jnp.int32)
    stop = is_stop.astype(jnp.int32)
    offsets = [
        (d1, d2)
        for d1 in range(-d, d + 1)
        for d2 in range(-d, d + 1)
        if d1 != 0 and d2 != 0 and d1 < d2
    ]
    for d1, d2 in offsets:  # static unroll: |offsets| = D*(2D-1)
        valid = stop * shift(stop, -d1) * shift(stop, -d2)
        s1 = shift(t32, -d1)
        s2 = shift(t32, -d2)
        h = (t32 * jnp.uint32(2654435761) ^ s1 * jnp.uint32(40503) ^ s2) % n_buckets
        hist = hist.at[h.reshape(-1)].add(valid.reshape(-1))
        total = total + valid.sum()
    return {"bucket_histogram": hist, "n_postings": total}
