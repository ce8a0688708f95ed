#!/usr/bin/env python3
"""Bring-up smoke run: the search serving path on one TPU chip.

    python3 chip_smoke.py [--passages N] [--seed S]

Run it from the root of a checkout.  Everything runs in this one process (a
chip belongs to one process at a time).  There is no CPU fallback: unless
JAX's first device is a TPU it exits non-zero before doing any work.

Phases, in order:

* corpus   a passage corpus shaped like MS MARCO passage ranking (Bajaj et
           al., arXiv:1611.09268): short web passages of about 60 tokens
           over a Zipf vocabulary (``synthesize_corpus``), made from
           ``--seed``;
* ingest   ``ShardedSearchService.bulk_ingest`` over 4 host-side shards with
           2 spawn workers, into a fresh directory that is removed at exit;
* serve    a ``ServingFrontend`` whose arena budget holds every arena family
           of every shard, ``warmup`` on the request slate, then a
           ``ServiceDaemon``.  The requests are the paper's stop-word
           queries plus a seeded STOP/FU/ORDINARY 0.5/0.3/0.2 mix
           (``benchmarks/load.py``); each asks for every matching passage,
           so the whole fragment set comes back;
* exact    every response's fragment set must equal the union of the host
           SE2.4 Combiner (``se24_combiner``) over its subqueries and shards;
* kernels  the same requests with ``use_kernel=True``, once through the
           arena (compiled Pallas gather kernel) and once through the
           host-pack path (compiled Pallas proximity-window and
           intersection kernels); every response must equal the first
           phase's.

Earlier lines report phase times, the arena's resident bytes (used and
padding), peak device memory, arena hits and misses, and device dispatches.
Only when every phase passed is the last line the JSON result
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# MS MARCO passage ranking's passage length, and the passage count this
# smoke aims at (one chip's share of a passage deployment)
PASSAGE_TOKENS = 60
TARGET_PASSAGES = 100_000
DEFAULT_PASSAGES = TARGET_PASSAGES
VOCAB_SIZE = 30_000
N_SHARDS = 4
INGEST_WORKERS = 2
SW_COUNT, FU_COUNT, MAX_DISTANCE = 60, 150, 5
PAPER_QUERIES = ("to be or not to be", "who are you who")
N_MIX = 30
# one batch holds the whole slate, so each phase compiles one device
# program: the TPU compiler takes minutes for one arena program (its
# sorts and searches), and every extra bucket would add that again
MAX_BATCH = len(PAPER_QUERIES) + N_MIX
# ask for every matching passage: responses then carry the whole fragment
# set, which is what the exactness check compares
TOP_K_ALL = 1 << 20
MIN_ARENA_BYTES = 1 << 30
TICKET_TIMEOUT_S = 300.0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def _frag_set(resp) -> set:
    return {(f.doc_id, f.start, f.end) for d in resp.docs for f in d.fragments}


def _ranking(resp) -> list:
    return [
        (d.doc_id, d.score, [(f.doc_id, f.start, f.end) for f in d.fragments])
        for d in resp.docs
    ]


def _serve(frontend, requests) -> tuple[list, dict]:
    """Warm the frontend up on the slate, then serve it through a started
    daemon.  The tickets are queued before the daemon starts, so its
    batches are the slate's ``max_batch`` chunks, the shapes the warm-up
    compiled."""
    from repro.search import fused
    from repro.search.service import ServiceDaemon

    t0 = time.perf_counter()
    warm = frontend.warmup(queries=[r.query for r in requests], top_k=TOP_K_ALL)
    t1 = time.perf_counter()
    d0 = fused.dispatch_count()
    daemon = ServiceDaemon(frontend, max_queue=len(requests))
    tickets = [daemon.submit(r) for r in requests]
    daemon.start()
    try:
        responses = [t.result(timeout=TICKET_TIMEOUT_S) for t in tickets]
    finally:
        daemon.stop()
    t2 = time.perf_counter()
    for r in responses:
        if r.stats.partial or r.stats.shed:
            raise SmokeFailure(f"{r.query!r}: partial or shed response")
        if len(_frag_set(r)) != r.stats.results:
            raise SmokeFailure(f"{r.query!r}: response lacks some matching passages")
    return responses, {
        "warmup_s": t1 - t0,
        "warmup_programs": warm["programs"],
        "serve_s": t2 - t1,
        "dispatches": fused.dispatch_count() - d0,
        "batches": daemon.metrics()["batches"],
        "arena_key_hits": sum(r.stats.arena_hits for r in responses),
        "arena_key_misses": sum(r.stats.arena_misses for r in responses),
    }


def run(
    passages: int = DEFAULT_PASSAGES,
    seed: int = 0,
    *,
    workers: int = INGEST_WORKERS,
    n_mix: int = N_MIX,
    min_arena_bytes: int = MIN_ARENA_BYTES,
) -> dict:
    """Every phase of the smoke run on JAX's default device; raises
    :class:`SmokeFailure` on a wrong result.  Returns the phase report."""
    import jax

    from benchmarks.load import make_query_mix
    from repro.core.combiner import se24_combiner
    from repro.core.keys import expand_subqueries
    from repro.index import synthesize_corpus
    from repro.search.distributed import ShardedSearchService
    from repro.search.frontend import SearchRequest, ServingFrontend

    report: dict = {"passages": passages}
    t = time.perf_counter()
    store = synthesize_corpus(
        n_docs=passages, doc_len=PASSAGE_TOKENS, vocab_size=VOCAB_SIZE, seed=seed
    )
    report["synthesis_s"] = time.perf_counter() - t
    report["tokens"] = store.total_positions()
    log(f"corpus: {len(store)} passages, {report['tokens']} tokens "
        f"in {report['synthesis_s']:.1f} s")

    with tempfile.TemporaryDirectory(dir=ROOT, prefix=".chip_smoke_") as tmp:
        t = time.perf_counter()
        svc, _ = ShardedSearchService.bulk_ingest(
            store, Path(tmp) / "index", n_shards=N_SHARDS, sw_count=SW_COUNT,
            fu_count=FU_COUNT, max_distance=MAX_DISTANCE, workers=workers,
        )
        report["ingest_s"] = time.perf_counter() - t
        log(f"ingest: {N_SHARDS} shards, {workers} spawn workers, "
            f"{report['ingest_s']:.1f} s")

        queries = list(PAPER_QUERIES) + make_query_mix(store, svc, n_mix, seed)
        requests = [SearchRequest(q, top_k=TOP_K_ALL) for q in queries]
        log(f"requests: {len(requests)} ({len(PAPER_QUERIES)} paper stop-word "
            f"queries + {n_mix} seeded STOP/FU/ORDINARY mix)")

        # ---- phase 1: default path, every arena family resident ----------
        front = ServingFrontend(svc, max_batch=MAX_BATCH, arena_budget_mb=12 * 1024)
        base, p1 = _serve(front, requests)
        am = front.arena.metrics()
        report["arena_upload_s"] = am["arena_upload_sec"]
        report["arena_bytes"] = am["arena_bytes"]
        report["arena_used_bytes"] = am["arena_used_bytes"]
        log(f"arena: {am['arena_entries']} resident families, "
            f"{am['arena_bytes']} bytes ({am['arena_used_bytes']} used, "
            f"{am['arena_bytes'] - am['arena_used_bytes']} padding), "
            f"uploaded in {am['arena_upload_sec']:.1f} s; family acquires "
            f"{am['arena_hits']} hits / {am['arena_misses']} misses")
        log(f"phase default: warm-up {p1['warmup_s']:.1f} s "
            f"(compile {p1['warmup_s'] - am['arena_upload_sec']:.1f} s + arena "
            f"upload; {p1['warmup_programs']} programs), serve "
            f"{p1['serve_s']:.2f} s in {p1['batches']} batches, "
            f"{p1['dispatches']} device dispatches, arena keys "
            f"{p1['arena_key_hits']} hits / {p1['arena_key_misses']} misses")
        report["default"] = p1
        if am["arena_entries"] != 4 * N_SHARDS:
            raise SmokeFailure("not every arena family is resident")
        if am["arena_bytes"] < min_arena_bytes:
            raise SmokeFailure(
                f"device-resident arena holds {am['arena_bytes']} bytes, "
                f"below {min_arena_bytes}"
            )

        # ---- exactness against the host SE2.4 Combiner --------------------
        t = time.perf_counter()
        n_frags = 0
        for resp in base:
            want = set()
            for sub in expand_subqueries(resp.query, svc.lemmatizer):
                for shard in svc.shards:
                    got, _ = se24_combiner(sub, shard)
                    want.update((r.doc_id, r.start, r.end) for r in got)
            if _frag_set(resp) != want:
                raise SmokeFailure(
                    f"{resp.query!r}: {len(_frag_set(resp))} fragments served, "
                    f"{len(want)} from se24_combiner"
                )
            n_frags += len(want)
        report["reference_s"] = time.perf_counter() - t
        report["fragments"] = n_frags
        log(f"exact: {len(base)}/{len(base)} responses equal se24_combiner "
            f"({n_frags} fragments; host reference {report['reference_s']:.1f} s)")

        # ---- phase 2: compiled Pallas kernels -----------------------------
        kernel_fronts = {
            # arena path: the gather kernel slices the resident families
            "kernel-arena": ServingFrontend(
                svc, max_batch=MAX_BATCH, use_kernel=True, arena=front.arena
            ),
            # host-pack path: proximity-window and intersection kernels
            "kernel-hostpack": ServingFrontend(svc, max_batch=MAX_BATCH, use_kernel=True),
        }
        for name, fe in kernel_fronts.items():
            got, p = _serve(fe, requests)
            for a, b in zip(base, got):
                if _ranking(a) != _ranking(b):
                    raise SmokeFailure(f"{name}: {a.query!r} differs from the default path")
            log(f"phase {name}: warm-up {p['warmup_s']:.1f} s "
                f"({p['warmup_programs']} programs), serve {p['serve_s']:.2f} s "
                f"in {p['batches']} batches, {p['dispatches']} device dispatches, "
                f"arena keys {p['arena_key_hits']} hits / "
                f"{p['arena_key_misses']} misses; {len(got)}/{len(got)} "
                f"responses equal the default path")
            report[name] = p
        front.close()

    stats = jax.devices()[0].memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    log(f"device memory: peak_bytes_in_use {report['peak_bytes_in_use']}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--passages", type=int, default=DEFAULT_PASSAGES)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: src/repro not found next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this smoke run needs a TPU and has no CPU "
              f"fallback", file=sys.stderr)
        return 2
    log(f"jax {jax.__version__}; device {dev.device_kind} x{len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    if args.passages < TARGET_PASSAGES:
        log(f"passages: {args.passages} of the {TARGET_PASSAGES} target "
            f"(cut by --passages)")
    else:
        log(f"passages: {args.passages}")

    t0 = time.perf_counter()
    try:
        run(args.passages, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
