"""Benchmark harness: one section per paper table + system benches.

Prints ``name,us_per_call,derived`` CSV rows (per harness contract) and a
human-readable table; roofline sections read the dry-run artifacts.
``--json`` additionally records the serving comparison (seed per-subquery
path vs fused query-at-a-time batch) in ``BENCH_serving.json``, the
indexing/persistence numbers in ``BENCH_indexing.json``, and the §14
resilience numbers (recovery time, degraded p50/p99, the seeded
chaos-differential gate) in ``BENCH_robustness.json``, and the §16 serving
daemon's traffic profile (closed/open-loop QPS, tail latency, batch
occupancy, exactness sampling) in ``BENCH_traffic.json``.

Run: PYTHONPATH=src python -m benchmarks.run [--quick] [--json]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmarks.load import (  # noqa: E402
    bench_traffic,
    print_rows as print_traffic_rows,
    traffic_gates,
)
from benchmarks.paper_tables import (  # noqa: E402
    INGEST_SPEEDUP_GATE,
    bench_algorithms,
    bench_arena,
    bench_duplicates,
    bench_frontend,
    bench_indexing,
    bench_ingest,
    bench_overlap,
    bench_persistence,
    bench_robustness,
    bench_roofline,
    bench_serving,
    bench_serving_results_match,
    bench_vectorized,
)
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

# §15.3 gate: host readout's share of one batch's phase-bracketed wall time.
# The §15.1 device-side assembly + lazy materialization must keep the host's
# post-compute work a thin constant slice on both serving paths.
READOUT_FRACTION_GATE = 0.10


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--json",
        action="store_true",
        help="write the serving comparison to BENCH_serving.json",
    )
    args = ap.parse_args()
    enable_compile_cache()
    n_queries = 10 if args.quick else 30

    print("name,us_per_call,derived")

    # ---- paper Experiment 1/2 analogue: Fig.5/6 + postings tables ---------
    rows = bench_algorithms(n_queries=n_queries)
    se1_ms = next(r["avg_ms"] for r in rows if r["algorithm"] == "SE1")
    for r in rows:
        speedup = se1_ms / r["avg_ms"] if r["avg_ms"] else 0.0
        print(f"paper_fig5_{r['algorithm']},{r['avg_ms']*1000:.1f},"
              f"speedup_vs_SE1={speedup:.2f}")
        print(f"paper_postings_{r['algorithm']},{r['avg_postings']:.0f},"
              f"avg_kb={r['avg_kb']:.1f};intermediate={r['avg_intermediate']:.0f};"
              f"results={r['avg_results']:.1f}")

    # ---- §12 duplicate-lemma case ------------------------------------------
    dup = bench_duplicates()
    for name, d in dup.items():
        print(f"paper_dup_{name},{d['ms']*1000:.1f},"
              f"postings={d['postings']};intermediate={d['intermediate']};"
              f"results={d['results']}")

    # ---- vectorized / Pallas engines ---------------------------------------
    for r in bench_vectorized():
        print(f"engine_{r['engine']},{r['avg_ms']*1000:.1f},results={r['results']}")

    # ---- fused batched serving vs seed per-subquery path --------------------
    committed = Path(__file__).parent.parent / "BENCH_serving.json"
    committed_speedup = None
    if committed.exists():
        try:
            committed_speedup = json.loads(committed.read_text())["speedup"]
        except (json.JSONDecodeError, KeyError):
            pass
    serving = bench_serving(repeats=2 if args.quick else 5)
    for path in ("per_subquery_seed", "fused_batch"):
        print(f"serving_{path},{serving[path]['us_per_call']:.1f},"
              f"results={serving[path]['results']}")
    print(f"serving_speedup,{serving['speedup']:.2f},"
          f"dispatches_per_batch="
          f"{serving['fused_batch']['device_dispatches_per_batch']:.0f}")
    for phase, us in serving["fused_batch"]["phases_us_per_batch"].items():
        print(f"serving_phase_{phase.removesuffix('_us')},{us:.0f},per_batch")
    print(f"serving_readout_fraction,"
          f"{serving['fused_batch']['readout_fraction']:.3f},"
          f"gate={READOUT_FRACTION_GATE}")
    # CI gate (benchmarks/README.md): with the §15.1 device-side assembly the
    # host readout must stay a thin slice of the batch
    if serving["fused_batch"]["readout_fraction"] >= READOUT_FRACTION_GATE:
        print(f"readout_fraction_GATE,0,"
              f"fused={serving['fused_batch']['readout_fraction']:.3f};"
              f"gate={READOUT_FRACTION_GATE}")
        sys.exit(1)
    if not bench_serving_results_match(serving):
        print("serving_results_MISMATCH,0,"
              f"seed={serving['per_subquery_seed']['results']};"
              f"fused={serving['fused_batch']['results']}")
        sys.exit(1)
    # CI gate (benchmarks/README.md): the fused path's µs/query advantage
    # over the seed path — a SAME-RUN ratio, so machine speed cancels —
    # must stay within 2x of the committed BENCH_serving.json speedup
    if (
        committed_speedup is not None
        and serving["speedup"] < 0.5 * committed_speedup
    ):
        print(f"serving_fused_REGRESSION,{serving['speedup']:.2f},"
              f"committed_speedup={committed_speedup:.2f};gate=0.5x")
        sys.exit(1)

    # ---- device-resident posting arena vs host-pack path (DESIGN.md §13) ---
    arena = bench_arena(quick=args.quick, repeats=3 if args.quick else 5)
    for path in ("host_pack", "arena_path"):
        print(f"arena_{path},{arena[path]['us_per_query']:.1f},"
              f"results={arena[path]['results']}")
    print(f"arena_speedup,{arena['speedup']:.2f},"
          f"dispatches_per_batch={arena['device_dispatches_per_batch']};"
          f"hit_rate={arena['arena']['hit_rate']:.2f};"
          f"resident_mb={arena['arena']['resident_bytes'] / (1 << 20):.1f};"
          f"h2d_per_batch={arena['arena']['h2d_bytes_per_batch']};"
          f"upload_ms={arena['arena']['upload_sec'] * 1e3:.0f}")
    for phase, us in arena["arena_path"]["phases_us_per_batch"].items():
        print(f"arena_phase_{phase.removesuffix('_us')},{us:.0f},per_batch")
    print(f"arena_readout_fraction,"
          f"{arena['arena_path']['readout_fraction']:.3f},"
          f"gate={READOUT_FRACTION_GATE}")
    # CI gates (benchmarks/README.md): the arena must be invisible in
    # results, keep one-dispatch-per-batch serving, and hold the §15.3
    # readout budget on its own path too
    if not arena["results_match"]:
        print("arena_results_MISMATCH,0,arena != host-pack fragments")
        sys.exit(1)
    if arena["device_dispatches_per_batch"] != 1:
        print(f"arena_dispatch_GATE,0,"
              f"dispatches={arena['device_dispatches_per_batch']}")
        sys.exit(1)
    if arena["arena_path"]["readout_fraction"] >= READOUT_FRACTION_GATE:
        print(f"readout_fraction_GATE,0,"
              f"arena={arena['arena_path']['readout_fraction']:.3f};"
              f"gate={READOUT_FRACTION_GATE}")
        sys.exit(1)
    serving["arena"] = arena

    # ---- §15.2 pipelined dispatch: two-deep overlap vs serial loop ----------
    overlap = bench_overlap(
        n_queries=8 if args.quick else 16, repeats=2 if args.quick else 3
    )
    print(f"overlap_serial,{overlap['serial_us_per_query']:.1f},"
          f"max_batch={overlap['max_batch']}")
    print(f"overlap_pipelined,{overlap['pipelined_us_per_query']:.1f},"
          f"speedup={overlap['overlap_speedup']:.2f}")
    # CI gate (benchmarks/README.md): the pipelined driver must be invisible
    # in results — byte-identical responses in admission order
    if not overlap["results_match"]:
        print("overlap_results_MISMATCH,0,pipelined != serial responses")
        sys.exit(1)
    serving["overlap"] = overlap

    # ---- §15.4 serving-program roofline (fused + arena compiled HLO) --------
    roofline = bench_roofline()
    for prog in ("fused", "arena"):
        if prog not in roofline:
            continue
        r = roofline[prog]
        print(f"roofline_serving_{prog},{r['step_lower_bound_s']*1e6:.0f},"
              f"dominant={r['dominant']};"
              f"intensity={r['arithmetic_intensity']:.4f};"
              f"ridge={r['ridge_intensity']:.0f}")
    serving["roofline"] = roofline

    # ---- planner + deadline-aware frontend (cache hit rate, tail latency) ---
    frontend = bench_frontend(
        n_queries=16 if args.quick else 32, repeats=2 if args.quick else 3
    )
    for path in ("cold", "warm_cached"):
        extra = (
            f";hit_rate={frontend[path]['hit_rate']:.2f}"
            if path == "warm_cached" else ""
        )
        print(f"frontend_{path},{frontend[path]['us_per_query']:.1f},"
              f"p50_us={frontend[path]['p50_us']:.1f};"
              f"p99_us={frontend[path]['p99_us']:.1f}{extra}")
    print(f"frontend_microbatch,{frontend['microbatch']['us_per_query']:.1f},"
          f"dispatches={frontend['microbatch']['device_dispatches']}")
    print(f"frontend_deadline,{frontend['deadline']['budget_postings']:.0f},"
          f"partials={frontend['deadline']['partial_responses']};"
          f"skipped_subqueries={frontend['deadline']['skipped_subqueries']}")
    # CI gates (benchmarks/README.md): the planner/caching layer must be
    # invisible in results, and a repeat pass must be fully cache-served
    if not frontend["results_match_unplanned"]:
        print("frontend_results_MISMATCH,0,planned != unplanned fragments")
        sys.exit(1)
    if frontend["warm_cached"]["hit_rate"] < 1.0:
        print(f"frontend_cache_MISS,0,"
              f"hit_rate={frontend['warm_cached']['hit_rate']:.2f}")
        sys.exit(1)
    serving["frontend"] = frontend
    if args.json:
        out_path = Path(__file__).parent.parent / "BENCH_serving.json"
        out_path.write_text(json.dumps(serving, indent=2) + "\n")
        print(f"# wrote {out_path}")

    # ---- index construction: full build vs incremental ingest vs compact ----
    indexing = bench_indexing(quick=args.quick)
    for path in ("full_build", "incremental_pinned", "incremental_refresh"):
        print(f"indexing_{path},{indexing[path]['sec']*1e6:.0f},"
              f"docs_per_sec={indexing[path]['docs_per_sec']:.1f}")
    print(f"indexing_compact,{indexing['compact']['sec']*1e6:.0f},"
          f"segments_merged={indexing['compact']['segments_merged']};"
          f"docs_per_sec={indexing['compact']['docs_per_sec']:.1f}")
    if not indexing["results_match_rebuild"]:
        print(f"indexing_results_MISMATCH,0,{indexing['mismatch_reason']}")
        sys.exit(1)

    # ---- §17 external-memory bulk ingest ------------------------------------
    ingest = bench_ingest(
        quick=args.quick,
        artifact_dir=(Path(__file__).parent.parent / "artifacts"
                      / "ingest_spills") if args.json else None,
    )
    print(f"ingest_bulk,{ingest['bulk']['sec']*1e6:.0f},"
          f"docs_per_sec={ingest['bulk']['docs_per_sec']:.1f};"
          f"lemmatize_s={ingest['bulk']['lemmatize_s']:.2f};"
          f"spill_s={ingest['bulk']['spill_s']:.2f};"
          f"merge_s={ingest['bulk']['merge_s']:.2f};"
          f"spill_bytes={ingest['bulk']['spill_bytes']}")
    print(f"ingest_full_build_same_run,"
          f"{ingest['full_build_same_run']['sec']*1e6:.0f},"
          f"docs_per_sec={ingest['full_build_same_run']['docs_per_sec']:.1f};"
          f"same_run_ratio={ingest['speedup_same_run']:.2f}")
    # CI gates (benchmarks/README.md): the published bulk snapshot must be
    # index_sets_equal to the in-RAM build (exactness first), and bulk
    # throughput must clear 10x the frozen pre-§17 full-build figure
    if not ingest["ingest_equality"]:
        print(f"ingest_equality_GATE,0,{ingest['mismatch_reason']}")
        sys.exit(1)
    if ingest["speedup_vs_seed_full_build"] < INGEST_SPEEDUP_GATE:
        print(f"ingest_speedup_GATE,0,"
              f"speedup={ingest['speedup_vs_seed_full_build']:.2f};"
              f"gate={INGEST_SPEEDUP_GATE}x_vs_"
              f"{ingest['seed_full_build_docs_per_sec']}_docs_per_sec")
        sys.exit(1)
    print(f"ingest_speedup,{ingest['bulk']['sec']*1e6:.0f},"
          f"vs_seed_full_build={ingest['speedup_vs_seed_full_build']:.2f}x;"
          f"gate={INGEST_SPEEDUP_GATE}x")
    indexing["ingest"] = ingest

    # ---- durable index store: snapshot / restore / compression --------------
    persistence = bench_persistence(quick=args.quick)
    for path in ("snapshot", "rebuild", "restore"):
        print(f"persistence_{path},{persistence[path]['sec']*1e6:.0f},"
              f"docs_per_sec={persistence[path]['docs_per_sec']:.1f}")
    print(f"persistence_cold_boot,{persistence['restore']['sec']*1e6:.0f},"
          f"speedup_vs_rebuild={persistence['restore']['speedup_vs_rebuild']:.1f};"
          f"first_touch_us={persistence['first_touch']['sec']*1e6:.0f}")
    print(f"persistence_compression,{persistence['compression']['posting_blob_bytes']},"
          f"ratio={persistence['compression']['ratio']:.2f};"
          f"memory_bytes={persistence['compression']['memory_bytes']}")
    # CI gates (benchmarks/README.md): restore must be exact, the §12.1 codec
    # must actually compress, and the cold-boot claim must hold with margin
    if not persistence["restore_equality"]:
        print(f"persistence_restore_MISMATCH,0,{persistence['mismatch_reason']}")
        sys.exit(1)
    if persistence["compression"]["ratio"] < 1.5:
        print(f"persistence_compression_LOW,0,"
              f"ratio={persistence['compression']['ratio']:.2f}")
        sys.exit(1)
    if persistence["restore"]["speedup_vs_rebuild"] < 5.0:
        print(f"persistence_cold_boot_SLOW,0,"
              f"speedup={persistence['restore']['speedup_vs_rebuild']:.1f}")
        sys.exit(1)
    indexing["persistence"] = persistence
    if args.json:
        out_path = Path(__file__).parent.parent / "BENCH_indexing.json"
        out_path.write_text(json.dumps(indexing, indent=2) + "\n")
        print(f"# wrote {out_path}")

    # ---- resilient serving under injected faults (DESIGN.md §14) -----------
    robustness = bench_robustness(quick=args.quick)
    ff, deg, rec, chaos, walrep = (robustness[k] for k in
                                   ("fault_free", "degraded", "recovery",
                                    "chaos", "wal_replay"))
    print(f"robustness_fault_free,{ff['p50_us']:.0f},"
          f"p99_us={ff['p99_us']:.0f};counters_clean={ff['counters_clean']}")
    print(f"robustness_degraded,{deg['p50_us']:.0f},"
          f"p99_us={deg['p99_us']:.0f};flagged_rate={deg['flagged_rate']:.2f}")
    print(f"robustness_recovery,{rec['batch_ms']*1000:.0f},"
          f"batch_ms={rec['batch_ms']:.1f};"
          f"fault_free_batch_ms={rec['fault_free_batch_ms']:.1f}")
    print(f"robustness_chaos,{chaos['responses']},"
          f"seeds={len(chaos['seeds'])};flagged={chaos['flagged']};"
          f"faults_fired={chaos['faults_fired']};"
          f"mismatches={chaos['mismatches']}")
    print(f"robustness_wal_replay,{walrep['ms_per_1k_records']:.1f},"
          f"records={walrep['records']};replay_ms={walrep['replay_ms']:.1f};"
          f"results_match={walrep['results_match']}")
    # CI gates (benchmarks/README.md): under ANY seeded fault schedule every
    # response must be exact or flagged-partial-with-exact-coverage; the
    # chaos sweep must actually exercise the degraded path (flagged >= 1);
    # a degraded fan-out must flag 100% of its responses; fault-free
    # traffic must leave every §14 counter zero; and §18.2 recovery must
    # stay within 10x of the fault-free batch (the MTTR bound)
    if chaos["mismatches"] or not robustness["results_match"]:
        print(f"chaos_results_MISMATCH,0,mismatches={chaos['mismatches']};"
              f"fault_free={ff['results_match']};"
              f"degraded={deg['results_match']};"
              f"recovery={rec['results_match']};"
              f"wal_replay={walrep['results_match']}")
        sys.exit(1)
    if chaos["flagged"] < 1:
        print(f"robustness_chaos_flag_GATE,0,flagged={chaos['flagged']};"
              "unrecoverable schedule produced no degraded responses")
        sys.exit(1)
    if deg["flagged_rate"] < 1.0:
        print(f"robustness_flag_GATE,0,flagged_rate={deg['flagged_rate']:.2f}")
        sys.exit(1)
    if not ff["counters_clean"]:
        print("robustness_counters_DIRTY,0,fault-free counters non-zero")
        sys.exit(1)
    if rec["batch_ms"] > 10 * rec["fault_free_batch_ms"]:
        print(f"robustness_mttr_GATE,0,batch_ms={rec['batch_ms']:.1f};"
              f"fault_free_batch_ms={rec['fault_free_batch_ms']:.1f};"
              "recovery batch exceeded 10x fault-free")
        sys.exit(1)
    if args.json:
        out_path = Path(__file__).parent.parent / "BENCH_robustness.json"
        out_path.write_text(json.dumps(robustness, indent=2) + "\n")
        print(f"# wrote {out_path}")

    # ---- §16 serving daemon under load: traffic profile + gates -------------
    committed_traffic_path = Path(__file__).parent.parent / "BENCH_traffic.json"
    committed_traffic = None
    if committed_traffic_path.exists():
        try:
            committed_traffic = json.loads(committed_traffic_path.read_text())
        except json.JSONDecodeError:
            pass
    traffic = bench_traffic(quick=args.quick)
    print_traffic_rows(traffic)
    # CI gates (benchmarks/README.md): sampled daemon responses must match
    # the single-frontend reference or carry a partial/shed flag; the virtual
    # replay must show continuous batching (occupancy > 1); and the SAME-RUN
    # batched-vs-serial QPS ratio must stay within 2x of the committed one
    traffic_failures = traffic_gates(traffic, committed=committed_traffic)
    for name, value, detail in traffic_failures:
        print(f"{name},{value},{detail}")
    if traffic_failures:
        sys.exit(1)
    if args.json:
        committed_traffic_path.write_text(json.dumps(traffic, indent=2) + "\n")
        print(f"# wrote {committed_traffic_path}")

    # ---- roofline (from dry-run artifacts, if present) ----------------------
    try:
        from benchmarks.roofline import load_records, roofline_terms

        recs = load_records()
        for r in recs:
            t = roofline_terms(r)
            print(f"roofline_{r['arch']}__{r['shape']},"
                  f"{t['step_lower_bound_s']*1e6:.0f},"
                  f"dominant={t['dominant']};frac={t['roofline_fraction']:.3f};"
                  f"model_over_hlo={t['model_over_hlo_flops']:.3f}")
    except Exception as e:  # artifacts absent on a fresh checkout
        print(f"roofline_skipped,0,reason={type(e).__name__}")


if __name__ == "__main__":
    main()
