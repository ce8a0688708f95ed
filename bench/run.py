#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Everything is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json``, each per-layer metric's
reader in ``bench/metrics/<metric>.py`` and the device peaks in
``bench/peaks.json``.  There is no CPU fallback: unless JAX's first device
is a TPU, and there are as many as the cell asks for, it exits non-zero
before doing any work.

A run, in order:

* set-up   the configuration's corpus and index come from the checkout's
           cache (``bench/.cache``), built on the first run from the
           configuration's ``corpus_seed`` by the program's bulk ingest
           and restored on later runs the way a restarted server restores
           them; the window's bursts (``bench/traffic.py``) are planned,
           their device programs compiled in parallel on the first run,
           and each burst served once through a warm-up frontend, which
           uploads the device-resident posting arena and loads every
           program the window forms;
* window   ``--seconds`` of open-loop arrivals ordered by ``--seed``,
           served by a fresh ``ServingFrontend`` (empty result and posting
           caches) on the same arena behind a ``ServiceDaemon`` on its own
           thread; each burst is submitted at its due time, and latency
           runs from a request's due time to its completion.  With
           ``--trace 1`` the JAX profiler records the window;
* check    the answers of the window, waited for up to a minute past its
           close, a sample of them drawn from the seed compared with the
           plain reference (``bench/reference.py``).

Set-up progress goes to standard error, which ends with the numbers
compared and their limits; the last line of standard output is the JSON
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
GRACE_S = 60.0
# answered requests the check compares per run, drawn from the seed: the
# reference takes about a tenth of a second per query, and the check has
# to stay shorter than the window
CHECK_SAMPLE = 160
# programs compiled at once on the first run: each compile holds several GB
# of host memory, and a one-chip host has 40 GiB beside the index (four at
# once peaked at 34 GB on a TPU v5e host)
COMPILE_THREADS = 3
# numbers compared by the check, each with its limit (both exact: 0)
LIMITS = {"mismatched_responses": 0, "unanswered_requests": 0}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---- finding a cell by name ------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    config_bytes: bytes
    traffic_name: str
    traffic: dict
    traffic_bytes: bytes
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict = field(default_factory=dict)


def _applies(metric: dict, cell: str, reported: set[str] | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(name: str, benchmark_file: Path = ROOT / "BENCHMARK.json", bench_dir: Path = BENCH) -> Cell:
    """The cell ``name`` with its configuration, traffic and metric readers,
    each read from its own file."""
    spec = json.loads(Path(benchmark_file).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in {benchmark_file}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config_file = Path(benchmark_file).parent / configs[w["config"]]["file"]
    traffic_file = Path(bench_dir) / "traffic" / f"{w['traffic']}.json"
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    cell = Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=json.loads(config_file.read_text()),
        config_bytes=config_file.read_bytes(),
        traffic_name=w["traffic"],
        traffic=json.loads(traffic_file.read_text()),
        traffic_bytes=traffic_file.read_bytes(),
        end_to_end=e2e,
        per_layer=per_layer,
    )
    for m in per_layer:
        path = Path(bench_dir) / "metrics" / f"{m['name']}.py"
        mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{len(cell.readers)}", path)
        module = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(module)
        cell.readers[m["name"]] = module.read
    return cell


# ---- set-up ------------------------------------------------------------------


@dataclass
class Stack:
    cell: Cell
    corpus: object
    service: object  # the restored index
    arena: object  # the device-resident posting arena every frontend shares
    warm: object  # the frontend that served the warm-up
    catalog: list
    setup_items: dict
    cache_root: Path
    frontend: object = None  # the last window's frontend
    daemon: object = None  # the last window's daemon


def _digest(*parts: bytes) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p)
    return h.hexdigest()[:12]


def _reference(corpus, cfg: dict):
    from bench.reference import Reference

    return Reference(
        corpus, sw_count=cfg["sw_count"], fu_count=cfg["fu_count"], max_distance=cfg["max_distance"]
    )


def build_index(cell: Cell, cache_root: Path) -> tuple[object, Path, dict]:
    """The configuration's corpus and the directory of its ingested index,
    built once per checkout and configuration file."""
    from bench.corpus import Corpus, synthesize

    cfg = cell.config
    where = cache_root / f"{cell.config_name}-{_digest(cell.config_bytes)}"
    done = where / "complete"
    items: dict = {}
    if done.exists():
        t = time.perf_counter()
        corpus = Corpus.load(where / "corpus.npz")
        items["corpus_load_s"] = time.perf_counter() - t
        return corpus, where, items
    if where.exists():
        shutil.rmtree(where)
    where.mkdir(parents=True)
    t = time.perf_counter()
    corpus = synthesize(
        n_docs=cfg["n_docs"], vocab_size=cfg["vocab_size"], zipf_a=cfg["zipf_a"],
        phrase_rate=cfg["phrase_rate"], length=cfg["doc_length"], seed=cfg["corpus_seed"],
    )
    corpus.save(where / "corpus.npz")
    items["synthesis_s"] = time.perf_counter() - t
    log(f"corpus: {corpus.n_docs} documents, {len(corpus.tokens)} words, "
        f"synthesized in {items['synthesis_s']:.1f} s")

    from repro.index.corpus import DocumentStore
    from repro.search.distributed import ShardedSearchService

    t = time.perf_counter()
    store = DocumentStore.from_texts(corpus.texts())
    _svc, _ = ShardedSearchService.bulk_ingest(
        store, where / "index", n_shards=cfg["n_shards"], sw_count=cfg["sw_count"],
        fu_count=cfg["fu_count"], max_distance=cfg["max_distance"], workers=cfg["ingest_workers"],
    )
    del store, _svc
    items["ingest_s"] = time.perf_counter() - t
    log(f"ingest: {cfg['n_shards']} shards, {cfg['ingest_workers']} workers, "
        f"{items['ingest_s']:.1f} s")
    done.write_text("ok\n")
    return corpus, where, items


def load_catalog(cell: Cell, corpus, where: Path, n_bursts: int) -> list[list[str]]:
    """The window's bursts, drawn once per corpus, traffic file and length
    and kept beside the index."""
    from bench.reference import word_lemmas
    from bench.traffic import make_catalog

    path = where / f"catalog-{cell.traffic_name}-{_digest(cell.traffic_bytes)}-{n_bursts}.json"
    if path.exists():
        return json.loads(path.read_text())
    ref = _reference(corpus, cell.config)
    catalog = make_catalog(corpus, lambda w: ref.kind(word_lemmas(w)[0]), cell.traffic, n_bursts)
    path.write_text(json.dumps(catalog))
    return catalog


def compile_in_parallel(frontend, catalog: list, top_k: int, workers: int) -> dict:
    """Compile the arena programs of every burst at once, before the bursts
    are served one by one.

    A dry pass plans each burst through the frontend's real path with the
    device dispatch replaced by a stand-in that keeps the burst's plan and
    answers empty; then one thread per distinct program runs the plan
    through the real dispatch, which compiles the program into JAX's jit
    cache and its persistent cache, as serving would.  One compile takes a
    minute or more, one core and a few GB of host memory, so the pool
    divides the first run's set-up by about its width.  Batches that
    overflow the arena take the host pack, which the warm pass compiles in
    turn."""
    import repro.search.arena as arena_mod
    from repro.search.fused import PendingBatch, empty_batch_result

    real = arena_mod.run_arena_batch
    plans: dict = {}
    n_batches = 0

    def capture(plan, **kw):
        nonlocal n_batches
        n_batches += 1
        # the program's static arguments and argument shapes
        key = (plan.families, tuple(plan.e_budget), tuple(len(d) for d in plan.d_src), plan.query_budget,
               plan.n_budget, plan.row_budget, plan.lemma_budget, len(plan.n_keys), plan.key_budget,
               plan.doc_bits, plan.tier, plan.block, kw["max_distance"], kw["top_k"], kw.get("use_kernel", False))
        plans.setdefault(key, (plan, kw))
        empty = empty_batch_result(plan.n_queries, kw["top_k"])
        return PendingBatch(lambda: empty) if kw.get("defer") else empty

    arena_mod.run_arena_batch = capture
    try:
        for burst in catalog:
            frontend.warmup(queries=burst, top_k=top_k)
    finally:
        arena_mod.run_arena_batch = real

    def compile_one(item):
        plan, kw = item
        real(plan, max_distance=kw["max_distance"], top_k=kw["top_k"], use_kernel=kw.get("use_kernel", False))

    t = time.perf_counter()
    libc = _libc()
    if libc is not None:
        libc.mallopt(M_ARENA_MAX, 2)  # compiler threads would each keep a heap of their own
    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(compile_one, list(plans.values())))
    if libc is not None:
        libc.malloc_trim(0)
    return {"planned_batches": n_batches, "programs": len(plans), "compile_s": time.perf_counter() - t}


M_ARENA_MAX = -8  # glibc mallopt parameter


def _libc():
    import ctypes
    import ctypes.util

    try:
        return ctypes.CDLL(ctypes.util.find_library("c") or "libc.so.6")
    except OSError:
        return None


def set_up(cell: Cell, seconds: float, cache_root: Path = CACHE) -> Stack:
    """Restore (or build) the index and compile, load and run once every
    device program the window's bursts form."""
    from bench.traffic import bursts_due
    from repro.search import fused
    from repro.search.arena import PostingArena
    from repro.search.distributed import ShardedSearchService
    from repro.search.frontend import ServingFrontend

    cfg = cell.config
    corpus, where, items = build_index(cell, cache_root)
    t = time.perf_counter()
    svc = ShardedSearchService.restore(where / "index")
    items["restore_s"] = time.perf_counter() - t
    log(f"restore: {svc.n_shards} shards from {where.name} in {items['restore_s']:.1f} s")
    catalog = load_catalog(cell, corpus, where, bursts_due(cell.traffic, seconds))

    arena = PostingArena(budget_bytes=int(cfg["arena_budget_mb"] * (1 << 20)))
    warm = ServingFrontend(svc, max_batch=cfg["max_batch"], arena=arena)
    compiled = where / f"compiled-{_digest(json.dumps(catalog).encode())}"
    if not compiled.exists():
        c = compile_in_parallel(warm, catalog, cfg["top_k"], workers=max(1, min(COMPILE_THREADS, (os.cpu_count() or 2) // 2)))
        items["parallel_compile"] = c
        log(f"parallel compile: {c['programs']} programs for {c['planned_batches']} arena batches "
            f"in {c['compile_s']:.1f} s")
        compiled.write_text(json.dumps(c) + "\n")
    items["programs"] = []
    t_warm = time.perf_counter()
    for b, burst in enumerate(catalog):
        c0 = fused.compile_count()
        t = time.perf_counter()
        warm.warmup(queries=burst, top_k=cfg["top_k"])
        dt = time.perf_counter() - t
        new = (fused.compile_count() or 0) - (c0 or 0)
        items["programs"].append({"burst": b, "new_programs": new, "seconds": dt})
        log(f"warm-up burst {b}: {len(burst)} requests in {dt:.2f} s, {new} programs loaded or compiled")
    items["warm_s"] = time.perf_counter() - t_warm
    am = arena.metrics()
    items["arena_upload_s"] = am["arena_upload_sec"]
    items["arena_bytes"] = am["arena_bytes"]
    log(f"arena: {am['arena_entries']} families, {am['arena_bytes']} bytes "
        f"({am['arena_used_bytes']} used), uploaded in {am['arena_upload_sec']:.1f} s")
    log(f"warm pass: {items['warm_s']:.1f} s in all")
    return Stack(cell, corpus, svc, arena, warm, catalog, items, cache_root)


# ---- the window -------------------------------------------------------------


def _daemon(frontend, cfg: dict, burst: int, note):
    """The program's daemon, its scheduler step named in the trace."""
    from repro.search.service import ServiceDaemon

    class Daemon(ServiceDaemon):
        def pump(self):
            with note("bench.pump"):
                return super().pump()

    return Daemon(frontend, max_queue=cfg["max_queue"], batch_limit=burst)


def run_window(stack: Stack, seed: int, seconds: float, trace: bool, setup_s: float, traffic: dict | None = None):
    """Serve ``seconds`` of arrivals through a fresh frontend (empty result
    and posting caches) on the shared arena, with the daemon on its own
    thread; returns the run record and the completed responses as
    ``(query, response)`` pairs."""
    import jax

    from bench.readings import Request, RunRecord
    from bench.traffic import bursts_due, schedule
    from repro.search import fused
    from repro.search.frontend import SearchRequest, ServingFrontend

    cfg = stack.cell.config
    traffic = traffic or stack.cell.traffic
    catalog = stack.catalog[: bursts_due(traffic, seconds)]
    arrivals = schedule(catalog, traffic, seconds, seed)
    note = jax.profiler.TraceAnnotation if trace else (lambda _name: contextlib.nullcontext())
    front = ServingFrontend(stack.service, max_batch=cfg["max_batch"], arena=stack.arena)
    daemon = _daemon(front, cfg, int(traffic["burst"]), note)
    stack.frontend, stack.daemon = front, daemon
    clock = daemon.clock
    trace_dir = None
    if trace:
        trace_dir = Path(tempfile.mkdtemp(prefix="trace-", dir=stack.cache_root))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    daemon.start()
    submitted: list[list] = []
    lag = []
    c0 = fused.compile_count()
    t_open = clock.now()
    for arr in arrivals:
        with note("bench.wait_arrival"):
            time.sleep(max(0.0, arr.due - (clock.now() - t_open)))
        # a burst joins the queue whole: the daemon's lock is held across
        # its submits, so no batch takes part of it
        with daemon._work:
            submitted.append([daemon.submit(SearchRequest(q, top_k=cfg["top_k"])) for q in arr.queries])
        lag.append(clock.now() - t_open - arr.due)
    trace_span = None
    if trace:
        with note("bench.wait_arrival"):
            time.sleep(max(0.0, seconds - (clock.now() - t_open)))
        trace_span = clock.now() - t_open  # before the stop, which takes seconds to collect
        jax.profiler.stop_trace()
    tickets = [t for burst in submitted for t in burst]
    while not all(t.done() for t in tickets) and clock.now() - t_open < seconds + GRACE_S:
        time.sleep(0.01)
    closed = clock.now() - t_open
    daemon.stop(drain=False)
    compiles = None if c0 is None else fused.compile_count() - c0

    record = RunRecord(window_s=float(seconds), setup_s=setup_s, closed_s=closed, compiles_in_window=compiles)
    answered = []
    for arr, burst_tickets in zip(arrivals, submitted):
        launched = completed = None
        for q, t in zip(arr.queries, burst_tickets):
            req = Request(due=arr.due)
            if t.done() and not t.shed_at_queue:
                try:
                    resp = t.result(timeout=0)
                except Exception as exc:  # a failed batch: its answers never come
                    log(f"request {q!r} failed: {exc!r}")
                else:
                    req.launched = t.enqueued_at + t.queue_wait_sec - t_open
                    req.completed = t.enqueued_at + t.latency_sec - t_open
                    launched, completed = req.launched, req.completed
                    answered.append((q, resp))
            record.requests.append(req)
        if launched is not None:
            record.batches.append(
                {"launched": launched, "completed": completed, "size": len(burst_tickets), "burst": arr.burst}
            )
    log(f"window: {len(arrivals)} bursts of {traffic['burst']} due in {seconds} s, "
        f"{len(answered)} answered; generator lag mean {1e3 * sum(lag) / max(len(lag), 1):.1f} ms, "
        f"max {1e3 * max(lag, default=0.0):.1f} ms; {compiles} programs compiled in the window")
    if trace_dir is not None:
        from bench.trace import find_xplane, reduce_trace

        xplane = find_xplane(trace_dir)
        record.trace = reduce_trace(xplane) if xplane else None
        record.trace_window_s = trace_span
        shutil.rmtree(trace_dir, ignore_errors=True)
    return record, answered


# ---- the check ----------------------------------------------------------------


def as_ranking(resp) -> list:
    return [(int(d.doc_id), float(d.score), [(int(f.start), int(f.end)) for f in d.fragments]) for d in resp.docs]


def check_sample(answered: list, seed: int) -> list:
    """The answered requests the check compares: ``CHECK_SAMPLE`` of them,
    drawn from the seed, or all where there are fewer."""
    import numpy as np

    if len(answered) <= CHECK_SAMPLE:
        return list(answered)
    pick = np.random.default_rng([seed, 2]).choice(len(answered), CHECK_SAMPLE, replace=False)
    return [answered[i] for i in sorted(pick.tolist())]


def check(stack: Stack, record, answered, seed: int) -> dict:
    """A sample of the answered requests against the reference's answer
    to each query, and every request never answered."""
    t = time.perf_counter()
    ref = _reference(stack.corpus, stack.cell.config)
    top_k = stack.cell.config["top_k"]
    sample = check_sample(answered, seed)
    mismatched = sum(1 for q, resp in sample if as_ranking(resp) != ref.answer(q, top_k))
    unanswered = sum(1 for r in record.requests if r.completed is None)
    log(f"check: {len(sample)} of {len(answered)} responses against the reference "
        f"in {time.perf_counter() - t:.1f} s")
    return {"mismatched_responses": mismatched, "unanswered_requests": unanswered}


# ---- one run ------------------------------------------------------------------


def run_cell(
    cell: Cell, seed: int, seconds: float, trace: bool, cache_root: Path = CACHE, peaks_file: Path | None = None
) -> dict:
    """Set up, serve the window, check; returns the result object."""
    import jax

    from bench import readings
    from bench.roofline import PEAKS_FILE, batch_posting_bytes, device_peaks

    stack = set_up(cell, seconds, cache_root)
    setup_s = time.perf_counter() - T_START
    record, answered = run_window(stack, seed, seconds, trace, setup_s)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    metrics: dict = {}
    if trace:
        record.peaks = device_peaks(dev.device_kind, peaks_file or PEAKS_FILE)
        planner = stack.frontend.planner
        record.posting_bytes_per_burst = {
            b: batch_posting_bytes([planner.plan(q) for q in burst]) for b, burst in enumerate(stack.catalog)
        }
        for m in cell.per_layer:
            value = cell.readers[m["name"]](record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {
            "qps": lambda: readings.qps(record),
            "p50_ms": lambda: readings.latency_percentile(record, 50),
            "p90_ms": lambda: readings.latency_percentile(record, 90),
            "setup_s": lambda: record.setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]](), "unit": m["unit"]}
    stack.frontend.close()
    stack.warm.close()
    stack.arena.release()
    numbers = check(stack, record, answered, seed)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    out = {
        "correct": all(numbers[k] <= LIMITS[k] for k in LIMITS),
        "attempted": len(record.requests),
        "failed": sum(1 for r in record.requests if r.completed is None),
        "metrics": metrics,
        "device": device,
    }
    if trace and record.trace is not None:
        from bench.trace import breakdown

        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace_window_s
        out["breakdown"] = breakdown(record.trace)
    out["check"] = {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log("bench: src/repro not found beside bench/; run it from a checkout of the repository")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    cell = load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: no TPU found (JAX's first device is {devices[0].platform!r}); "
            f"the benchmark has no CPU fallback")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
        return 2
    CACHE.mkdir(exist_ok=True)
    # JAX's persistent compile cache, at a fixed path inside the checkout
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"jax {jax.__version__}; {devices[0].device_kind} x{len(devices)}; cell {cell.name}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for k, v in out["check"].items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
