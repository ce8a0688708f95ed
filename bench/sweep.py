#!/usr/bin/env python3
"""Find a cell's knee, and read both ends of its output check, on the chip.

    python3 bench/sweep.py --workload <cell> --bursts 24 --seed 1 \\
        --factors 0.6 0.8 1.0 1.2 --control 2

Sets the cell up once (as ``bench/run.py`` does) with a catalog of
``--bursts`` bursts, serves each burst once more alone to time it, then
serves one window per factor at ``factor / mean service time`` bursts per
second, each on a fresh frontend and with every burst of the catalog
once, and reports per rate the requests still unanswered at the close, the
latency percentiles and the mean service time of a batch in the window.
The knee is the highest rate at which the backlog does not grow over the
window; a traffic file's ``rate`` is set from it once.

For every window it also counts the sampled responses that differ from the
reference (the program's reading, whose largest over seeds is the lower end
of the check's limit) and, for the first ``--control`` windows, the same
responses of the control: the reference with MaxDistance one less, put in
the program's place, the cheapest cut of every key's and window's work.
The smallest control reading is the upper end of the limit.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def narrowed(corpus, cfg: dict):
    """The control: the reference with MaxDistance one less."""
    from bench.reference import Reference

    return Reference(corpus, sw_count=cfg["sw_count"], fu_count=cfg["fu_count"],
                     max_distance=cfg["max_distance"] - 1)


def readings(stack, answered: list, seed: int, control) -> dict:
    """The program's and (where ``control`` is given) the control's
    mismatches over the run's check sample."""
    from bench import run

    top_k = stack.cell.config["top_k"]
    ref = run._reference(stack.corpus, stack.cell.config)
    sample = run.check_sample(answered, seed)
    want = {q: ref.answer(q, top_k) for q, _ in sample}
    out = {"sampled": len(sample),
           "program_mismatched": sum(1 for q, resp in sample if run.as_ranking(resp) != want[q])}
    if control is not None:
        out["control_mismatched"] = sum(1 for q, _ in sample if control.answer(q, top_k) != want[q])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--bursts", type=int, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--factors", type=float, nargs="+", default=[0.6, 0.8, 1.0, 1.2])
    ap.add_argument("--control", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import numpy as np

    from bench import readings as rd
    from bench import run
    from repro.search.frontend import ServingFrontend

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 2
    run.CACHE.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = run.load_cell(args.workload)
    cfg = cell.config
    # a catalog of --bursts bursts: set_up sizes it from rate * seconds
    stack = run.set_up(dataclasses.replace(cell, traffic=dict(cell.traffic, rate=1.0)), float(args.bursts))
    run.log("sweep: set-up " + json.dumps(stack.setup_items))
    alone = ServingFrontend(stack.service, max_batch=cfg["max_batch"], arena=stack.arena)
    service = []
    for burst in stack.catalog:
        t = time.perf_counter()
        alone.search_many(burst)
        service.append(time.perf_counter() - t)
    alone.close()
    mean_s = float(np.mean(service))
    run.log(f"sweep: burst service alone {['%.3f' % s for s in service]} s, mean {mean_s:.3f} s")
    control = narrowed(stack.corpus, cfg)
    rows = []
    for k, f in enumerate(args.factors):
        rate = f / mean_s
        seconds = len(stack.catalog) / rate
        traffic = dict(cell.traffic, rate=rate)
        seed = args.seed + k
        record, answered = run.run_window(stack, seed, seconds, False, 0.0, traffic)
        stack.frontend.close()
        late = sum(1 for r in record.requests if r.completed is None or r.completed > record.window_s)
        row = {
            "factor": f, "rate_bursts_per_s": rate, "seconds": seconds, "seed": seed,
            "requests": len(record.requests), "unanswered_at_close": late,
            "unanswered": sum(1 for r in record.requests if r.completed is None),
            "qps": rd.qps(record), "p50_ms": rd.latency_percentile(record, 50),
            "p90_ms": rd.latency_percentile(record, 90), "service_ms": rd.service_ms(record),
            "queue_wait_ms": rd.queue_wait_ms(record), "compiles": record.compiles_in_window,
        }
        row.update(readings(stack, answered, seed, control if k < args.control else None))
        run.log("sweep: " + json.dumps(row))
        rows.append(row)
    print(json.dumps({"workload": cell.name, "burst_service_s": service, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
