"""A tiny benchmark tree for the harness's CPU tests: the real cell's
configuration and traffic, cut to a few hundred documents, in a directory
of its own, with the repository's metric readers and a peaks table for the
CPU backend."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
PASSAGE_CELL = "msmarco-passage-100k.stopmix-burst16-poisson-0.8knee"


def make_tree(where: Path, *, n_docs: int = 240, corpus_seed: int = 0, burst: int = 4) -> Path:
    """Write ``BENCHMARK.json`` and ``bench/`` under ``where``; returns the
    path of the ``BENCHMARK.json``."""
    import jax

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (where / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (where / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(n_docs=n_docs, vocab_size=1500, arena_budget_mb=128, corpus_seed=corpus_seed, ingest_workers=1)
        (where / c["file"]).write_text(json.dumps(cfg))
    for t in (BENCH / "traffic").glob("*.json"):
        traffic = json.loads(t.read_text())
        traffic.update(rate=2.0, burst=burst)
        (where / "bench" / "traffic" / t.name).write_text(json.dumps(traffic))
    shutil.copytree(BENCH / "metrics", where / "bench" / "metrics", dirs_exist_ok=True)
    (where / "peaks.json").write_text(
        json.dumps({"devices": {jax.devices()[0].device_kind: {"hbm_bytes_per_s": 1e10}}})
    )
    (where / "BENCHMARK.json").write_text(json.dumps(spec))
    return where / "BENCHMARK.json"
