"""The harness end to end on the CPU, at a tiny size: discovery by name,
the warm start from the checkout cache, latency from the due time, the
traced run's readers, and the check failing when the timed path is broken.

Every run here skips only the harness's look for a chip (``main``); the
rest of a run is the one the chip sees.
"""

from __future__ import annotations

import json
import subprocess
import sys

import pytest

from bench import run
from bench.tests.tiny import PASSAGE_CELL, ROOT, make_tree

SECONDS = 2.0


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    where = tmp_path_factory.mktemp("tree")
    return make_tree(where)


@pytest.fixture(scope="module")
def stack(tree):
    cell = run.load_cell(PASSAGE_CELL, tree, tree.parent / "bench")
    return run.set_up(cell, SECONDS, tree.parent / "cache")


def _cell(tree, name=PASSAGE_CELL):
    return run.load_cell(name, tree, tree.parent / "bench")


def test_cell_finds_its_files_by_name(tree):
    cell = _cell(tree)
    assert cell.config["name"] == "msmarco-passage-100k"
    assert cell.traffic["burst"] == 4 and cell.traffic["rate"] == 2.0  # the tiny tree's own file
    assert {m["name"] for m in cell.end_to_end} == {"p50_ms", "p90_ms", "setup_s"}
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert "daemon.service_ms.lat" in cell.readers


def test_new_configuration_traffic_and_reader_need_no_code(tmp_path):
    """A cell added only as files and entries is found and read."""
    spec_file = make_tree(tmp_path)
    spec = json.loads(spec_file.read_text())
    cfg = json.loads((tmp_path / "bench/configs/msmarco-passage-100k.json").read_text())
    (tmp_path / "bench/configs/other-corpus.json").write_text(json.dumps(dict(cfg, name="other-corpus")))
    (tmp_path / "bench/traffic/other-mix.json").write_text(json.dumps({"burst": 2, "rate": 1.0}))
    (tmp_path / "bench/metrics/custom.answer.py").write_text("def read(run):\n    return 42.0\n")
    spec["configs"].append(dict(spec["configs"][0], name="other-corpus", file="bench/configs/other-corpus.json"))
    spec["workloads"].append({"name": "other-corpus.other-mix", "config": "other-corpus",
                              "traffic": "other-mix", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "custom.answer", "unit": "%", "better": "higher", "source": "host_clock",
                              "layer": "client", "moves": "qps", "workloads": ["other-corpus.other-mix"]})
    spec_file.write_text(json.dumps(spec))
    cell = run.load_cell("other-corpus.other-mix", spec_file, tmp_path / "bench")
    assert cell.config["name"] == "other-corpus" and cell.traffic == {"burst": 2, "rate": 1.0}
    assert cell.readers["custom.answer"](None) == 42.0


def test_unknown_cell_is_refused(tree):
    with pytest.raises(SystemExit):
        _cell(tree, "no-such-cell")


def test_entry_point_exits_before_work_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", PASSAGE_CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def _serve(stack, burst):
    from repro.search.frontend import ServingFrontend

    front = ServingFrontend(stack.service, max_batch=16, arena=stack.arena)
    try:
        return [run.as_ranking(r) for r in front.search_many(burst)]
    finally:
        front.close()


def test_warm_start_serves_what_a_fresh_build_served(tree, stack):
    cell = _cell(tree)
    again = run.set_up(cell, SECONDS, tree.parent / "cache")
    assert "ingest_s" in stack.setup_items or "corpus_load_s" in stack.setup_items
    assert "ingest_s" not in again.setup_items, "the second set-up must restore, not build"
    assert "parallel_compile" not in again.setup_items, "the second set-up finds its programs compiled"
    assert again.catalog == stack.catalog
    for burst in stack.catalog:
        assert _serve(stack, burst) == _serve(again, burst)
    again.warm.close()
    again.arena.release()


def test_set_up_loads_every_program_the_window_forms(stack):
    """After the warm pass no burst of the catalog compiles anything."""
    from repro.search import fused

    c0 = fused.compile_count()
    for burst in stack.catalog:
        _serve(stack, burst)
    assert fused.compile_count() == c0


def test_latency_runs_from_the_due_time(stack):
    from bench.traffic import schedule

    seconds = 0.5  # every burst of the catalog inside half a second: requests queue
    traffic = dict(stack.cell.traffic, rate=len(stack.catalog) / seconds)
    record, answered = run.run_window(stack, 5, seconds, False, 0.0, traffic)
    stack.frontend.close()
    due = [a.due for a in schedule(stack.catalog, traffic, seconds, 5)]
    assert len(record.requests) == len(due) * traffic["burst"]
    for r in record.requests:
        assert r.completed is not None and r.launched is not None
        assert r.completed >= r.launched >= r.due - 1e-6
        assert r.latency == pytest.approx(r.completed - r.due)
    # queued bursts wait: the last burst's latency exceeds its own service
    last = max(record.batches, key=lambda b: b["launched"])
    worst = max(r.latency for r in record.requests)
    assert worst >= last["completed"] - last["launched"]
    assert len(answered) == len(record.requests)


def test_every_batch_is_one_whole_burst(stack):
    """The daemon runs on its own thread and a burst joins its queue whole,
    so each batch is one burst of the catalog, whatever the timing."""
    for seed in (1, 2):
        record, answered = run.run_window(stack, seed, 0.3, False, 0.0,
                                          dict(stack.cell.traffic, rate=len(stack.catalog) / 0.3))
        stack.frontend.close()
        assert sorted(b["burst"] for b in record.batches) == list(range(len(stack.catalog)))
        assert {b["size"] for b in record.batches} == {stack.cell.traffic["burst"]}
        assert record.compiles_in_window == 0


def test_window_frontend_starts_with_empty_caches(stack):
    record, answered = run.run_window(stack, 3, SECONDS, False, 0.0)
    stats = [resp.stats for _, resp in answered]
    stack.frontend.close()
    assert answered and all(s.cache_misses == 1 for s in stats), "no answer may come from the result cache"


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_check_sample_is_drawn_from_the_seed(seed):
    answered = [(f"q{i}", i) for i in range(run.CHECK_SAMPLE * 3)]
    a, b = run.check_sample(answered, seed), run.check_sample(answered, seed)
    assert a == b and len(a) == run.CHECK_SAMPLE and len(set(a)) == len(a)
    assert run.check_sample(answered, seed + 1) != a
    assert run.check_sample(answered[:5], seed) == answered[:5]


def test_end_to_end_run_is_correct_and_reports_its_metrics(tree):
    out = run.run_cell(_cell(tree), 2**40 + 11, SECONDS, False, tree.parent / "cache", tree.parent / "peaks.json")
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"p50_ms", "p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "check"
    assert out["check"] == {"mismatched_responses": {"value": 0, "limit": 0},
                            "unanswered_requests": {"value": 0, "limit": 0}}


def test_traced_run_reports_per_layer_metrics(tree):
    out = run.run_cell(_cell(tree), 77, SECONDS, True, tree.parent / "cache", tree.parent / "peaks.json")
    assert out["correct"] is True
    names = set(out["metrics"])
    assert {"daemon.queue_wait_ms.lat", "daemon.service_ms.lat", "jit.compiles_in_window.lat",
            "device.idle_pct.lat", "device.program_ms.lat", "serving_program_roofline.lat"} <= names
    assert out["metrics"]["jit.compiles_in_window.lat"]["value"] == 0
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"]


def _alter_first_answer(monkeypatch):
    """A fault where answers are produced: the ranking drops each
    response's last document."""
    import repro.search.planner as planner

    real = planner.rank_documents
    monkeypatch.setattr(planner, "rank_documents", lambda frags, top_k=10: real(frags, top_k)[:-1])


def _drop_half_the_batch(monkeypatch):
    """A fault in the batch: the second half of every batch comes back
    empty, as if it had been left out of the device program."""
    from repro.search.frontend import ServingFrontend

    real = ServingFrontend.submit_many

    def submit_many(self, requests):
        finalize = real(self, requests)

        def half():
            out = finalize()
            for r in out[len(out) // 2 :]:
                r.docs = []
            return out

        return half

    monkeypatch.setattr(ServingFrontend, "submit_many", submit_many)


@pytest.mark.parametrize("fault", [_alter_first_answer, _drop_half_the_batch], ids=["answer-altered", "half-batch"])
def test_check_fails_when_the_timed_path_is_broken(tmp_path, monkeypatch, fault):
    spec = make_tree(tmp_path, n_docs=160)
    fault(monkeypatch)
    cell = run.load_cell(PASSAGE_CELL, spec, tmp_path / "bench")
    out = run.run_cell(cell, 9, SECONDS, False, tmp_path / "cache", tmp_path / "peaks.json")
    assert out["correct"] is False
    assert out["check"]["mismatched_responses"]["value"] > 0
