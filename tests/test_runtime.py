"""Checkpointing, fault tolerance, elastic topology, data determinism."""

import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_checkpoint, save_checkpoint
from repro.data.pipeline import LMTokenPipeline, RecsysBatchPipeline
from repro.data.sampler import NeighborSampler, random_graph
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.runtime import ElasticTopology, RestartPolicy, StragglerMonitor, run_with_restarts


def test_checkpoint_roundtrip(tmp_path):
    payload = {"a": np.arange(6).reshape(2, 3), "b": [np.float32(1.5), np.ones(4)]}
    save_checkpoint(tmp_path, 3, payload)
    restored, step = restore_checkpoint(tmp_path, payload)
    assert step == 3
    np.testing.assert_array_equal(restored["a"], payload["a"])
    np.testing.assert_array_equal(restored["b"][1], payload["b"][1])


def test_checkpoint_retention_and_latest(tmp_path):
    for s in [1, 2, 3, 4, 5]:
        save_checkpoint(tmp_path, s, {"x": np.array([s])}, keep=2)
    restored, step = restore_checkpoint(tmp_path, {"x": np.array([0])})
    assert step == 5
    steps = sorted(p.name for p in tmp_path.glob("step_*"))
    assert len(steps) == 2  # retention


def test_checkpoint_atomicity_tmp_never_restored(tmp_path):
    save_checkpoint(tmp_path, 1, {"x": np.array([1])})
    # a crashed write leaves a .tmp dir that must be ignored
    (tmp_path / "step_9.tmp").mkdir()
    restored, step = restore_checkpoint(tmp_path, {"x": np.array([0])})
    assert step == 1


def test_async_manager(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save_async(1, {"w": np.ones(8)})
    mgr.wait()
    out = mgr.restore_latest({"w": np.zeros(8)})
    assert out is not None and out[1] == 1


def test_run_with_restarts_recovers_and_is_deterministic(tmp_path):
    """Inject a crash at step 7; the run must resume from the checkpoint and
    produce the same final state as an uninterrupted run."""

    def make_state():
        return {"acc": np.zeros(4), "pipe": np.int64(0)}

    def make_step(crash_once):
        crashed = {"done": False}

        def step(state, i):
            if crash_once and i == 7 and not crashed["done"]:
                crashed["done"] = True
                raise RuntimeError("injected pod failure")
            rng = np.random.default_rng(int(state["pipe"]))
            return {
                "acc": state["acc"] + rng.normal(size=4),
                "pipe": state["pipe"] + 1,
            }

        return step

    mgr = CheckpointManager(tmp_path / "a")
    final, stats = run_with_restarts(
        make_state, make_step(True), n_steps=12, manager=mgr,
        policy=RestartPolicy(min_backoff_s=0.0), checkpoint_every=5,
    )
    assert stats["restarts"] == 1 and stats["recovered_from"] == [5]

    mgr2 = CheckpointManager(tmp_path / "b")
    clean, _ = run_with_restarts(
        make_state, make_step(False), n_steps=12, manager=mgr2,
        checkpoint_every=5,
    )
    np.testing.assert_allclose(final["acc"], clean["acc"])  # bit-identical


def test_straggler_monitor():
    mon = StragglerMonitor(n_workers=8, window=10, mad_threshold=4.0)
    rng = np.random.default_rng(0)
    for _ in range(10):
        for w in range(8):
            t = 1.0 + rng.normal(0, 0.02)
            if w == 5:
                t *= 3.0  # persistent straggler
            mon.record(w, t)
    assert mon.stragglers() == [5]


def test_elastic_topology_plan():
    topo = ElasticTopology(chips_per_pod=256, model_parallel=16, global_batch=256)
    p2 = topo.plan(2)
    assert p2["mesh_shape"] == (2, 16, 16) and p2["chips"] == 512
    p1 = topo.plan(1)
    assert p1["mesh_shape"] == (16, 16)
    with pytest.raises(RuntimeError):
        topo.plan(0)


def test_pipeline_determinism():
    a = LMTokenPipeline(vocab=100, seq_len=16, batch=4, seed=9)
    b = LMTokenPipeline(vocab=100, seq_len=16, batch=4, seed=9)
    for _ in range(3):
        x, y = a.next_batch(), b.next_batch()
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    # resuming from a cursor replays identically
    c = LMTokenPipeline(vocab=100, seq_len=16, batch=4, seed=9)
    c.state.step = a.state.step
    np.testing.assert_array_equal(a.next_batch()["tokens"], c.next_batch()["tokens"])


def test_recsys_pipeline_fields():
    p = RecsysBatchPipeline(field_vocab=(50, 20, 10), batch=8, n_dense=3)
    b = p.next_batch()
    assert b["sparse_ids"].shape == (8, 3)
    assert (b["sparse_ids"] < np.array([50, 20, 10])).all()
    assert b["dense"].shape == (8, 3)


def test_neighbor_sampler_static_shapes_and_validity():
    g = random_graph(500, avg_degree=6, d_feat=8, n_classes=5, seed=1)
    s = NeighborSampler(g, batch_nodes=16, fanout=(4, 3), seed=2)
    out1 = s.sample()
    out2 = s.sample()
    assert out1["x"].shape == out2["x"].shape == (s.max_nodes, 8)
    assert out1["src"].shape == (s.max_edges,)
    n_real = int(out1["n_real_nodes"])
    e_real = int(out1["n_real_edges"])
    assert 16 <= n_real <= s.max_nodes
    assert (out1["src"][:e_real] < n_real).all()
    assert (out1["dst"][:e_real] < n_real).all()
    assert out1["label_mask"].sum() == 16  # loss only on seeds


def test_adamw_converges_on_quadratic():
    import jax
    import jax.numpy as jnp

    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1, total_steps=200)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, state = adamw_update(g, state, cfg)
    assert float(loss(params)) < 1e-3


def test_grad_compression_error_feedback_subprocess():
    """int8 compressed psum with error feedback: mean of shard gradients is
    recovered to within quantization noise, and residuals carry over."""
    import subprocess, sys, os

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.optim.grad_compression import compressed_psum
mesh = jax.make_mesh((4,), ("pod",))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)  # per-shard grads
err = jnp.zeros((4, 64), jnp.float32)
def f(g, e):
    return compressed_psum(g, e, "pod")
out, new_err = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                  out_specs=(P("pod"), P("pod")), check_vma=False))(g, err)
mean = np.asarray(g).mean(axis=0)
got = np.asarray(out)[0]
rel = np.abs(got - mean).max() / (np.abs(mean).max() + 1e-9)
# one-shot int8+mean-scale reconstruction is coarse; error feedback carries
# the residual into the next step (the convergence guarantee), so a single
# round only needs to be in the right ballpark
assert rel < 0.3, rel
assert np.abs(np.asarray(new_err)).max() > 0  # residual captured
print("COMPRESS_OK", rel)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "COMPRESS_OK" in r.stdout, r.stdout + r.stderr


import os  # noqa: E402  (used by the subprocess env above)
