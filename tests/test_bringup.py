"""CPU checks of what lets the serving path run on a TPU: the kernels'
backend check, the compile-cache helper, numpy-only ingest workers, and
``chip_smoke.py`` (refusal without a TPU, and its phases at a tiny size)."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

ROOT = Path(__file__).resolve().parent.parent


def test_resolve_interpret_follows_backend_and_device_intersect_uses_it(monkeypatch):
    """On the CPU backend every kernel resolves to the Pallas interpreter,
    and the Step-1 pre-filter's ``_device_intersect`` takes that default
    instead of fixing a mode of its own."""
    from repro.kernels import intersect
    from repro.kernels.backend import resolve_interpret
    from repro.search import fused

    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True
    assert resolve_interpret(False) is False

    modes = []
    real_pallas_call = intersect.pl.pallas_call

    def spy(*args, **kwargs):
        modes.append(kwargs.get("interpret"))
        return real_pallas_call(*args, **kwargs)

    monkeypatch.setattr(intersect.pl, "pallas_call", spy)
    # the unjitted body, so the spy sees this call's pallas_call arguments
    monkeypatch.setattr(fused, "intersect_sorted", intersect.intersect_sorted.__wrapped__)
    rng = np.random.default_rng(3)
    a = np.unique(rng.integers(0, 20_000, 5000)).astype(np.int32)
    b = np.unique(rng.integers(0, 20_000, 6000)).astype(np.int32)
    hit = fused._device_intersect(a, b)
    assert modes == [True]
    np.testing.assert_array_equal(hit, np.isin(a, b))


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    from repro.runtime.compile_cache import CACHE_DIR, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_env_dir_receives_entries(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the helper sets nothing, and
    compiled programs land in that directory."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.runtime.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: jnp.cumsum(x) * 3)(jnp.arange(8)).block_until_ready()\n"
    )
    env = {
        **os.environ,
        "PYTHONPATH": str(ROOT / "src"),
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    }
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path)]
    assert any(tmp_path.iterdir()), "no compiled entry was written"


def test_ingest_workers_import_no_jax():
    """Bulk-ingest spawn workers import ``repro.index.ingest``: that import
    must not pull in jax, so a worker never starts a backend next to the
    process that holds the chip."""
    code = "import sys, repro.index.ingest; print('jax' in sys.modules)"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=120, env={**os.environ, "PYTHONPATH": str(ROOT / "src")})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "False"


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_without_tpu_or_checkout(where, tmp_path):
    """No TPU (``JAX_PLATFORMS=cpu``) or no repository next to the script:
    non-zero exit, a plain message, and no result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(script)], env=env, cwd=script.parent,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    expected = "no TPU found" if where == "checkout" else "src/repro not found"
    assert expected in r.stderr


def test_chip_smoke_phases_on_cpu(monkeypatch):
    """Every phase of the smoke run at a tiny size on the CPU: served
    responses equal se24_combiner, and the kernel phases equal the default
    phase (the script's own checks raise otherwise)."""
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke

    report = chip_smoke.run(120, seed=5, workers=2, n_mix=4, min_arena_bytes=0)
    assert report["fragments"] > 0
    for phase in ("default", "kernel-arena", "kernel-hostpack"):
        assert report[phase]["batches"] >= 1
    assert report["default"]["arena_key_misses"] == 0
    assert 0 < report["arena_used_bytes"] <= report["arena_bytes"]
