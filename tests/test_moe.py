"""MoE dispatch equivalence: einsum == sort (same capacity semantics) on
no-overflow loads; the shard_map `local` path is exercised in a forced
8-device subprocess (device count locks at first jax init)."""

import subprocess
import sys

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.moe import MoEConfig, init_moe_params, moe_ffn


def _cfg(dispatch):
    return MoEConfig(n_experts=8, top_k=2, d_ff=32, capacity_factor=8.0,
                     dispatch=dispatch)


def test_einsum_equals_sort_no_overflow():
    cfg_e, cfg_s = _cfg("einsum"), _cfg("sort")
    params = init_moe_params(jax.random.key(0), 16, cfg_e, jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 12, 16)), jnp.float32)
    y_e, aux_e = moe_ffn(x, params, cfg_e)
    y_s, aux_s = moe_ffn(x, params, cfg_s)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_s), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux_e), float(aux_s), rtol=1e-4)


def test_capacity_drops_tokens():
    """With capacity factor << 1 outputs shrink (tokens dropped), not NaN."""
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff=32, capacity_factor=0.1,
                    dispatch="sort")
    params = init_moe_params(jax.random.key(1), 16, cfg, jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 64, 16)), jnp.float32)
    y, _ = moe_ffn(x, params, cfg)
    assert np.isfinite(np.asarray(y)).all()


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.moe import MoEConfig, init_moe_params, moe_ffn
mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg_l = MoEConfig(n_experts=8, top_k=2, d_ff=32, capacity_factor=8.0, dispatch="local")
cfg_s = MoEConfig(n_experts=8, top_k=2, d_ff=32, capacity_factor=8.0, dispatch="sort")
params = init_moe_params(jax.random.key(0), 16, cfg_l, jnp.float32)
x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8, 16)), jnp.float32)
with jax.set_mesh(mesh):
    y_l, _ = jax.jit(lambda x, p: moe_ffn(x, p, cfg_l))(x, params)
y_s, _ = moe_ffn(x, params, cfg_s)
err = float(jnp.max(jnp.abs(y_l - y_s)))
assert err < 2e-4, err
print("LOCAL_OK", err)
"""


def test_local_dispatch_multidevice_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert "LOCAL_OK" in r.stdout, r.stdout + r.stderr
