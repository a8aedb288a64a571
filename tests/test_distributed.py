"""Distributed correctness: the sharded paths (pjit constraints, MoE
expert-parallel shard_map, flash-decode seq-sharding) must reproduce the
mesh-less numerics bit-for-bit (up to fp reduction order).

Runs in a subprocess with 8 forced host devices so the main pytest
process keeps seeing 1 device (smoke tests depend on that).
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_debug_mesh
from repro.models import ModelConfig, RunConfig, build_model
from conftest import tiny_batch

assert len(jax.devices()) == 8
mesh = make_debug_mesh(2, 4)

CONFIGS = [
    ModelConfig(name="dense", family="dense", n_layers=2, d_model=64,
                vocab=128, n_heads=8, n_kv_heads=2, d_ff=128),
    ModelConfig(name="moe", family="moe", n_layers=2, d_model=64,
                vocab=128, n_heads=8, n_kv_heads=8, d_ff=64, n_experts=8,
                n_shared_experts=1, top_k=2, d_expert=64,
                capacity_factor=8.0),   # high capacity: no drops -> exact
    ModelConfig(name="ssm", family="ssm", n_layers=2, d_model=64,
                vocab=128, ssm_state=16, ssm_head_dim=16, ssm_chunk=8),
]

for cfg in CONFIGS:
    m0 = build_model(cfg, RunConfig(compute_dtype=jnp.float32))
    m1 = build_model(cfg, RunConfig(compute_dtype=jnp.float32, mesh=mesh))
    params = m0.init(jax.random.PRNGKey(0))
    batch = tiny_batch(cfg, B=4, S=16)

    l0, _ = jax.jit(m0.loss_fn)(params, batch)
    l1, _ = jax.jit(m1.loss_fn)(params, batch)
    err = abs(float(l0) - float(l1))
    assert err < 5e-4, (cfg.name, float(l0), float(l1))
    print(f"loss {cfg.name}: unsharded={float(l0):.6f} sharded={float(l1):.6f}")

    # gradient agreement
    g0 = jax.jit(jax.grad(lambda p: m0.loss_fn(p, batch)[0]))(params)
    g1 = jax.jit(jax.grad(lambda p: m1.loss_fn(p, batch)[0]))(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-4)
    print(f"grads {cfg.name}: ok")

# flash-decode seq-sharding vs local decode
cfg = CONFIGS[0]
S = 16
m0 = build_model(cfg, RunConfig(compute_dtype=jnp.float32, max_seq=S + 4,
                                decode_seq_shard=False))
m1 = build_model(cfg, RunConfig(compute_dtype=jnp.float32, max_seq=S + 4,
                                mesh=mesh, decode_seq_shard=True))
params = m0.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, S + 1), 0, cfg.vocab,
                          jnp.int32)
_, c0 = m0.prefill(params, {"tokens": toks[:, :S]})
_, c1 = m1.prefill(params, {"tokens": toks[:, :S]})
lg0, _ = m0.decode_step(params, {"tokens": toks[:, S:]}, c0,
                        jnp.asarray(S, jnp.int32))
lg1, _ = m1.decode_step(params, {"tokens": toks[:, S:]}, c1,
                        jnp.asarray(S, jnp.int32))
np.testing.assert_allclose(np.asarray(lg0), np.asarray(lg1), rtol=2e-4,
                           atol=2e-4)
print("flash-decode: ok")
print("ALL_DISTRIBUTED_OK")
"""


@pytest.mark.slow
def test_sharded_equals_unsharded():
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        os.path.join(root, "tests") + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert "ALL_DISTRIBUTED_OK" in r.stdout, (r.stdout[-2000:],
                                              r.stderr[-4000:])
