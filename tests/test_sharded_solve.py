"""Mesh-sharded batched solving: ``odeint(..., mesh=...)`` parity tier.

The multi-device tests need 8 devices, which jax locks at first init —
so this file runs twice:

* under plain tier-1 (1 CPU device) every multi-device test skips and
  ``test_suite_under_forced_devices`` re-runs *this same file* in a
  subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
  (where the wrapper itself skips — no recursion);
* under the CI ``multidevice`` job (flag already exported) the tests
  run directly, with per-test granularity.

Parity contract proven here, per gradient method × {pytree, pallas}:
the sharded solve IS the unsharded ``batch_axis=0`` solve — outputs
and per-element stats bit-equal, z0-cotangents bit-equal — and the
pytree path also matches ``jax.vmap``-of-solo bit-for-bit.  Only the
shared-``args`` gradient may move: ``shard_map``'s transpose psums the
per-shard partial sums in a different association order (≤1e-6 rel for
the RK methods; MALI's longer per-step accumulation chain amplifies
the reorder to a few 1e-6).

Also here: solve-health status isolation per shard (a poisoned element
fails alone), mesh validation errors, per-element ``h0`` placement, a
2-D (data, model) mesh, ``NodeConfig.mesh`` threading, and the elastic
mesh-shape derivation (pure at any device count; constructed meshes at
{1, 8, 16, 32} forced host devices in a subprocess).
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SolveStatus, odeint
from repro.core.node_block import NodeConfig, node_block_apply
from repro.distributed import (batch_partition_axes, batch_shard_count,
                               shard_mesh)
from repro.launch.mesh import elastic_mesh_shape

from faults import faulty_field

MULTI = jax.device_count() >= 8
multi = pytest.mark.skipif(
    not MULTI, reason="needs 8 forced host devices (subprocess wrapper "
    "covers this under tier-1)")

B, D = 8, 4
TS = jnp.array([0.0, 0.5, 1.0])
METHODS = ["aca", "adjoint", "naive", "mali"]
# shared-args cotangent tolerance: the psum reorders the per-shard
# partial sums; mali accumulates over ~10x more (lattice) steps
ARGS_RTOL = {"aca": 1e-6, "adjoint": 1e-6, "naive": 1e-6, "mali": 5e-6}


def _f(t, z, w):
    """Per-sample field with state-embedded stiffness: z[-1] holds the
    element's log-rate (derivative 0), so one batch spans easy → stiff
    and every element earns its own adaptive grid."""
    x, logk = z[:-1], z[-1]
    dx = -jnp.exp(logk) * x + 0.1 * jnp.tanh(w * x)
    return jnp.concatenate([dx, jnp.zeros((1,), z.dtype)])


def _hetero_batch(b=B, d=D, top=3.5):
    x0 = jax.random.normal(jax.random.PRNGKey(0), (b, d - 1)) * 0.5
    logk = jnp.linspace(0.0, top, b)
    return jnp.concatenate([x0, logk[:, None]], axis=1).astype(jnp.float32)


def _kw(method):
    kw = dict(rtol=1e-5, atol=1e-5, grad_method=method, batch_axis=0)
    kw.update(dict(max_steps=2048) if method == "mali"
              else dict(solver="dopri5", max_steps=64))
    return kw


def _batch_for(method):
    # the 2nd-order ALF pair needs ~e^logk steps at this tolerance: a
    # 3.5 top overflows max_steps=2048, so mali gets a milder ladder
    # (still stiffness-heterogeneous: ~25x trial spread)
    return _hetero_batch(top=1.5 if method == "mali" else 3.5)


@pytest.fixture
def _interpret_kernels():
    from repro.kernels import ops
    ops.set_interpret(True)
    yield
    ops.set_interpret(None)


def _counted(st):
    """Every ``SolveStats`` field but ``n_rides``: a row's slots in the
    processed block follow its shard's trip count, not the batch's."""
    return [v for k, v in st._asdict().items() if k != "n_rides"]


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------- parity

@multi
@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("pallas", [False, True], ids=["pytree", "pallas"])
def test_sharded_matches_unsharded(method, pallas, _interpret_kernels):
    """ys/stats bit-equal, z0-grad bit-equal, args-grad ≤tol."""
    mesh = shard_mesh()
    z0, w = _batch_for(method), jnp.float32(0.7)
    kw = _kw(method)
    kw["use_pallas"] = pallas

    ref = jax.jit(lambda z, w: odeint(_f, z, TS, (w,), **kw))
    shd = jax.jit(lambda z, w: odeint(_f, z, TS, (w,), **kw, mesh=mesh))
    ys0, st0 = ref(z0, w)
    ys1, st1 = shd(z0, w)
    _assert_tree_equal(ys0, ys1)
    _assert_tree_equal(_counted(st0), _counted(st1))
    assert bool((np.asarray(st1.status) == SolveStatus.OK).all())

    def loss(z, w, mesh=None):
        ys, _ = odeint(_f, z, TS, (w,), **kw, mesh=mesh)
        return jnp.sum(ys * ys)

    g0 = jax.jit(lambda z, w: jax.grad(loss, argnums=(0, 1))(z, w))(z0, w)
    g1 = jax.jit(
        lambda z, w: jax.grad(loss, argnums=(0, 1))(z, w, mesh))(z0, w)
    _assert_tree_equal(g0[0], g1[0])           # z0-grad: shard-local
    np.testing.assert_allclose(np.asarray(g0[1]), np.asarray(g1[1]),
                               rtol=ARGS_RTOL[method])


@multi
@pytest.mark.parametrize("method", METHODS)
def test_sharded_matches_vmap_of_solo(method):
    """The pytree sharded solve == jax.vmap of the solo solver, bitwise
    (the batch_axis=0 engine's contract, preserved under shard_map)."""
    mesh = shard_mesh()
    z0, w = _batch_for(method), jnp.float32(0.7)
    kw = _kw(method)
    solo_kw = dict(kw)
    solo_kw.pop("batch_axis")

    shd = jax.jit(lambda z, w: odeint(_f, z, TS, (w,), **kw, mesh=mesh))
    vm = jax.jit(jax.vmap(
        lambda zi, w: odeint(_f, zi, TS, (w,), **solo_kw)[0],
        in_axes=(0, None), out_axes=1))
    ys1, _ = shd(z0, w)
    np.testing.assert_array_equal(np.asarray(ys1), np.asarray(vm(z0, w)))


@multi
def test_per_element_h0_shards_with_the_batch():
    mesh = shard_mesh()
    z0, w = _hetero_batch(), jnp.float32(0.7)
    h0 = jnp.full((B,), 1e-3, jnp.float32)
    kw = _kw("aca")
    ys0, st0 = jax.jit(
        lambda z: odeint(_f, z, TS, (w,), **kw, h0=h0))(z0)
    ys1, st1 = jax.jit(
        lambda z: odeint(_f, z, TS, (w,), **kw, h0=h0, mesh=mesh))(z0)
    _assert_tree_equal(ys0, ys1)
    _assert_tree_equal(_counted(st0), _counted(st1))


@multi
@pytest.mark.parametrize("method", METHODS)
def test_scalar_args_grad_wrt_z0_only(method):
    """Rank-0 args leaves under mesh with grads taken wrt z0 ONLY.

    jax 0.4.x shard_map dies with a _SpecError when a custom_vjp inside
    the body saves a rank-0 residual and that residual is a *known*
    (non-differentiated) value — grad wrt (z0, args) works, grad wrt z0
    alone does not.  odeint promotes scalar args leaves to (1,) around
    the shard_map (field code still sees true scalars), so both
    argnums shapes must work and match the unsharded path.
    """
    mesh = shard_mesh()
    z0, w = _batch_for(method), jnp.float32(0.7)
    kw = _kw(method)

    def loss(z, w, mesh=None):
        ys, _ = odeint(_f, z, TS, (w,), **kw, mesh=mesh)
        return jnp.sum(ys * ys)

    g0 = jax.jit(lambda z: jax.grad(loss)(z, w))(z0)
    g1 = jax.jit(lambda z: jax.grad(loss)(z, w, mesh))(z0)
    _assert_tree_equal(g0, g1)
    # dict-shaped args with a scalar leaf, eager grad (no jit)
    f2 = lambda t, z, a: _f(t, z, a["w"])
    ge = jax.grad(lambda z: jnp.sum(
        odeint(f2, z, TS, {"w": w}, **kw, mesh=mesh)[0]))(z0)
    gu = jax.grad(lambda z: jnp.sum(
        odeint(f2, z, TS, {"w": w}, **kw)[0]))(z0)
    _assert_tree_equal(gu, ge)


@multi
def test_2d_mesh_shards_data_axis_only():
    """On a (data=4, model=2) mesh the batch splits 4-way over 'data'
    and replicates over 'model' — same answers, 4 shards."""
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    assert batch_partition_axes(mesh) == ("data",)
    assert batch_shard_count(mesh) == 4
    z0, w = _hetero_batch(), jnp.float32(0.7)
    kw = _kw("aca")
    ys0, _ = jax.jit(lambda z: odeint(_f, z, TS, (w,), **kw))(z0)
    ys1, _ = jax.jit(
        lambda z: odeint(_f, z, TS, (w,), **kw, mesh=mesh))(z0)
    _assert_tree_equal(ys0, ys1)


@multi
def test_composes_with_segmented_checkpoints():
    mesh = shard_mesh()
    z0, w = _hetero_batch(), jnp.float32(0.7)
    kw = _kw("aca")
    ys0, _ = jax.jit(lambda z: odeint(
        _f, z, TS, (w,), **kw, checkpoint_segments=4))(z0)
    ys1, _ = jax.jit(lambda z: odeint(
        _f, z, TS, (w,), **kw, checkpoint_segments=4, mesh=mesh))(z0)
    _assert_tree_equal(ys0, ys1)


@multi
def test_composes_with_interpolate_ts():
    """Dense-output eval under sharding: the step grid (stats) and the
    endpoint states are bit-equal; *interior* interpolated reads are
    weighted stage sums whose fusion the sharded module reassociates —
    equal only to a few ulp, well inside the solve tolerance."""
    mesh = shard_mesh()
    z0, w = _hetero_batch(), jnp.float32(0.7)
    kw = _kw("aca")
    ys0, st0 = jax.jit(lambda z: odeint(
        _f, z, TS, (w,), **kw, interpolate_ts=True))(z0)
    ys1, st1 = jax.jit(lambda z: odeint(
        _f, z, TS, (w,), **kw, interpolate_ts=True, mesh=mesh))(z0)
    _assert_tree_equal(_counted(st0), _counted(st1))
    np.testing.assert_array_equal(np.asarray(ys0[0]), np.asarray(ys1[0]))
    np.testing.assert_array_equal(np.asarray(ys0[-1]), np.asarray(ys1[-1]))
    np.testing.assert_allclose(np.asarray(ys0), np.asarray(ys1),
                               rtol=1e-5, atol=1e-6)


@multi
def test_sharded_compaction_matches_unsharded(monkeypatch):
    """With the compaction floor at 2 each shard compacts its own 8 rows
    (8, 4, 2).  The sharded batched ACA solve then equals the sharded
    one-loop solve bit for bit, and the unsharded solve in answers and
    stats bit for bit and in gradients to the psum's reorder (the z0
    gradient too: the CPU backend fuses the shard_map module's backward
    differently, one ulp apart with or without compaction)."""
    from repro.core import integrate

    mesh = shard_mesh()
    perm = jax.random.permutation(jax.random.PRNGKey(3), 8 * B)
    z0, w = _hetero_batch(b=8 * B)[perm], jnp.float32(0.7)
    kw = _kw("aca")

    def run(floor, mesh=None):
        monkeypatch.setattr(integrate, "COMPACT_FLOOR", floor)

        def loss(z, w):
            ys, st = odeint(_f, z, TS, (w,), **kw, mesh=mesh)
            return jnp.sum(ys * ys), (ys, st)

        (_, (ys, st)), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(z0, w)
        return ys, st, g

    ys0, st0, g0 = run(1000)
    ys1, st1, g1 = run(1000, mesh)
    ys2, st2, g2 = run(2, mesh)
    rides = [int(np.asarray(s.n_rides).sum()) for s in (st0, st1, st2)]
    assert rides[2] < rides[1] < rides[0], rides
    _assert_tree_equal((ys1, _counted(st1), g1), (ys2, _counted(st2), g2))
    _assert_tree_equal(ys0, ys2)
    _assert_tree_equal(_counted(st0), _counted(st2))
    for a, b in zip(g0, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=ARGS_RTOL["aca"], atol=1e-7)


# ------------------------------------------------- solve-health isolation

@multi
def test_fault_isolation_per_shard():
    """A NaN-poisoned element fails alone under sharding: only its
    status flips to NONFINITE_STATE (solve-health is per element, per
    shard), outputs stay finite, and the whole faulty solve takes the
    *same trajectory* as the unsharded faulty solve — statuses, trial
    counts and f-evals bit-equal per element; the output values agree
    to a few ulp (the fault wrapper's extra where-ops fuse differently
    inside the shard_map module, reassociating the stage combines —
    the clean-field parity test above stays fully bitwise).  The
    clean-vs-faulty inertness of the guards is PR 6's property, covered
    in test_solve_health_properties."""
    mesh = shard_mesh()
    z0, w = _hetero_batch(), jnp.float32(0.7)
    bad = 5
    tag = float(z0[bad, -1])
    fbad = faulty_field(_f, "nan", t_ge=0.5,
                        predicate=lambda t, z: jnp.abs(z[-1] - tag) < 1e-4)
    kw = _kw("aca")
    ys0, st0 = jax.jit(
        lambda z: odeint(fbad, z, TS, (w,), **kw))(z0)
    ys, stats = jax.jit(
        lambda z: odeint(fbad, z, TS, (w,), **kw, mesh=mesh))(z0)
    status = np.asarray(stats.status)
    assert status[bad] == SolveStatus.NONFINITE_STATE
    for b in range(B):
        if b != bad:
            assert status[b] == SolveStatus.OK
    _assert_tree_equal(_counted(st0), _counted(stats))
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ys0),
                               rtol=1e-6, atol=1e-6)
    assert bool(jnp.isfinite(ys).all())


# ------------------------------------------------------- validation errors

@multi
def test_uneven_batch_raises():
    mesh = shard_mesh()
    z0 = _hetero_batch(b=6)  # 6 % 8 != 0
    with pytest.raises(ValueError, match="does not divide evenly"):
        odeint(_f, z0, TS, (jnp.float32(0.7),), **_kw("aca"), mesh=mesh)


@multi
def test_mesh_requires_batch_axis():
    mesh = shard_mesh()
    kw = _kw("aca")
    kw.pop("batch_axis")
    with pytest.raises(ValueError, match="mesh requires batch_axis"):
        odeint(_f, _hetero_batch()[0], TS, (jnp.float32(0.7),), **kw,
               mesh=mesh)


@multi
def test_mesh_without_data_axis_raises():
    mesh = jax.make_mesh((8,), ("model",))
    with pytest.raises(ValueError, match="no data-parallel axis"):
        odeint(_f, _hetero_batch(), TS, (jnp.float32(0.7),), **_kw("aca"),
               mesh=mesh)


# ------------------------------------------------------ NodeConfig thread

@multi
def test_node_block_mesh_threading():
    mesh = shard_mesh()
    z0 = _hetero_batch()

    def block_fn(params, z, t):
        return _f(t, z, params)

    base = NodeConfig(enabled=True, solver="dopri5", grad_method="aca",
                      rtol=1e-4, atol=1e-4, max_steps=64, batch_axis=0)
    cfg = dataclasses.replace(base, mesh=mesh)
    w = jnp.float32(0.7)
    out0 = jax.jit(lambda z: node_block_apply(block_fn, w, z, base))(z0)
    out1 = jax.jit(lambda z: node_block_apply(block_fn, w, z, cfg))(z0)
    # the block's state and its per-sample SolveStats
    _assert_tree_equal(out0[0], out1[0])
    _assert_tree_equal(_counted(out0[1]), _counted(out1[1]))


# -------------------------------------------------- elastic mesh shapes

def test_elastic_mesh_shape_pure():
    """Shape derivation at the satellite's device counts {1, 8, 16, 32}
    (model_parallel=1) plus the production TP=16 ladder — pure, so it
    runs at any live device count."""
    assert elastic_mesh_shape(1, 1) == (1, 1, 1)
    assert elastic_mesh_shape(8, 1) == (1, 8, 1)
    assert elastic_mesh_shape(16, 1) == (1, 16, 1)
    assert elastic_mesh_shape(32, 1) == (2, 16, 1)
    assert elastic_mesh_shape(16) == (1, 1, 16)
    assert elastic_mesh_shape(256) == (1, 16, 16)
    assert elastic_mesh_shape(512) == (2, 16, 16)
    assert elastic_mesh_shape(1024) == (4, 16, 16)


def test_elastic_mesh_shape_always_consistent():
    """pods·data·model == n_devices for every divisible count —
    including dp not a multiple of 16 (the old derivation violated
    this: dp=33 gave pods=2, data=16, product 32) — with pods the
    largest divisor of dp not exceeding max(dp // 16, 1)."""
    for mp in (1, 2, 16):
        for dp in range(1, 67):
            n = dp * mp
            pods, data, model = elastic_mesh_shape(n, mp)
            assert pods * data * model == n, (n, mp, pods, data, model)
            assert dp % pods == 0 and pods <= max(dp // 16, 1)


def test_elastic_mesh_shape_raises_readably():
    with pytest.raises(ValueError, match="not a multiple"):
        elastic_mesh_shape(8, 16)
    with pytest.raises(ValueError, match="at least one device"):
        elastic_mesh_shape(0)


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
import jax
from repro.launch.mesh import make_elastic_mesh

devs = jax.devices()
assert len(devs) == 32
for n, mp in [(1, 1), (8, 2), (16, 4), (32, 8)]:
    mesh = make_elastic_mesh(devices=devs[:n], model_parallel=mp)
    assert mesh.axis_names == ("pod", "data", "model"), mesh
    assert mesh.devices.size == n, (n, mesh)
    assert mesh.shape["model"] == mp, (mp, mesh)
try:
    make_elastic_mesh(devices=devs[:8], model_parallel=16)
    raise SystemExit("expected ValueError")
except ValueError:
    pass
print("ELASTIC_MESH_OK")
"""


def test_make_elastic_mesh_forced_devices():
    """Constructed meshes at {1, 8, 16, 32} forced host devices (a
    subprocess: the device count is locked at jax init)."""
    r = _run_sub([sys.executable, "-c", _MESH_SCRIPT])
    assert "ELASTIC_MESH_OK" in r.stdout, (r.stdout[-2000:],
                                           r.stderr[-4000:])


# ------------------------------------------------------ tier-1 wrapper

def _run_sub(cmd, extra_env=None, timeout=900):
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        os.path.join(root, "tests") + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.slow
@pytest.mark.skipif(MULTI, reason="already running on >=8 devices")
def test_suite_under_forced_devices():
    """Tier-1 entry point: re-run this file on 8 forced host devices so
    the parity tier executes under the plain pytest invocation too."""
    r = _run_sub(
        [sys.executable, "-m", "pytest", "-q", "-x", os.path.abspath(__file__)],
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                   "REPRO_PALLAS_INTERPRET": "1"})
    assert r.returncode == 0, (r.stdout[-4000:], r.stderr[-4000:])
