"""MALI — reversible asynchronous-leapfrog integrator tests.

Covers the ``grad_method="mali"`` contract end to end:

* ``alf_step_inverse(alf_step(s)) == s`` **bitwise** — the fixed-point
  lattice pair makes every state update an exact wrapping integer add,
  so inversion is a bijection for any input (deterministic pins across
  dtypes/scales + a hypothesis sweep when hypothesis is installed);
* full-trajectory reverse reconstruction is bit-identical to the
  forward trajectory on the solo engine (under jit — eager per-op
  dispatch may fuse the field by an ulp differently);
* gradients match ``grad_method="naive"`` to ≤1e-5 rel on the stiff
  van-der-Pol smoke problem, solo + batched × pytree + pallas;
* api surface: solver="alf" pairing rules, checkpoint_segments /
  interpolate_ts rejection, reverse-time ``ts``, multi-time outputs,
  NodeConfig threading.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NodeConfig, node_block_apply, odeint
from repro.core.controller import ControllerConfig
from repro.core.integrate import mali_adaptive_solve
from repro.core.stepper import (
    alf_lattice_exponent,
    alf_step,
    alf_step_batched,
    alf_step_inverse,
    alf_step_inverse_batched,
    lattice_decode,
    lattice_encode,
)

MU = 2.0


def vdp(t, z, mu):
    """Stiff-ish van der Pol — the MALI smoke problem."""
    x, y = z[..., 0], z[..., 1]
    return jnp.stack([y, mu * (1.0 - x**2) * y - x], axis=-1)


def linear(t, z, k):
    return k * z


Z0_VDP = np.array([2.0, 0.0], np.float32)
TS_VDP = np.array([0.0, 0.5])


def _tree_bits_equal(a, b):
    return all(
        bool(jnp.all(x == y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# bit-exact inversion of the lattice pair step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [
    jnp.float32,
    pytest.param(jnp.float64, marks=pytest.mark.skipif(
        not jax.config.jax_enable_x64, reason="needs JAX_ENABLE_X64")),
])
@pytest.mark.parametrize("scale", [1e-20, 1e-3, 1.0, 37.0, 1e8, 1e30])
def test_alf_roundtrip_bitexact_scales(dtype, scale):
    """inverse(step(s)) == s bitwise, across dtypes and 50 orders of
    magnitude of state scale (lattice wraparound included)."""
    k = jnp.asarray(-0.7, dtype)
    z = (jax.random.normal(jax.random.PRNGKey(0), (17,)) * scale
         ).astype(dtype)
    v = linear(0.0, z, k)
    se = alf_lattice_exponent(z, v)
    zq, vq = lattice_encode(z, se), lattice_encode(v, se)
    t, h = jnp.asarray(0.3, dtype), jnp.asarray(0.05, dtype)
    res = jax.jit(lambda zq, vq: alf_step(
        linear, t, h, zq, vq, se, z, (k,)))(zq, vq)
    back = jax.jit(lambda zq, vq: alf_step_inverse(
        linear, t, h, zq, vq, se, z, (k,)))(res.zq_next, res.vq_next)
    assert _tree_bits_equal(back, (zq, vq))


def test_alf_roundtrip_bitexact_pytree_chain():
    """50 chained steps forward then 50 inversions recover every
    intermediate pair bitwise, on a nested pytree state."""
    def f(t, z, k):
        return {"a": k * z["a"], "b": -0.3 * z["b"] + jnp.mean(z["a"])}

    k = jnp.float32(-0.5)
    z = {"a": jax.random.normal(jax.random.PRNGKey(1), (8,)),
         "b": jax.random.normal(jax.random.PRNGKey(2), (3, 2))}
    v = f(0.0, z, k)
    se = alf_lattice_exponent(z, v)
    step = jax.jit(lambda t, zq, vq: alf_step(f, t, 0.02, zq, vq, se, z,
                                              (k,)))
    inv = jax.jit(lambda t, zq, vq: alf_step_inverse(
        f, t, 0.02, zq, vq, se, z, (k,)))
    states = [(lattice_encode(z, se), lattice_encode(v, se))]
    for i in range(50):
        r = step(jnp.float32(0.02 * i), *states[-1])
        states.append((r.zq_next, r.vq_next))
    cur = states[-1]
    for i in range(49, -1, -1):
        cur = inv(jnp.float32(0.02 * i), *cur)
        assert _tree_bits_equal(cur, states[i]), f"mismatch at step {i}"


def test_alf_roundtrip_bitexact_batched():
    """Per-row inversion is bitwise exact with per-row stepsizes,
    including h = 0 rows (the batched sweep inverts then masks)."""
    k = jnp.float32(-0.9)
    z = jax.random.normal(jax.random.PRNGKey(3), (4, 6))
    v = jax.vmap(lambda zi: linear(0.0, zi, k))(z)
    se = alf_lattice_exponent(z, v)
    zq, vq = lattice_encode(z, se), lattice_encode(v, se)
    t = jnp.array([0.0, 0.1, 0.2, 0.3], jnp.float32)
    h = jnp.array([0.05, 0.0, 0.11, 0.02], jnp.float32)
    res = jax.jit(lambda zq, vq: alf_step_batched(
        linear, t, h, zq, vq, se, z, (k,)))(zq, vq)
    back = jax.jit(lambda zq, vq: alf_step_inverse_batched(
        linear, t, h, zq, vq, se, z, (k,)))(res.zq_next, res.vq_next)
    assert _tree_bits_equal(back, (zq, vq))


def test_alf_step_order():
    """One ALF step is 2nd order: halving h cuts the one-step error ~8x
    (local O(h³)) on dz/dt = kz against the exact flow."""
    k = jnp.float32(-1.3)
    z = jnp.asarray([1.5], jnp.float32)
    v = linear(0.0, z, k)
    se = alf_lattice_exponent(z, v)

    def one_step_err(h):
        r = alf_step(linear, 0.0, jnp.float32(h), lattice_encode(z, se),
                     lattice_encode(v, se), se, z, (k,))
        return abs(float(r.z_next[0]) - 1.5 * np.exp(float(k) * h))

    e1, e2 = one_step_err(0.2), one_step_err(0.1)
    assert e1 / e2 > 5.0, (e1, e2)


# ---------------------------------------------------------------------------
# hypothesis sweep (optional module)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # extra coverage only — deterministic pins above
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        scale=st.floats(1e-6, 1e6),
        h=st.floats(1e-6, 10.0),
        k=st.floats(-5.0, 5.0),
    )
    def test_alf_roundtrip_bitexact_property(seed, scale, h, k):
        """inverse(step(s)) == s bitwise for arbitrary states/steps."""
        kk = jnp.float32(k)
        z = (jax.random.normal(jax.random.PRNGKey(seed), (9,))
             * scale).astype(jnp.float32)
        v = linear(0.0, z, kk)
        se = alf_lattice_exponent(z, v)
        zq, vq = lattice_encode(z, se), lattice_encode(v, se)
        hh = jnp.float32(h)
        res = jax.jit(lambda a, b: alf_step(
            linear, 0.0, hh, a, b, se, z, (kk,)))(zq, vq)
        back = jax.jit(lambda a, b: alf_step_inverse(
            linear, 0.0, hh, a, b, se, z, (kk,)))(res.zq_next,
                                                  res.vq_next)
        assert _tree_bits_equal(back, (zq, vq))


# ---------------------------------------------------------------------------
# full-trajectory reverse reconstruction (solo engine)
# ---------------------------------------------------------------------------


def test_reverse_reconstruction_bit_identical():
    """Inverting from the terminal pair reproduces every accepted
    forward state bitwise — the O(1)-memory contract of the MALI
    backward sweep (acceptance gate)."""
    z0 = jnp.asarray(Z0_VDP)
    mu = jnp.float32(MU)
    ts = jnp.asarray(TS_VDP, jnp.float32)
    _, grid, stats = mali_adaptive_solve(
        vdp, z0, ts, (mu,), 1e-5, 1e-5, ControllerConfig(max_steps=1024))
    assert not bool(stats.overflow)
    n = int(grid.n)
    assert n > 20  # the smoke problem must exercise a real grid

    def fwd_buf(z0, mu, tg, hg):
        v0 = vdp(jnp.float32(0.0), z0, mu)
        zq = lattice_encode(z0, grid.scale_exp)
        vq = lattice_encode(v0, grid.scale_exp)
        zb = jnp.zeros((n + 1,) + zq.shape, zq.dtype).at[0].set(zq)
        vb = jnp.zeros((n + 1,) + vq.shape, vq.dtype).at[0].set(vq)

        def body(i, c):
            zq, vq, zb, vb = c
            r = alf_step(vdp, tg[i], hg[i], zq, vq, grid.scale_exp, z0,
                         (mu,))
            return (r.zq_next, r.vq_next, zb.at[i + 1].set(r.zq_next),
                    vb.at[i + 1].set(r.vq_next))

        _, _, zb, vb = jax.lax.fori_loop(0, n, body, (zq, vq, zb, vb))
        return zb, vb

    def bwd_buf(zT, vT, z0, mu, tg, hg):
        zb = jnp.zeros((n + 1,) + zT.shape, zT.dtype).at[n].set(zT)
        vb = jnp.zeros((n + 1,) + vT.shape, vT.dtype).at[n].set(vT)

        def body(j, c):
            zq, vq, zb, vb = c
            i = n - 1 - j
            pz, pv = alf_step_inverse(vdp, tg[i], hg[i], zq, vq,
                                      grid.scale_exp, z0, (mu,))
            return (pz, pv, zb.at[i].set(pz), vb.at[i].set(pv))

        _, _, zb, vb = jax.lax.fori_loop(0, n, body, (zT, vT, zb, vb))
        return zb, vb

    zb, vb = jax.jit(fwd_buf)(z0, mu, grid.t, grid.h)
    # the while_loop engine and the fori_loop replay agree bitwise
    assert bool(jnp.all(zb[n] == grid.zT)) and bool(jnp.all(vb[n] == grid.vT))
    rzb, rvb = jax.jit(bwd_buf)(grid.zT, grid.vT, z0, mu, grid.t, grid.h)
    assert bool(jnp.all(rzb == zb)) and bool(jnp.all(rvb == vb))


# ---------------------------------------------------------------------------
# forward accuracy + gradient match vs the naive method
# ---------------------------------------------------------------------------


def test_forward_tracks_tolerance():
    ts = jnp.linspace(0.0, 2.0, 5)
    k = jnp.float32(-0.8)
    ys, stats = odeint(linear, jnp.float32(1.5), ts, (k,),
                       grad_method="mali", rtol=1e-5, atol=1e-5,
                       max_steps=2048)
    exact = 1.5 * np.exp(-0.8 * np.asarray(ts))
    assert not bool(stats.overflow)
    assert np.abs(np.asarray(ys) - exact).max() < 1e-4


def test_one_feval_per_trial():
    """ALF costs exactly one field evaluation per ψ trial (+3 setup:
    v0 and the two hinit evals)."""
    ts = jnp.array([0.0, 1.0])
    _, stats = odeint(linear, jnp.float32(1.0), ts, (jnp.float32(-0.5),),
                      grad_method="mali", rtol=1e-4, atol=1e-4,
                      max_steps=1024)
    assert int(stats.nfe) == int(stats.n_trials) + 3


def _vdp_grads(method, *, rtol, max_steps, use_pallas=False,
               batch=False, solver=None):
    z0 = jnp.asarray(Z0_VDP)
    if batch:
        z0 = jnp.stack([z0, jnp.array([1.0, 0.5]), jnp.array([0.3, -0.2])]
                       ).astype(jnp.float32)
    ts = jnp.asarray(TS_VDP, jnp.float32)

    def L(z0, mu):
        ys, _ = odeint(vdp, z0, ts, (mu,), grad_method=method,
                       solver=solver, rtol=rtol, atol=rtol,
                       max_steps=max_steps, use_pallas=use_pallas,
                       batch_axis=0 if batch else None)
        return jnp.sum(ys[-1] ** 2)

    return jax.grad(L, argnums=(0, 1))(z0, jnp.float32(MU))


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("batch", [False, True])
def test_grads_match_naive_vdp(use_pallas, batch):
    """MALI gradients match naive direct-backprop ≤1e-5 rel on the
    stiff vdp smoke problem (acceptance gate), solo + batched ×
    pytree + pallas."""
    g_ref = _vdp_grads("naive", rtol=1e-8, max_steps=512, batch=batch,
                       solver="dopri5")
    g_mali = _vdp_grads("mali", rtol=1e-7, max_steps=8192, batch=batch,
                        use_pallas=use_pallas)
    for gm, gr in zip(g_mali, g_ref):
        denom = float(jnp.max(jnp.abs(gr)))
        assert float(jnp.max(jnp.abs(gm - gr))) <= 1e-5 * denom, (
            use_pallas, batch, gm, gr)


def test_grads_match_naive_pytree():
    """Pytree-state gradients (dict of mixed-shape leaves)."""
    def f(t, z, k):
        return {"a": k * z["a"], "b": -0.4 * z["b"] + jnp.mean(z["a"])}

    z0 = {"a": jnp.array([1.0, -0.5], jnp.float32),
          "b": jnp.array([[0.2], [0.7]], jnp.float32)}
    ts = jnp.array([0.0, 0.8])

    def L(method, rtol, ms, solver):
        def loss(z0, k):
            ys, _ = odeint(f, z0, ts, (k,), grad_method=method,
                           solver=solver, rtol=rtol, atol=rtol,
                           max_steps=ms)
            return sum(jnp.sum(l ** 2)
                       for l in jax.tree.leaves(
                           jax.tree.map(lambda y: y[-1], ys)))
        return jax.grad(loss, argnums=(0, 1))(z0, jnp.float32(-0.6))

    g_ref = L("naive", 1e-8, 512, "dopri5")
    g_mali = L("mali", 1e-7, 8192, None)
    for gm, gr in zip(jax.tree.leaves(g_mali), jax.tree.leaves(g_ref)):
        denom = float(jnp.max(jnp.abs(gr)))
        assert float(jnp.max(jnp.abs(gm - gr))) <= 1e-5 * max(denom, 1e-6)


def test_batched_matches_vmap_of_solo():
    """Per-element adaptive grids: batched outputs/grads track vmap of
    the solo solver (within the shared-lattice quantum)."""
    z0b = jnp.stack([jnp.array([2.0, 0.0]), jnp.array([1.0, 0.5]),
                     jnp.array([0.3, -0.2])]).astype(jnp.float32)
    ts = jnp.asarray(TS_VDP, jnp.float32)
    mu = jnp.float32(MU)

    ysb, stb = odeint(vdp, z0b, ts, (mu,), grad_method="mali",
                      batch_axis=0, rtol=1e-5, atol=1e-5, max_steps=2048)
    # heterogeneous stiffness must produce genuinely per-element grids
    assert len(set(np.asarray(stb.n_steps).tolist())) > 1

    def solo_solve(z):
        return odeint(vdp, z, ts, (mu,), grad_method="mali", rtol=1e-5,
                      atol=1e-5, max_steps=2048)

    ys_solo, st_solo = jax.vmap(solo_solve, out_axes=(1, 0))(z0b)
    # per-element lattices: the batched engine IS vmap of the solo
    # engine — identical grids and bit-equal outputs
    np.testing.assert_array_equal(np.asarray(stb.n_steps),
                                  np.asarray(st_solo.n_steps))
    np.testing.assert_array_equal(np.asarray(ysb), np.asarray(ys_solo))

    gb = jax.grad(lambda z: jnp.sum(odeint(
        vdp, z, ts, (mu,), grad_method="mali", batch_axis=0, rtol=1e-5,
        atol=1e-5, max_steps=2048)[0][-1] ** 2))(z0b)
    gs = jax.vmap(jax.grad(
        lambda z: jnp.sum(solo_solve(z)[0][-1] ** 2)))(z0b)
    assert float(jnp.max(jnp.abs(gb - gs))) < 1e-6


def test_multi_time_outputs_and_grad():
    """Interior eval times land exactly and carry cotangents through
    the inverting sweep."""
    ts = jnp.linspace(0.0, 1.0, 5)
    k = jnp.float32(-1.1)

    def L(z0):
        ys, _ = odeint(linear, z0, ts, (k,), grad_method="mali",
                       rtol=1e-6, atol=1e-6, max_steps=4096)
        return jnp.sum(ys ** 2)  # every eval time contributes

    g = jax.grad(L)(jnp.float32(1.3))
    exact = sum(2 * 1.3 * np.exp(2 * float(k) * t) for t in np.asarray(ts))
    assert abs(float(g) - exact) < 1e-3 * abs(exact)


def test_reverse_time_descending_ts():
    """Descending ts solves in reverse time under mali (front-door clock
    negation), gradients included."""
    k = jnp.float32(-0.8)
    ts = jnp.array([2.0, 0.0])

    def L(z0):
        ys, _ = odeint(linear, z0, ts, (k,), grad_method="mali",
                       rtol=1e-5, atol=1e-5, max_steps=2048)
        return ys[-1]

    val, g = jax.value_and_grad(L)(jnp.float32(1.0))
    assert abs(float(val) - np.exp(1.6)) < 1e-3
    assert abs(float(g) - np.exp(1.6)) < 1e-3 * np.exp(1.6)


# ---------------------------------------------------------------------------
# api surface
# ---------------------------------------------------------------------------


def test_api_solver_pairing():
    ts = jnp.array([0.0, 1.0])
    z0 = jnp.float32(1.0)
    with pytest.raises(ValueError, match="alf"):
        odeint(linear, z0, ts, (jnp.float32(-1.0),), grad_method="mali",
               solver="dopri5")
    with pytest.raises(ValueError, match="mali"):
        odeint(linear, z0, ts, (jnp.float32(-1.0),), grad_method="aca",
               solver="alf")
    # default solver resolves per method: both of these must run
    odeint(linear, z0, ts, (jnp.float32(-1.0),), grad_method="mali",
           rtol=1e-3, atol=1e-3)
    odeint(linear, z0, ts, (jnp.float32(-1.0),), grad_method="aca")


def test_api_rejects_checkpoint_segments():
    with pytest.raises(ValueError, match="checkpoint"):
        odeint(linear, jnp.float32(1.0), jnp.array([0.0, 1.0]),
               (jnp.float32(-1.0),), grad_method="mali",
               checkpoint_segments=4)


def test_api_rejects_interpolate_ts():
    with pytest.raises(ValueError, match="interpolate_ts"):
        odeint(linear, jnp.float32(1.0), jnp.array([0.0, 1.0]),
               (jnp.float32(-1.0),), grad_method="mali",
               interpolate_ts=True)


def test_node_block_mali():
    """NodeConfig(grad_method='mali') threads through the block apply;
    the fixed regime is rejected."""
    def block_fn(p, z, t):
        return jnp.tanh(z @ p)

    p = jax.random.normal(jax.random.PRNGKey(0), (8, 8)) * 0.3
    z0 = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    cfg = NodeConfig(enabled=True, solver="alf", grad_method="mali",
                     rtol=1e-3, atol=1e-3, max_steps=256)
    zT, stats = node_block_apply(block_fn, p, z0, cfg)
    assert zT.shape == z0.shape and bool(jnp.all(jnp.isfinite(zT)))
    assert int(stats.status) == 0 and int(stats.nfe) > 0
    g = jax.grad(lambda p: jnp.sum(
        node_block_apply(block_fn, p, z0, cfg)[0] ** 2))(p)
    assert bool(jnp.all(jnp.isfinite(g)))

    with pytest.raises(ValueError, match="fixed"):
        node_block_apply(block_fn, p, z0,
                         NodeConfig(enabled=True, grad_method="mali",
                                    regime="fixed"))


def test_pallas_backward_dispatches_increment_kernel(monkeypatch):
    """use_pallas=True must route the backward replay's half-drifts
    through the fused ``rk_stage_increment`` kernel (not silently fall
    back to the pytree path)."""
    from repro.kernels import ops
    ops.set_interpret(True)
    try:
        calls = {"increment": 0}
        orig = ops.rk_stage_increment
        monkeypatch.setattr(
            ops, "rk_stage_increment",
            lambda *a, **k: (calls.__setitem__(
                "increment", calls["increment"] + 1) or orig(*a, **k)))
        g = jax.grad(lambda z0: odeint(
            linear, z0, jnp.array([0.0, 1.0]), (jnp.float32(-0.5),),
            grad_method="mali", rtol=1e-3, atol=1e-3, max_steps=256,
            use_pallas=True)[0][-1].sum())(jnp.ones((4,), jnp.float32))
        assert calls["increment"] > 0
        assert bool(jnp.all(jnp.isfinite(g)))
    finally:
        ops.set_interpret(None)


def test_stats_shape_batched():
    z0b = jnp.stack([jnp.array([1.0, 0.0]), jnp.array([0.5, 0.2])]
                    ).astype(jnp.float32)
    _, st = odeint(vdp, z0b, jnp.array([0.0, 0.3]), (jnp.float32(MU),),
                   grad_method="mali", batch_axis=0, rtol=1e-4,
                   atol=1e-4, max_steps=1024)
    assert st.n_steps.shape == (2,)
    assert not bool(jnp.any(st.overflow))
