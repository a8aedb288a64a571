"""Per-sample batched adaptive solving (``odeint(..., batch_axis=)``).

Three properties are on trial:

* **Not lockstep** — on a stiffness-heterogeneous batch every element
  must record its *own* accepted grid (per-element ``n_steps`` differ),
  unlike integrating the stacked state as one system where a single
  accept/reject decision is shared.
* **vmap parity** — outputs and gradients of the batched solve must
  match ``jax.vmap`` of the unbatched solver to ≤1e-5 rel for every
  grad_method × use_pallas combination (the batched engine is the same
  per-element math, fused into one loop).
* **Freezing** — an element that lands on its last eval time is frozen
  by the masking; its outputs and stats must be bit-stable no matter how
  long the stragglers keep the loop alive.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GRAD_METHODS, SolveStatus, odeint

# dz/dt over z = [x (d-1,), logk (1,)]: per-sample stiffness exp(logk)
# rides inside the state, so a shared-args batch can still be
# heterogeneous.  Elementwise ops only (bit-stable under row slicing).


def _f(t, z, w):
    x, logk = z[:-1], z[-1]
    dx = -jnp.exp(logk) * x + 0.1 * jnp.tanh(w * x)
    return jnp.concatenate([dx, jnp.zeros((1,), z.dtype)])


def _hetero_batch(B=4, d=4, seed=1):
    x0 = jax.random.normal(jax.random.PRNGKey(seed), (B, d - 1))
    logk = jnp.linspace(0.0, 3.5, B)
    return jnp.concatenate([x0, logk[:, None]], axis=1).astype(jnp.float32)


TS = jnp.array([0.0, 0.5, 1.0], jnp.float32)
KW = dict(solver="dopri5", rtol=1e-5, atol=1e-5, max_steps=64)
W = jnp.float32(0.7)


def _kw(method):
    """Per-method solve kwargs: mali has no RK tableau and — being 2nd
    order with a 1st-order embedded estimate — needs a larger accepted-
    step budget on the stiff rows of the heterogeneous batch."""
    if method == "mali":
        return dict(solver=None, rtol=1e-5, atol=1e-5, max_steps=2048)
    return KW


def _counted(st):
    """Every ``SolveStats`` field but ``n_rides``, which counts a row's
    slots in the processed block and so depends on the rows around it."""
    return [v for k, v in st._asdict().items() if k != "n_rides"]


@pytest.fixture
def _interpret_kernels():
    from repro.kernels import ops
    ops.set_interpret(True)
    yield
    ops.set_interpret(None)


def test_per_element_grids_not_lockstep():
    """Heterogeneous stiffness ⇒ per-element accepted grids differ; the
    lockstep solve (stacked state, one controller) can't represent that."""
    z0 = _hetero_batch()
    _, stats = odeint(_f, z0, TS, (W,), grad_method="aca", batch_axis=0,
                      **KW)
    n = np.asarray(stats.n_steps)
    assert n.shape == (z0.shape[0],)
    assert len(np.unique(n)) > 1, n  # NOT one shared grid

    # lockstep baseline: same batch integrated as ONE stacked state.
    # A single global error norm means one shared grid: easy elements
    # are dragged onto it (paying more steps than their own grid), and
    # the stiff element's error is diluted by the batch RMS (the
    # degraded stepsize search batch_axis exists to avoid).
    fb = lambda t, zb, w: jax.vmap(lambda z: _f(t, z, w))(zb)
    _, st_lock = odeint(fb, z0, TS, (W,), grad_method="aca", **KW)
    assert np.asarray(st_lock.n_steps).shape == ()  # one shared decision
    assert int(st_lock.n_steps) > int(n.min())  # easy elements overpay


def _batched_case(method, use_pallas, z0, batch_axis=0):
    def loss(w, z0):
        ys, stats = odeint(_f, z0, TS, (w,), grad_method=method,
                           batch_axis=batch_axis, use_pallas=use_pallas,
                           **_kw(method))
        return jnp.sum(ys[-1] ** 2), (ys, stats)

    (_, (ys, stats)), (gw, gz) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(W, z0)
    return ys, stats, gw, gz


def _vmap_case(method, use_pallas, z0):
    def loss(w, z0):
        ys, stats = jax.vmap(
            lambda z: odeint(_f, z, TS, (w,), grad_method=method,
                             use_pallas=use_pallas, **_kw(method)),
            in_axes=0, out_axes=(1, 0))(z0)
        return jnp.sum(ys[-1] ** 2), (ys, stats)

    (_, (ys, stats)), (gw, gz) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(W, z0)
    return ys, stats, gw, gz


@pytest.mark.parametrize("method", GRAD_METHODS)
@pytest.mark.parametrize("use_pallas", [False, True])
def test_matches_vmap_of_solo(method, use_pallas, _interpret_kernels):
    """batch_axis=0 ≡ jax.vmap of the unbatched solver: same per-element
    grids, outputs and gradients to ≤1e-5 rel — for every grad method,
    with and without the fused kernels."""
    z0 = _hetero_batch()
    ys_b, st_b, gw_b, gz_b = _batched_case(method, use_pallas, z0)
    ys_s, st_s, gw_s, gz_s = _vmap_case(method, use_pallas, z0)

    np.testing.assert_array_equal(np.asarray(st_b.n_steps),
                                  np.asarray(st_s.n_steps))
    assert len(np.unique(np.asarray(st_b.n_steps))) > 1  # heterogeneous
    np.testing.assert_allclose(np.asarray(ys_b), np.asarray(ys_s),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(gz_b), np.asarray(gz_s),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(gw_b), np.asarray(gw_s),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("method", GRAD_METHODS)
def test_finished_elements_freeze_bit_stable(method):
    """Adding a stiff straggler to the batch keeps the easy elements'
    outputs AND stats bit-identical: once an element lands on its last
    ts[k] the masking freezes it completely."""
    if method == "mali":
        # ALF is non-dissipative (reversibility forbids damping: a
        # bijective map cannot contract), so very stiff rows pin its
        # stepsize at the atol floor — exercise the freezing contract
        # inside its effective stiffness range instead
        x0 = jax.random.normal(jax.random.PRNGKey(1), (3, 3))
        logk = jnp.array([0.0, 1.2, 1.6])
        z_more = jnp.concatenate([x0, logk[:, None]],
                                 axis=1).astype(jnp.float32)
        z_easy = z_more[:2]
    else:
        z_easy = _hetero_batch(B=2)
        stiff = jnp.concatenate([jnp.ones((1, 3)) * 0.5,
                                 jnp.full((1, 1), 4.2)], axis=1)
        z_more = jnp.concatenate([z_easy, stiff.astype(jnp.float32)],
                                 axis=0)

    ys2, st2 = odeint(_f, z_easy, TS, (W,), grad_method=method,
                      batch_axis=0, **_kw(method))
    ys3, st3 = odeint(_f, z_more, TS, (W,), grad_method=method,
                      batch_axis=0, **_kw(method))
    assert int(np.asarray(st3.n_steps)[2]) > int(
        np.asarray(st3.n_steps)[:2].max())
    np.testing.assert_array_equal(np.asarray(ys2), np.asarray(ys3)[:, :2])
    for a, b in zip(_counted(st2), _counted(st3)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:2])


def test_batch_axis_nonzero():
    """batch_axis=1 is batch_axis=0 on the moved state, moved back; a
    negative batch_axis normalizes to the same thing (regression: the
    output restore used the raw negative axis and scrambled ys)."""
    z0 = _hetero_batch()
    ys0, st0 = odeint(_f, z0, TS, (W,), grad_method="aca", batch_axis=0,
                      **KW)
    for ba in (1, -1):
        ys1, st1 = odeint(_f, z0.T, TS, (W,), grad_method="aca",
                          batch_axis=ba, **KW)
        np.testing.assert_array_equal(np.asarray(ys0),
                                      np.asarray(jnp.swapaxes(ys1, 1, 2)))
        np.testing.assert_array_equal(np.asarray(st0.n_steps),
                                      np.asarray(st1.n_steps))


@pytest.mark.parametrize("method", GRAD_METHODS)
def test_fixed_grid_batched(method):
    """Fixed grids are shared exactly — batch_axis must equal vmap of the
    solo fixed-grid solve, with (B,)-broadcast stats."""
    if method == "mali":
        pytest.skip("the reversible pair integrator is adaptive-only "
                    "(no fixed-grid regime)")
    z0 = _hetero_batch(B=3)

    def loss_b(z0):
        ys, st = odeint(_f, z0, TS, (W,), solver="rk4", grad_method=method,
                        steps_per_interval=8, batch_axis=0)
        return jnp.sum(ys[-1] ** 2), (ys, st)

    def loss_s(z0):
        ys, _ = jax.vmap(
            lambda z: odeint(_f, z, TS, (W,), solver="rk4",
                             grad_method=method, steps_per_interval=8),
            in_axes=0, out_axes=(1, 0))(z0)
        return jnp.sum(ys[-1] ** 2), (ys, None)

    (_, (ys_b, st_b)), g_b = jax.value_and_grad(
        loss_b, has_aux=True)(z0)
    (_, (ys_s, _)), g_s = jax.value_and_grad(loss_s, has_aux=True)(z0)
    assert np.asarray(st_b.n_steps).shape == (3,)
    np.testing.assert_allclose(np.asarray(ys_b), np.asarray(ys_s),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_s),
                               rtol=1e-5, atol=1e-7)


def test_pytree_state_batched(_interpret_kernels):
    """Dict states batch too; the fused path ravels per sample into one
    (B, N) carry (maybe_flatten_batched)."""
    def f(t, z, w):
        return {"a": -1.5 * z["a"] + 0.1 * jnp.tanh(w * z["b"]),
                "b": -0.5 * z["b"]}

    z0 = {"a": jax.random.normal(jax.random.PRNGKey(0), (3, 4)),
          "b": jax.random.normal(jax.random.PRNGKey(1), (3, 4))}

    outs = {}
    for up in (False, True):
        def loss(w):
            ys, _ = odeint(f, z0, TS, (w,), grad_method="aca",
                           batch_axis=0, use_pallas=up, **KW)
            return sum(jnp.sum(v[-1] ** 2) for v in ys.values()), ys
        (_, ys), g = jax.value_and_grad(loss, has_aux=True)(W)
        outs[up] = (ys, g)
    for k in outs[False][0]:
        assert outs[False][0][k].shape == (TS.shape[0], 3, 4)
        # 1-ulp tolerance: the flat path computes the initial-stepsize
        # norm over one raveled leaf, the pytree path per leaf — a
        # different (legitimate) reduction order for multi-leaf states
        np.testing.assert_allclose(np.asarray(outs[False][0][k]),
                                   np.asarray(outs[True][0][k]),
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(outs[True][1]),
                               np.asarray(outs[False][1]),
                               rtol=1e-5, atol=1e-7)


def test_per_element_overflow():
    """max_steps exhaustion is per element: the stiff element overflows,
    the easy one still lands on its eval times."""
    z0 = jnp.stack([
        jnp.concatenate([jnp.ones((3,)) * 0.3, jnp.array([0.0])]),
        jnp.concatenate([jnp.ones((3,)) * 0.3, jnp.array([5.5])]),
    ]).astype(jnp.float32)
    _, stats = odeint(_f, z0, TS, (W,), grad_method="aca", batch_axis=0,
                      solver="dopri5", rtol=1e-7, atol=1e-7, max_steps=12)
    ov = np.asarray(stats.overflow)
    assert not ov[0] and ov[1], ov


# ------------------------------------------------------ row compaction
#
# From 2 * COMPACT_FLOOR rows up the batched loop runs in halving phases
# over a block of the rows still live.  A floor of 2 makes a 16-row batch
# run every phase (16, 8, 4, 2); a floor above B keeps one loop.

COMPACT_B = 16
COMPACT_CASES = ["full", "segments", "interpolate", "rowtol", "pallas",
                 "nonfinite"]


def _shuffled_batch(B=COMPACT_B):
    """Stiffness spread over the rows in shuffled order, so the live rows
    of each compacted block are scattered across the batch."""
    x0 = jax.random.normal(jax.random.PRNGKey(1), (B, 3))
    logk = jax.random.permutation(jax.random.PRNGKey(2),
                                  jnp.linspace(0.0, 3.5, B))
    return jnp.concatenate([x0, logk[:, None]], axis=1).astype(jnp.float32)


def _compaction_case(case):
    """(field, ts, engine kwargs, odeint kwargs) of one case."""
    f, ts = _f, TS
    rtol = atol = 1e-5
    eng, api = {}, {}
    if case == "segments":
        eng["checkpoint_segments"] = 4
        api["checkpoint_segments"] = 4
    elif case == "interpolate":
        ts = jnp.linspace(0.0, 1.0, 6).astype(jnp.float32)
        eng["interpolate_ts"] = api["interpolate_ts"] = True
    elif case == "rowtol":
        loose = jnp.arange(COMPACT_B) % 3 == 0
        rtol = jnp.where(loose, 1e-3, 1e-5).astype(jnp.float32)
        atol = jnp.where(loose, 1e-4, 1e-6).astype(jnp.float32)
    elif case == "pallas":
        eng["use_pallas"] = api["use_pallas"] = True
    elif case == "nonfinite":
        from faults import faulty_field
        # the stiffest row meets a NaN field from t = 0.3 on and freezes
        f = faulty_field(_f, "nan", t_ge=0.3,
                         predicate=lambda t, z: z[-1] > 3.4)
    return f, ts, rtol, atol, eng, api


def _compaction_run(case, floor, monkeypatch):
    from repro.core import ControllerConfig, get_tableau, integrate

    monkeypatch.setattr(integrate, "COMPACT_FLOOR", floor)
    f, ts, rtol, atol, eng, api = _compaction_case(case)
    z0 = _shuffled_batch()
    ys, ckpts, st = integrate.batched_adaptive_while_solve(
        get_tableau("dopri5"), f, z0, ts, (W,), rtol, atol,
        ControllerConfig(max_steps=64), **eng)

    def loss(z0, w):
        ys, st = odeint(f, z0, ts, (w,), grad_method="aca", batch_axis=0,
                        solver="dopri5", rtol=rtol, atol=atol,
                        max_steps=64, **api)
        return jnp.sum(ys[-1] ** 2), (ys, st)

    (_, (ys_api, st_api)), grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(z0, W)
    return dict(ys=ys, ckpts=ckpts, stats=_counted(st), ys_api=ys_api,
                stats_api=_counted(st_api), grads=grads), st


@pytest.mark.parametrize("case", COMPACT_CASES)
def test_compaction_is_bitwise(case, monkeypatch, _interpret_kernels):
    """The compacted loop gives every row the outputs, checkpoints,
    stats and ACA gradients of one loop over the whole batch, bit for
    bit."""
    one, st_one = _compaction_run(case, 10 * COMPACT_B, monkeypatch)
    cmp, st_cmp = _compaction_run(case, 2, monkeypatch)
    n_trials = np.asarray(st_one.n_trials)
    assert len(np.unique(n_trials)) > 4, n_trials   # phases do work
    assert (np.asarray(st_cmp.n_rides) < n_trials.max()).any()
    status = np.asarray(st_one.status)
    if case == "nonfinite":
        assert (status == SolveStatus.NONFINITE_STATE).sum() == 1, status
    else:
        assert (status == SolveStatus.OK).all(), status
    for key in one:
        la, lb = jax.tree.leaves(one[key]), jax.tree.leaves(cmp[key])
        assert len(la) == len(lb), key
        for a, b in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=key)


def test_n_rides_counts_block_slots(monkeypatch):
    """n_rides ≥ n_trials per row; one loop gives every row the trip
    count; the compacted loop processes fewer row-slots."""
    from repro.core import integrate

    z0 = _shuffled_batch()

    def solve(floor):
        monkeypatch.setattr(integrate, "COMPACT_FLOOR", floor)
        _, st = odeint(_f, z0, TS, (W,), grad_method="aca", batch_axis=0,
                       **KW)
        return np.asarray(st.n_trials), np.asarray(st.n_rides)

    trials, rides_one = solve(10 * COMPACT_B)
    trials_cmp, rides_cmp = solve(2)
    np.testing.assert_array_equal(trials, trials_cmp)
    # the straggler is live in every iteration: the trip count
    np.testing.assert_array_equal(rides_one, trials.max())
    assert (rides_one >= trials).all() and (rides_cmp >= trials).all()
    assert rides_cmp.sum() < rides_one.sum()
    assert trials.sum() / rides_cmp.sum() > trials.sum() / rides_one.sum()


def _count_while_loops(jaxpr) -> int:
    """``while`` equations in a jaxpr and every jaxpr nested in it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "while"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count_while_loops(inner)
    return n


@pytest.mark.parametrize("slots", [8, 512])
def test_serve_slots_keep_one_loop(slots):
    """The solve server's 8 slots stay under 2 * COMPACT_FLOOR and trace
    today's single loop; 512 slots trace one loop per phase."""
    from repro.core import integrate
    from repro.serve.node_engine import NodeEngineConfig, NodeServeEngine

    def f(t, z, w):
        return -w * z

    eng = NodeServeEngine(f, 3, (jnp.float32(1.0),),
                          NodeEngineConfig(slots=slots))
    row = jnp.zeros((slots,), jnp.float32)
    jaxpr = jax.make_jaxpr(eng._solve)(
        jnp.zeros((slots, 3 + 2), jnp.float32), row + 1e-5, row + 1e-5,
        row + 0.1)
    want = 1 if slots < 2 * integrate.COMPACT_FLOOR else \
        len(integrate._block_sizes(slots))
    assert want == (1 if slots == 8 else 3)
    assert _count_while_loops(jaxpr.jaxpr) == want
