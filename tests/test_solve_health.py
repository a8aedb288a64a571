"""Solve-health subsystem: status codes, non-finite guards, freeze
semantics, cotangent masking, policies, fallback ladder, and the
training/serving-layer guards that compose with them.

Fault injection comes from ``tests/faults.py``; the default
(``on_failure="status"``, no faults) path is asserted bitwise-identical
with the guards compiled out (``guard_nonfinite=False``), which is the
same property the ``bench_failure_overhead`` gate prices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ControllerConfig,
    SolveStatus,
    adaptive_while_solve,
    batched_adaptive_while_solve,
    odeint,
    odeint_checked,
    solve_with_fallback,
)
from repro.core.integrate import mali_adaptive_solve
from repro.core.tableaus import get_tableau

from faults import faulty_field

METHODS = ("aca", "adjoint", "naive", "mali")
TOL = dict(rtol=1e-3, atol=1e-3)      # keeps mali inside its step budget


def _kw(method, **extra):
    kw = dict(TOL, grad_method=method, **extra)
    if method != "mali":
        kw["solver"] = "dopri5"
    return kw


def _decay(t, z):
    return -z


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------ status
@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("batched", [False, True])
def test_clean_solve_status_ok(method, batched):
    z0 = jnp.ones((3, 4)) if batched else jnp.ones((4,))
    ts = jnp.linspace(0.0, 1.0, 4)
    kw = _kw(method, batch_axis=0) if batched else _kw(method)
    ys, stats = odeint(_decay, z0, ts, **kw)
    assert bool(jnp.all(stats.status == SolveStatus.OK)), stats.status
    assert bool(jnp.isfinite(ys).all())


@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_nan_fault_detected_and_frozen(method, batched, use_pallas):
    """Mid-solve NaN: NONFINITE_STATE status, finite outputs, and the
    pre-fault eval prefix bit-equal to the unfaulted solve."""
    z0 = jnp.ones((3, 4)) if batched else jnp.ones((4,))
    ts = jnp.linspace(0.0, 1.0, 5)
    t_fault = 0.45
    kw = _kw(method, use_pallas=use_pallas)
    if batched:
        kw["batch_axis"] = 0
    ys_ok, _ = odeint(_decay, z0, ts, **kw)
    ys, stats = odeint(faulty_field(_decay, "nan", t_ge=t_fault),
                       z0, ts, **kw)
    assert bool(jnp.all(stats.status == SolveStatus.NONFINITE_STATE)), \
        stats.status
    assert bool(jnp.isfinite(ys).all())
    # eval times strictly before the trigger never saw a faulted stage
    n_pre = int((np.asarray(ts) < t_fault).sum())
    _assert_bitwise(ys[:n_pre], ys_ok[:n_pre])
    # post-fault slots are all the frozen last-accepted state
    for k in range(n_pre + 1, ts.shape[0]):
        _assert_bitwise(ys[k], ys[n_pre])


@pytest.mark.parametrize("kind", ["nan", "inf", "spike"])
def test_fault_kinds_all_unhealthy(kind):
    """Every injector kind ends with a non-OK status (NaN/Inf are
    detected as NONFINITE; a finite 1e30 spike wrecks the error test
    instead and surfaces as underflow/budget exhaustion)."""
    z0 = jnp.ones((4,))
    ts = jnp.linspace(0.0, 1.0, 4)
    ys, stats = odeint(faulty_field(_decay, kind, t_ge=0.45), z0, ts,
                       **_kw("aca"))
    assert int(stats.status) != SolveStatus.OK
    if kind in ("nan", "inf"):
        assert int(stats.status) == SolveStatus.NONFINITE_STATE
    assert bool(jnp.isfinite(ys).all())


def test_status_underflow_budget_overflow():
    """The three degradation codes are distinguishable: a discontinuity
    rails h at h_min while still failing the error test (UNDERFLOW); a
    1-trial budget exhausts trials (BUDGET); a tight tolerance with a
    tiny step cap runs out of checkpoints (OVERFLOW)."""
    z0 = jnp.ones((2,))
    ts = jnp.linspace(0.0, 1.0, 3)

    def fjump(t, z):
        return jnp.where(t < 0.5, 1.0, -1e6) * jnp.ones_like(z)

    _, stats = odeint(fjump, z0, ts, rtol=1e-6, atol=1e-9, max_steps=256)
    assert int(stats.status) == SolveStatus.STEPSIZE_UNDERFLOW

    def fstiff(t, z):
        return -1e5 * z

    _, stats = odeint(fstiff, z0, ts, rtol=1e-12, atol=1e-14,
                      max_steps=64, max_trials=1)
    assert int(stats.status) == SolveStatus.TRIAL_BUDGET_EXHAUSTED

    _, stats = odeint(_decay, z0, ts, rtol=1e-12, atol=1e-14, max_steps=8)
    assert int(stats.status) == SolveStatus.CHECKPOINT_OVERFLOW


def test_status_describe():
    assert SolveStatus.describe(SolveStatus.OK) == "OK"
    assert SolveStatus.describe(
        SolveStatus.NONFINITE_STATE) == "NONFINITE_STATE"
    for code in range(5):
        assert "UNKNOWN" not in SolveStatus.describe(code)
    assert "UNKNOWN" in SolveStatus.describe(99)


# ------------------------------------------------- batched isolation/grads
@pytest.mark.parametrize("method", METHODS)
def test_batched_single_element_fault_isolated(method):
    """One poisoned batch element: its status flips, every other
    element's trajectory is bit-identical to the unfaulted batch, and
    (aca/adjoint/mali) gradients stay finite with the failed row's
    dz0 exactly zero."""
    # state = [x, tag]; the tag channel is constant and marks element 1
    def f(t, z):
        return jnp.stack([-z[0], 0.0 * z[1]])

    z0 = jnp.stack([jnp.array([1.0, 0.0]), jnp.array([1.0, 1.0]),
                    jnp.array([1.0, 2.0])])
    ts = jnp.linspace(0.0, 1.0, 4)
    # tolerant tag match: MALI's lattice quantization perturbs the tag
    # channel by ~1 ulp (1.0 decodes as 0.99999994), so exact equality
    # would never trigger the fault there
    fbad = faulty_field(f, "nan", t_ge=0.45,
                        predicate=lambda t, z: jnp.abs(z[1] - 1.0) < 0.5)
    kw = _kw(method, batch_axis=0)

    ys_ok, _ = odeint(f, z0, ts, **kw)
    ys, stats = odeint(fbad, z0, ts, **kw)
    assert [int(s) for s in stats.status] == [
        SolveStatus.OK, SolveStatus.NONFINITE_STATE, SolveStatus.OK]
    assert bool(jnp.isfinite(ys).all())
    _assert_bitwise(ys[:, 0], ys_ok[:, 0])
    _assert_bitwise(ys[:, 2], ys_ok[:, 2])

    if method == "naive":
        # naive keeps the faulted trial on its differentiable tape, so
        # post-fault gradients are not guaranteed finite (documented in
        # docs/robustness.md); the train-loop skip-step guard is the
        # mitigation there
        return

    def loss(z):
        ys, _ = odeint(fbad, z, ts, **kw)
        return jnp.sum(ys[-1, :, 0] ** 2)

    g = jax.grad(loss)(z0)
    assert bool(jnp.isfinite(g).all()), g
    _assert_bitwise(g[1], jnp.zeros_like(g[1]))  # failed row: exact zeros
    assert float(jnp.abs(g[0]).max()) > 0.0      # healthy rows still flow


# -------------------------------------------------- default-path identity
def test_guards_are_bitwise_noop_on_healthy_solve():
    """guard_nonfinite=True vs False: identical trajectories and
    counters on a healthy solve — the status field is the only
    addition."""
    tab = get_tableau("dopri5")
    cfg = ControllerConfig()
    z0 = jnp.ones((4,))
    ts = jnp.linspace(0.0, 1.0, 4)

    ys_g, _, st_g = adaptive_while_solve(
        tab, _decay, z0, ts, (), 1e-6, 1e-6, cfg, guard_nonfinite=True)
    ys_n, _, st_n = adaptive_while_solve(
        tab, _decay, z0, ts, (), 1e-6, 1e-6, cfg, guard_nonfinite=False)
    _assert_bitwise(ys_g, ys_n)
    _assert_bitwise(st_g.n_steps, st_n.n_steps)
    _assert_bitwise(st_g.n_trials, st_n.n_trials)
    assert int(st_g.status) == SolveStatus.OK

    z0b = jnp.ones((3, 4))
    ys_g, _, st_g = batched_adaptive_while_solve(
        tab, _decay, z0b, ts, (), 1e-6, 1e-6, cfg, guard_nonfinite=True)
    ys_n, _, st_n = batched_adaptive_while_solve(
        tab, _decay, z0b, ts, (), 1e-6, 1e-6, cfg, guard_nonfinite=False)
    _assert_bitwise(ys_g, ys_n)
    _assert_bitwise(st_g.n_trials, st_n.n_trials)

    ys_g, _, st_g = mali_adaptive_solve(
        _decay, z0, ts, (), 1e-3, 1e-3, cfg, guard_nonfinite=True)
    ys_n, _, st_n = mali_adaptive_solve(
        _decay, z0, ts, (), 1e-3, 1e-3, cfg, guard_nonfinite=False)
    _assert_bitwise(ys_g, ys_n)
    _assert_bitwise(st_g.n_trials, st_n.n_trials)


# ------------------------------------------------------------- policies
def test_on_failure_validation():
    z0, ts = jnp.ones((2,)), jnp.linspace(0.0, 1.0, 3)
    with pytest.raises(ValueError, match="on_failure"):
        odeint(_decay, z0, ts, on_failure="explode")
    with pytest.raises(ValueError, match="h0"):
        odeint(_decay, z0, ts, solver="rk4", h0=0.1)


def test_on_failure_warn_smoke():
    z0, ts = jnp.ones((2,)), jnp.linspace(0.0, 1.0, 3)
    fbad = faulty_field(_decay, "nan", t_ge=0.45)
    ys, stats = odeint(fbad, z0, ts, on_failure="warn", **_kw("aca"))
    jax.effects_barrier()
    assert int(stats.status) == SolveStatus.NONFINITE_STATE
    # healthy solve must not warn (and must stay bit-identical)
    ys, stats = odeint(_decay, z0, ts, on_failure="warn", **_kw("aca"))
    assert int(stats.status) == SolveStatus.OK


def test_odeint_checked_raises_on_fault():
    from jax.experimental import checkify

    z0, ts = jnp.ones((2,)), jnp.linspace(0.0, 1.0, 3)
    ys, stats = odeint_checked(_decay, z0, ts, **_kw("aca"))
    assert int(stats.status) == SolveStatus.OK
    fbad = faulty_field(_decay, "nan", t_ge=0.45)
    with pytest.raises(checkify.JaxRuntimeError, match="status"):
        odeint_checked(fbad, z0, ts, **_kw("aca"))


def test_node_config_threads_on_failure():
    from repro.core import NodeConfig, node_block_apply

    cfg = NodeConfig(enabled=True, on_failure="status")
    params = {"w": jnp.ones((3,)) * 0.1}

    def block(p, z, t):
        return -p["w"] * z

    zT, stats = node_block_apply(block, params, jnp.ones((3,)), cfg)
    assert bool(jnp.isfinite(zT).all())
    assert int(stats.status) == SolveStatus.OK


# ------------------------------------------------------------- fallback
def test_solve_with_fallback_recovers():
    z0, ts = jnp.ones((2,)), jnp.linspace(0.0, 1.0, 3)
    # tight tolerance + tiny step cap fails; the ladder's fixed-rk4
    # rung has no stepsize search left to exhaust
    ys, stats, report = solve_with_fallback(
        _decay, z0, ts, rtol=1e-12, atol=1e-14, max_steps=8)
    assert bool(jnp.all(stats.status == SolveStatus.OK))
    assert bool(jnp.isfinite(ys).all())
    assert report[0]["ok"] is False
    assert report[-1]["ok"] is True
    assert any("rk4" in r["note"] for r in report)
    np.testing.assert_allclose(np.asarray(ys[-1]),
                               np.exp(-1.0) * np.ones(2), rtol=1e-4)


def test_solve_with_fallback_healthy_short_circuits():
    z0, ts = jnp.ones((2,)), jnp.linspace(0.0, 1.0, 3)
    ys, stats, report = solve_with_fallback(_decay, z0, ts, **_kw("aca"))
    assert len(report) == 1 and report[0]["note"] == "original"
    assert report[0]["ok"] is True


def test_solve_with_fallback_unrecoverable_returns_frozen():
    z0, ts = jnp.ones((2,)), jnp.linspace(0.0, 1.0, 3)
    fbad = faulty_field(_decay, "nan", t_ge=0.45)
    ys, stats, report = solve_with_fallback(fbad, z0, ts, **_kw("aca"))
    assert all(not r.get("ok") for r in report)
    assert int(stats.status) == SolveStatus.NONFINITE_STATE
    assert bool(jnp.isfinite(ys).all())   # frozen, not garbage


# -------------------------------------------------------- train guards
def test_clip_by_global_norm_nonfinite():
    from repro.optim.grad_utils import clip_by_global_norm

    g = {"a": jnp.ones((3,)), "b": jnp.array([jnp.inf, 1.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert not bool(jnp.isfinite(norm))       # raw norm surfaces the Inf
    for leaf in jax.tree.leaves(clipped):      # default: zeroed, not NaN
        _assert_bitwise(leaf, jnp.zeros_like(leaf))
    clipped, norm = clip_by_global_norm(g, 1.0, on_nonfinite="keep")
    _assert_bitwise(clipped["a"], g["a"])      # kept unclipped, unscaled
    with pytest.raises(ValueError, match="on_nonfinite"):
        clip_by_global_norm(g, 1.0, on_nonfinite="explode")
    # healthy path unchanged
    g2 = {"a": jnp.ones((3,)) * 3.0}
    clipped, norm = clip_by_global_norm(g2, 1.0)
    np.testing.assert_allclose(float(norm), 3.0 * np.sqrt(3.0), rtol=1e-6)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(clipped["a"])), 1.0, rtol=1e-6)


class _ToyModel:
    """Quadratic toy whose loss goes NaN whenever the batch does."""

    def loss_fn(self, params, batch):
        loss = jnp.mean((params["w"] * batch["x"] - 1.0) ** 2)
        return loss, {}


def test_train_step_skips_nonfinite_update():
    from repro.optim.adamw import adamw
    from repro.train import TrainState, build_train_step
    from repro.train.loop import TrainLoopConfig

    model, opt = _ToyModel(), adamw(lambda s: 1e-2)
    params = {"w": jnp.ones((4,))}
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt.init(params))
    step = build_train_step(model, opt, TrainLoopConfig())

    from repro.optim.grad_utils import CompressionState
    comp = CompressionState(error=())
    clean = {"x": jnp.ones((4,)) * 2.0}
    poison = {"x": jnp.full((4,), jnp.nan)}

    s1, comp, m1 = step(state, clean, comp)
    assert int(m1["skipped"]) == 0
    assert float(jnp.abs(s1.params["w"] - params["w"]).max()) > 0.0

    s2, comp, m2 = step(s1, poison, comp)
    assert int(m2["skipped"]) == 1
    assert int(s2.step) == int(s1.step) + 1   # step advances anyway
    _assert_bitwise(s2.params["w"], s1.params["w"])   # update held
    for a, b in zip(jax.tree.leaves(s2.opt_state),
                    jax.tree.leaves(s1.opt_state)):
        _assert_bitwise(a, b)

    # guard off: no skip metric, and params stay finite only because
    # clip_by_global_norm zeroes the non-finite grads (defense in
    # depth) — but the held-update contract is gone: adamw's weight
    # decay + stale momentum still move the params on the poisoned step
    step_raw = build_train_step(
        model, opt, TrainLoopConfig(skip_nonfinite=False))
    s3, _, m3 = step_raw(s1, poison, comp)
    assert "skipped" not in m3
    assert not bool(jnp.isfinite(m3["loss"]))          # loss is NaN
    assert bool(jnp.isfinite(s3.params["w"]).all())    # clip guard held
    assert float(jnp.abs(s3.params["w"] - s1.params["w"]).max()) > 0.0


def test_train_loop_counts_skipped_steps():
    from repro.optim.adamw import adamw
    from repro.train import TrainLoop, TrainState
    from repro.train.loop import TrainLoopConfig

    model, opt = _ToyModel(), adamw(lambda s: 1e-2)
    params = {"w": jnp.ones((4,))}
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt.init(params))
    loop = TrainLoop(model, opt, TrainLoopConfig(log_every=1), state,
                     jit=False)

    def batch_fn(s):
        if s == 1:
            return {"x": jnp.full((4,), jnp.nan)}
        return {"x": jnp.ones((4,)) * 2.0}

    loop.run(batch_fn, 3)
    assert loop.skipped_steps == 1
    assert bool(jnp.isfinite(loop.state.params["w"]).all())


# --------------------------------------------------------------- serve
class _ScriptedModel:
    """Serving stub that emits a scripted token sequence per row."""

    def __init__(self, script, vocab=16):
        self.script = np.asarray(script)     # (B, T) new-token ids
        self.vocab = vocab

    def _logits(self, idx):
        return jax.nn.one_hot(jnp.asarray(self.script[:, idx]),
                              self.vocab) * 10.0

    def prefill(self, params, batch):
        self._s = batch["tokens"].shape[1]
        return self._logits(0), jnp.zeros((), jnp.int32)

    def decode_step(self, params, batch, caches, pos):
        idx = int(pos) - self._s + 1
        return self._logits(idx), caches


def test_serve_generate_breaks_early_on_eos():
    from repro.serve import ServeConfig, ServeEngine

    eos = 7
    # rows finish after 3, 5 and 2 new tokens respectively
    script = [[1, 2, eos, 3, 3, 3, 3, 3],
              [1, 2, 3, 4, eos, 3, 3, 3],
              [1, eos, 3, 3, 3, 3, 3, 3]]
    model = _ScriptedModel(script)
    eng = ServeEngine(model, params={},
                      cfg=ServeConfig(max_new_tokens=8, eos_id=eos),
                      jit=False)
    toks = jnp.zeros((3, 4), jnp.int32)
    out = eng.generate(toks)["tokens"]
    # loop stops right after the slowest row's eos: 4 decode steps,
    # not max_new_tokens - 1 = 7
    assert eng.last_decode_steps == 4
    assert out.shape == (3, 4 + 5)
    got = np.asarray(out[:, 4:])
    np.testing.assert_array_equal(got[0], [1, 2, eos, eos, eos])
    np.testing.assert_array_equal(got[1], [1, 2, 3, 4, eos])
    np.testing.assert_array_equal(got[2], [1, eos, eos, eos, eos])


def test_serve_generate_all_eos_at_first_token():
    from repro.serve import ServeConfig, ServeEngine

    eos = 7
    script = [[eos] * 8, [eos] * 8]
    eng = ServeEngine(_ScriptedModel(script), params={},
                      cfg=ServeConfig(max_new_tokens=8, eos_id=eos),
                      jit=False)
    out = eng.generate(jnp.zeros((2, 4), jnp.int32))["tokens"]
    assert eng.last_decode_steps == 0     # decode loop never entered
    assert out.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(out[:, -1]), [eos, eos])


def test_serve_generate_no_eos_runs_full_budget():
    from repro.serve import ServeConfig, ServeEngine

    script = [[1] * 8, [2] * 8]
    eng = ServeEngine(_ScriptedModel(script), params={},
                      cfg=ServeConfig(max_new_tokens=8), jit=False)
    out = eng.generate(jnp.zeros((2, 4), jnp.int32))["tokens"]
    assert eng.last_decode_steps == 7
    assert out.shape == (2, 12)
