"""The naive method — direct back-propagation through the ODE solver.

The paper's second baseline (Sec. 3.3): every solver operation, *including
the stepsize search*, stays on the differentiation path.  The stepsize
update chain  h_{i+1} = h_i · decay(ê_i)  is itself differentiated, so the
computation graph has depth O(N_f · N_t · m) and reverse-mode AD stores the
stage intermediates of every trial — the paper's memory blow-up, realized
in JAX as scan-carried residuals over the full trial budget.

JAX cannot reverse-differentiate a dynamic-trip-count ``while_loop``, so the
adaptive naive solver is a *bounded* ``lax.scan`` over the flattened
trial/accept loop with where-masking once integration finishes — the
standard fixed-budget encoding; the budget (max_steps × max_trials) plays
the role of the tape length.

Sharding contract (relied on by ``odeint(..., mesh=...)``): the batched
scan tape is per-row, so reverse-mode AD through it is **shard-local**
under ``shard_map``; only the shared-``args`` cotangent crosses devices
(one psum from the transpose).  See ``docs/distributed.md``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .controller import ControllerConfig, initial_stepsize, propose_stepsize
from .integrate import (
    SolveStats,
    _as_tuple,
    _buffer_set,
    _bwhere,
    _compose_status,
    _empty_buffer,
    _freeze_fill,
    _nonfinite_any,
    _nonfinite_rows,
    _row_tolerances,
    fixed_grid_solve,
    natural_grid_outputs,
    natural_grid_outputs_batched,
)
from .stepper import (
    error_ratio,
    maybe_flatten,
    maybe_flatten_batched,
    rk_step,
    rk_step_batched,
)
from .tableaus import Tableau

PyTree = Any


def odeint_naive(
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: PyTree = (),
    *,
    solver: Tableau,
    rtol: float = 1e-6,
    atol: float = 1e-6,
    cfg: Optional[ControllerConfig] = None,
    trial_budget: Optional[int] = None,
    use_pallas: bool = False,
    interpolate_ts: bool = False,
    h0: Optional[jnp.ndarray] = None,
) -> Tuple[PyTree, SolveStats]:
    """Differentiable adaptive solve (naive method).

    ``trial_budget`` bounds the total number of ψ trials (accepted or
    rejected); defaults to cfg.max_steps * cfg.max_trials.  ``h0``
    overrides the Hairer initial stepsize (ignored on the fixed-grid
    fallback).

    Solve-health: non-finite trials are never accepted; once the
    stepsize rails at ``h_min`` with the trial still non-finite the
    element freezes at its last accepted state (post-failure iterations
    take the same discarded sliver trials as finished elements) and
    ``stats.status`` reports ``SolveStatus.NONFINITE_STATE``.  NOTE:
    unlike the custom-vjp methods, the naive method keeps *every* trial
    on the differentiation tape — including the non-finite one that
    tripped the guard — so gradients after a fault are not guaranteed
    finite here; pair with the train-loop skip-step guard
    (``docs/robustness.md``).

    ``use_pallas`` runs every recorded trial (step + error norm) through
    the fused flat-state kernels over the raveled state; reverse-mode AD
    goes through their custom_vjp, including the stepsize chain via the
    fused ``ratio``.

    ``interpolate_ts`` advances on the controller's natural grid and
    reads interior eval times off per-step interpolants; the
    interpolation arithmetic sits on the tape like everything else, so
    reverse-mode AD differentiates through it (including θ's dependence
    on the stepsize chain — everything stays on the naive tape).
    """
    if cfg is None:
        cfg = ControllerConfig()
    if not solver.adaptive:
        return fixed_grid_solve(solver, f, z0, ts, _as_tuple(args),
                                steps_per_interval=cfg.max_steps,
                                use_pallas=use_pallas)

    f, z0, unravel, use_pallas = maybe_flatten(f, z0, use_pallas)

    n_eval = ts.shape[0]
    tdt = ts.dtype
    budget = trial_budget if trial_budget is not None else (
        cfg.max_steps * cfg.max_trials)
    tiny = jnp.asarray(jnp.finfo(tdt).eps, tdt)
    targs = _as_tuple(args)
    karr = jnp.arange(n_eval)

    h_init = initial_stepsize(f, ts[0], z0, targs, solver.order, rtol,
                              atol) if h0 is None else h0

    ys0 = jax.tree.map(
        lambda l: jnp.zeros((n_eval,) + l.shape, l.dtype), z0)
    ys0 = jax.tree.map(lambda b, v: b.at[0].set(v), ys0, z0)

    failed0 = _nonfinite_any(
        (z0, jnp.asarray(h_init, tdt)))

    carry0 = dict(
        t=ts[0], z=z0, h=jnp.asarray(h_init, tdt),
        prev_ratio=jnp.asarray(1.0, jnp.float32),
        eval_idx=jnp.asarray(1, jnp.int32),
        n_acc=jnp.asarray(0, jnp.int32),
        failed=failed0, uflow=jnp.asarray(False),
        ys=ys0,
    )

    def body(c, _):
        # failed elements behave exactly like finished ones: frozen
        # state, discarded sliver trials until the budget runs out
        done = (c["eval_idx"] >= n_eval) | c["failed"]
        t, z, h = c["t"], c["z"], c["h"]
        t_target = ts[n_eval - 1] if interpolate_ts else \
            ts[jnp.minimum(c["eval_idx"], n_eval - 1)]
        h_min = 16.0 * tiny * jnp.maximum(jnp.abs(t), jnp.asarray(1.0, tdt))
        # done elements keep taking discarded sliver trials, but the
        # sliver is pinned to FLOAT32 eps regardless of the time dtype:
        # an ~eps(float64) step puts ratios of order eps/tol on the
        # tape, whose pow/sqrt jacobians overflow f32 and fuse into NaN
        # (a full-size h would instead evaluate f past ts[-1], where the
        # field may be singular).  In f32 time this is exactly h_min.
        h_done = 16.0 * jnp.asarray(jnp.finfo(jnp.float32).eps, tdt) \
            * jnp.maximum(jnp.abs(t), jnp.asarray(1.0, tdt))
        h_use = jnp.where(done, h_done,
                          jnp.clip(h, h_min,
                                   jnp.maximum(t_target - t, h_min)))

        # NOTE: no k0 caching here — the naive method re-records the whole
        # trial in the graph, including the first stage.
        res = rk_step(solver, f, t, z, h_use, targs,
                      use_pallas=use_pallas, err_scale=(rtol, atol),
                      dense=interpolate_ts)
        ratio = res.err_ratio if res.err_ratio is not None else \
            error_ratio(res.err, z, res.z_next, rtol, atol)
        railed = h_use <= h_min * (1 + 1e-3)
        # detection reads stop_gradiented values: the flags must not
        # add edges to the naive tape
        bad = _nonfinite_any(jax.lax.stop_gradient(res.z_next)) | \
            ~jnp.isfinite(jax.lax.stop_gradient(ratio))
        accept = (~done) & ((ratio <= 1.0) | railed) & ~bad
        fail_now = (~done) & bad & railed
        uflow_now = accept & railed & (ratio > 1.0)

        t_new = t + h_use
        hit = accept & (t_new >= t_target - 16.0 * tiny * jnp.maximum(
            jnp.abs(t_target), jnp.asarray(1.0, tdt)))

        if interpolate_ts:
            # interior eval times read off this trial's interpolant —
            # all on the tape, like everything else in the naive method
            k1 = res.k_last if solver.fsal else \
                f(t_new, res.z_next, *targs)
            ys, _, _, eval_advance = natural_grid_outputs(
                ts, karr, tiny, t, t_new, h_use, accept, hit,
                c["eval_idx"], c["ys"], z, res.z_next, res.k_first,
                k1, res.z_mid)
        else:
            ys = jax.tree.map(
                lambda b, v: b.at[c["eval_idx"]].set(
                    jnp.where(hit, v, b[jnp.minimum(c["eval_idx"],
                                                    n_eval - 1)])),
                c["ys"], res.z_next)
            eval_advance = hit.astype(jnp.int32)

        # differentiable stepsize chain: gradient flows through `ratio`
        # into h_next — the redundant graph the paper criticizes.  A
        # done element's h_next is discarded by the where below, but its
        # post-done h_min trials produce ratios ~eps(tdt)/tol whose
        # ratio^(-1/p) jacobian overflows f32 under x64 time grids and
        # XLA fusion can turn the masked inf into NaN — feed the
        # discarded computation a neutral ratio instead.  Non-finite
        # ratios get the same neutral treatment so the h chain cannot
        # absorb a NaN.
        ratio_h = jnp.where(done | bad, jnp.ones_like(ratio), ratio)
        h_next = propose_stepsize(cfg, h_use, ratio_h, c["prev_ratio"],
                                  solver.order).astype(tdt)

        c_new = dict(
            t=jnp.where(accept, t_new, t),
            z=jax.tree.map(lambda a, b: jnp.where(accept, a, b),
                           res.z_next, z),
            h=jnp.where(done, h, h_next),
            prev_ratio=jnp.where(accept, jnp.maximum(ratio, 1e-10),
                                 c["prev_ratio"]),
            eval_idx=c["eval_idx"] + eval_advance,
            n_acc=c["n_acc"] + accept.astype(jnp.int32),
            failed=c["failed"] | fail_now,
            uflow=c["uflow"] | uflow_now,
            ys=ys,
        )
        return c_new, None

    c, _ = jax.lax.scan(body, carry0, None, length=budget)
    # frozen solve: repeat the last accepted state into un-reached slots
    # (stop_gradiented — a failed element's cotangents stay off the fill)
    fill = c["failed"] & (karr >= c["eval_idx"])
    ys_filled = _freeze_fill(c["ys"], fill,
                             jax.lax.stop_gradient(c["z"]))
    ys_out = ys_filled if unravel is None else jax.vmap(unravel)(ys_filled)

    overflow = c["eval_idx"] < n_eval
    status = _compose_status(c["failed"], c["uflow"], ~overflow,
                             jnp.asarray(True))
    # interpolate mode on a non-FSAL pair pays one extra k1 eval/trial
    evals_per_trial = solver.stages + (
        1 if interpolate_ts and not solver.fsal else 0)
    stats = SolveStats(
        n_steps=jax.lax.stop_gradient(c["n_acc"]),
        n_trials=jnp.asarray(budget, jnp.int32),
        nfe=jnp.asarray(budget * evals_per_trial, jnp.int32),
        overflow=jax.lax.stop_gradient(overflow),
        status=jax.lax.stop_gradient(status),
    )
    return ys_out, stats


def odeint_naive_batched(
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: PyTree = (),
    *,
    solver: Tableau,
    rtol: float = 1e-6,
    atol: float = 1e-6,
    cfg: Optional[ControllerConfig] = None,
    trial_budget: Optional[int] = None,
    use_pallas: bool = False,
    interpolate_ts: bool = False,
    h0: Optional[jnp.ndarray] = None,
) -> Tuple[PyTree, SolveStats]:
    """Per-sample batched naive method: ``odeint(..., batch_axis=0)``
    with direct backprop through the masked solver scan.

    ``z0`` leaves carry a leading batch dim B and ``f`` is per-sample.
    The bounded ``lax.scan`` advances every element each iteration with
    its own trial stepsize, accept/reject mask and differentiable
    stepsize chain; finished elements are where-frozen (they keep taking
    discarded h_min trials — a zero step's error norm would put sqrt(0)
    on the tape and NaN the backward pass), so reverse-mode AD through
    the scan yields each element's own discretize-then-optimize gradient —
    including the per-element stepsize-search graph the paper
    criticizes.  ``trial_budget`` bounds the scan length (shared across
    elements); defaults to cfg.max_steps * cfg.max_trials.
    ``interpolate_ts`` / ``h0`` / solve-health semantics (including the
    naive-tape gradient caveat after a fault) as in ``odeint_naive``,
    per element.
    """
    if cfg is None:
        cfg = ControllerConfig()
    if not solver.adaptive:
        raise ValueError(
            "odeint_naive_batched requires an embedded adaptive tableau; "
            "fixed grids batch losslessly through odeint_naive_fixed")

    f, z0, unravel, use_pallas = maybe_flatten_batched(f, z0, use_pallas)

    B = jax.tree.leaves(z0)[0].shape[0]
    rows = jnp.arange(B)
    n_eval = ts.shape[0]
    tdt = ts.dtype
    budget = trial_budget if trial_budget is not None else (
        cfg.max_steps * cfg.max_trials)
    tiny = jnp.asarray(jnp.finfo(tdt).eps, tdt)
    targs = _as_tuple(args)

    row_tol = _row_tolerances(rtol, atol, B)
    if h0 is None:
        if row_tol is not None:
            h_init = jax.vmap(lambda z, rt, at: initial_stepsize(
                f, ts[0], z, targs, solver.order, rt, at))(z0, *row_tol)
        else:
            h_init = jax.vmap(lambda z: initial_stepsize(
                f, ts[0], z, targs, solver.order, rtol, atol))(z0)
    else:
        h_init = jnp.broadcast_to(jnp.asarray(h0, tdt), (B,))

    ys0 = _buffer_set(_empty_buffer(z0, n_eval), 0, z0)

    failed0 = _nonfinite_rows((z0, jnp.asarray(h_init, tdt)))

    carry0 = dict(
        t=jnp.full((B,), ts[0], tdt), z=z0,
        h=jnp.asarray(h_init, tdt),
        prev_ratio=jnp.ones((B,), jnp.float32),
        eval_idx=jnp.ones((B,), jnp.int32),
        n_acc=jnp.zeros((B,), jnp.int32),
        failed=failed0, uflow=jnp.zeros((B,), bool),
        ys=ys0,
    )

    karr = jnp.arange(n_eval)

    def body(c, _):
        # failed rows behave exactly like finished ones: frozen state,
        # discarded sliver trials until the budget runs out
        done = (c["eval_idx"] >= n_eval) | c["failed"]      # (B,)
        t, z, h = c["t"], c["z"], c["h"]
        t_target = ts[n_eval - 1] if interpolate_ts else \
            ts[jnp.minimum(c["eval_idx"], n_eval - 1)]
        h_min = 16.0 * tiny * jnp.maximum(jnp.abs(t), jnp.asarray(1.0, tdt))
        # done elements keep taking discarded float32-eps sliver trials
        # (see odeint_naive): h = 0 would put sqrt(0) on the tape, an
        # ~eps(float64) sliver's ratio jacobian overflows f32, and a
        # full-size h would evaluate f past each element's ts[-1]
        h_done = 16.0 * jnp.asarray(jnp.finfo(jnp.float32).eps, tdt) \
            * jnp.maximum(jnp.abs(t), jnp.asarray(1.0, tdt))
        h_use = jnp.where(done, h_done,
                          jnp.clip(h, h_min,
                                   jnp.maximum(t_target - t, h_min)))

        # NOTE: no k0 caching here — the naive method re-records the whole
        # trial in the graph, including the first stage (per element).
        res = rk_step_batched(solver, f, t, z, h_use, targs,
                              use_pallas=use_pallas, err_scale=(rtol, atol),
                              dense=interpolate_ts)
        ratio = res.err_ratio                               # (B,)
        railed = h_use <= h_min * (1 + 1e-3)
        # detection reads stop_gradiented values: the flags must not
        # add edges to the naive tape (per element)
        bad = _nonfinite_rows(jax.lax.stop_gradient(res.z_next)) | \
            ~jnp.isfinite(jax.lax.stop_gradient(ratio))
        accept = (~done) & ((ratio <= 1.0) | railed) & ~bad
        fail_now = (~done) & bad & railed
        uflow_now = accept & railed & (ratio > 1.0)

        t_new = t + h_use
        hit = accept & (t_new >= t_target - 16.0 * tiny * jnp.maximum(
            jnp.abs(t_target), jnp.asarray(1.0, tdt)))

        if interpolate_ts:
            # per-element interior reads off each row's interpolant (all
            # on the tape); ts[-1] stays an exact landing per element
            if solver.fsal:
                k1 = res.k_last
            else:
                k1 = jax.vmap(lambda ti, zi: f(ti, zi, *targs))(
                    t_new, res.z_next)
            ys, _, _, eval_advance = natural_grid_outputs_batched(
                ts, karr, tiny, None, t, t_new, h_use, accept, hit,
                c["eval_idx"], c["ys"], z, res.z_next, res.k_first,
                k1, res.z_mid)
        else:
            e_c = jnp.minimum(c["eval_idx"], n_eval - 1)
            ys = jax.tree.map(
                lambda b, v: b.at[e_c, rows].set(
                    _bwhere(hit, v, b[e_c, rows])),
                c["ys"], res.z_next)
            eval_advance = hit.astype(jnp.int32)

        # differentiable per-element stepsize chain: gradient flows
        # through each element's own `ratio` into its h_next.  done
        # rows get a neutral ratio (see odeint_naive: their h_next is
        # discarded, and the h_min-trial ratio's pow jacobian would
        # overflow f32 under x64 time grids).  Non-finite ratios get the
        # same neutral treatment so the h chain cannot absorb a NaN.
        ratio_h = jnp.where(done | bad, jnp.ones_like(ratio), ratio)
        h_next = propose_stepsize(cfg, h_use, ratio_h, c["prev_ratio"],
                                  solver.order).astype(tdt)

        c_new = dict(
            t=jnp.where(accept, t_new, t),
            z=jax.tree.map(lambda a, b: _bwhere(accept, a, b), res.z_next, z),
            h=jnp.where(done, h, h_next),
            prev_ratio=jnp.where(accept, jnp.maximum(ratio, 1e-10),
                                 c["prev_ratio"]),
            eval_idx=c["eval_idx"] + eval_advance,
            n_acc=c["n_acc"] + accept.astype(jnp.int32),
            failed=c["failed"] | fail_now,
            uflow=c["uflow"] | uflow_now,
            ys=ys,
        )
        return c_new, None

    c, _ = jax.lax.scan(body, carry0, None, length=budget)
    fill = c["failed"][None, :] & (karr[:, None] >= c["eval_idx"][None, :])
    ys_filled = _freeze_fill(c["ys"], fill,
                             jax.lax.stop_gradient(c["z"]))
    ys_out = ys_filled if unravel is None else \
        jax.vmap(jax.vmap(unravel))(ys_filled)

    overflow = c["eval_idx"] < n_eval
    status = _compose_status(c["failed"], c["uflow"], ~overflow,
                             jnp.ones((B,), bool))
    evals_per_trial = solver.stages + (
        1 if interpolate_ts and not solver.fsal else 0)
    stats = SolveStats(
        n_steps=jax.lax.stop_gradient(c["n_acc"]),
        n_trials=jnp.full((B,), budget, jnp.int32),
        nfe=jnp.full((B,), budget * evals_per_trial, jnp.int32),
        overflow=jax.lax.stop_gradient(overflow),
        status=jax.lax.stop_gradient(status),
    )
    return ys_out, stats


def odeint_naive_fixed(
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: PyTree = (),
    *,
    solver: Tableau,
    steps_per_interval: int = 8,
    use_pallas: bool = False,
) -> Tuple[PyTree, SolveStats]:
    """Naive fixed-grid: plain reverse-mode AD through the scan (stores all
    stage intermediates — O(N_f · N_t) memory, no recompute)."""
    return fixed_grid_solve(solver, f, z0, ts, _as_tuple(args),
                            steps_per_interval, use_pallas=use_pallas)
