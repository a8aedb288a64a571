"""Unified odeint front-end:  solver × gradient-method dispatch.

    ys, stats = odeint(f, z0, ts, args,
                       solver="dopri5",          # tableau name, or "alf"
                       grad_method="aca",        # aca | adjoint | naive | mali
                       rtol=1e-6, atol=1e-6,
                       max_steps=256,            # checkpoint capacity
                       max_trials=12,            # stepsize trials per step
                       steps_per_interval=8,     # fixed-grid solvers
                       trial_budget=None,        # naive-method tape bound
                       use_pallas=False,         # fused flat-state kernels
                       batch_axis=None,          # per-sample batched solve
                       checkpoint_segments=None, # O(K)-state ACA memory
                       interpolate_ts=False,     # dense-output eval reads
                       h0=None,                  # initial-stepsize override
                       on_failure="status",      # solve-health policy
                       mesh=None,                # shard batch over a Mesh
                       shard_rules=None)         # AxisRules override

``f(t, z, *args) -> dz/dt`` over arbitrary pytrees; ``ts`` strictly
monotone — ascending for a forward solve, or *descending* for a
reverse-time solve (internally solved as the time-negated ascending
problem, so every gradient method — including ACA's bit-exact
checkpoint replay — works unchanged); ``ys[k] = z(ts[k])`` with
``ys[0] = z0``.  Gradients flow to ``z0`` and ``args`` under every
method; the methods differ exactly as the paper's Table 1 describes,
plus the paper-family successor ``grad_method="mali"`` (reversible
asynchronous-leapfrog: O(1) state memory, exact reverse reconstruction;
pairs with ``solver="alf"`` — see ``odeint_mali.py`` and
``docs/method-selection.md``).

With ``batch_axis=a``, leaves of ``z0`` carry a batch dimension at axis
``a`` and ``f`` stays *per-sample*: each batch element is integrated on
its own adaptive grid (own stepsize controller, own accept/reject, own
checkpoint buffer) instead of one lockstep decision for the whole batch —
the semantics of ``jax.vmap`` over the unbatched solver, in one fused
loop.  ``args`` are shared across the batch (their gradient is summed).

With ``mesh=...`` on top of ``batch_axis``, the batched solve is
``shard_map``-ed over the mesh's data-parallel axes: each device
integrates its own batch shard with its own while_loop trip count (a
stiff straggler no longer stalls the whole batch), forward/backward
sweeps of every gradient method run shard-local, and the one
cross-device collective is the psum of the shared-``args`` cotangent
that ``shard_map``'s transpose inserts.  See ``docs/distributed.md``.

``odeint_dense`` solves once over [t0, t1] and returns a
``DenseSolution`` carrying every accepted step's interpolant
coefficients — evaluate it post hoc at arbitrary times.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import checkify

from .controller import ControllerConfig
from .integrate import (
    SolveStats,
    SolveStatus,
    _as_tuple,
    adaptive_while_solve,
)
from .odeint_aca import odeint_aca, odeint_aca_batched, odeint_aca_fixed
from .odeint_adjoint import (
    odeint_adjoint,
    odeint_adjoint_batched,
    odeint_adjoint_fixed,
)
from .odeint_mali import odeint_mali, odeint_mali_batched
from .odeint_naive import (
    odeint_naive,
    odeint_naive_batched,
    odeint_naive_fixed,
)
from .stepper import InterpCoeffs, interp_eval_aligned, maybe_flatten
from .tableaus import Tableau, get_tableau

PyTree = Any

GRAD_METHODS = ("aca", "adjoint", "naive", "mali")

ON_FAILURE_POLICIES = ("status", "warn", "raise")


def _apply_on_failure(ys, stats, on_failure: str):
    """Apply the solve-health policy to a finished solve.

    ``"status"`` is a no-op (callers read ``stats.status``); ``"warn"``
    emits a ``jax.debug.print`` line when any element failed (works
    under jit — the print fires at run time, off the hot path behind a
    ``lax.cond``); ``"raise"`` inserts a functionalized
    ``checkify.check`` — eager callers get an immediate exception,
    jitted callers must functionalize with ``checkify.checkify`` (see
    ``odeint_checked``, which does exactly that).
    """
    if on_failure == "status":
        return ys, stats
    any_bad = jnp.any(stats.status != SolveStatus.OK)
    if on_failure == "warn":
        jax.lax.cond(
            any_bad,
            lambda s: jax.debug.print(
                "odeint: solve-health failure, status={s} "
                "(see repro.core.SolveStatus.describe)", s=s),
            lambda s: None,
            stats.status)
        return ys, stats
    checkify.check(
        ~any_bad,
        "odeint: solve failed, status={s} "
        "(see repro.core.SolveStatus.describe)", s=stats.status)
    return ys, stats


def _is_alf(solver) -> bool:
    """True when ``solver`` names the reversible asynchronous-leapfrog
    pair integrator (the only pairing ``grad_method='mali'`` accepts —
    ALF is not an RK tableau)."""
    return (isinstance(solver, str)
            and solver.lower().replace("-", "_") == "alf")


def _ts_direction(ts: jnp.ndarray) -> int:
    """Validate the ``ts`` monotonicity contract; return the direction.

    Returns +1 for strictly ascending, -1 for strictly descending;
    raises ValueError for anything else (repeated times included) —
    unsorted input used to silently produce garbage.  Traced ``ts``
    (inside jit with ts as an argument) cannot be inspected and is
    assumed ascending — pass concrete eval times to use reverse-time
    solving.
    """
    if isinstance(ts, jax.core.Tracer):
        return 1
    d = np.diff(np.asarray(ts))
    if bool((d > 0).all()):
        return 1
    if bool((d < 0).all()):
        return -1
    raise ValueError(
        "ts must be strictly monotone: ascending (forward solve) or "
        "descending (reverse-time solve); got neither — sort your eval "
        "times (and deduplicate repeats) before calling odeint")


def _negate_time(f: Callable) -> Callable:
    """The time-negated vector field: solving dz/ds = -f(-s, z) forward
    over ascending s = -t is exactly the reverse-time solve over
    descending t."""
    def f_neg(s, z, *a):
        return jax.tree.map(jnp.negative, f(-s, z, *a))

    return f_neg


def odeint(
    f: Callable,
    z0: PyTree,
    ts,
    args: PyTree = (),
    *,
    solver: Optional[Union[str, Tableau]] = None,
    grad_method: str = "aca",
    rtol: float = 1e-6,
    atol: float = 1e-6,
    max_steps: int = 256,
    max_trials: int = 12,
    steps_per_interval: int = 8,
    trial_budget: Optional[int] = None,
    use_pallas: bool = False,
    batch_axis: Optional[int] = None,
    checkpoint_segments: Optional[Union[int, str]] = None,
    interpolate_ts: bool = False,
    h0: Optional[Any] = None,
    on_failure: str = "status",
    mesh: Optional[Any] = None,
    shard_rules: Optional[Any] = None,
) -> Tuple[PyTree, SolveStats]:
    """See module docstring for the solver × grad-method matrix.

    Solve health: adaptive solves guard every trial against non-finite
    states — a poisoned element freezes at its last accepted state
    (finite outputs, zeroed cotangents) and ``stats.status`` carries a
    per-solve (per-element under ``batch_axis``) ``SolveStatus`` code.
    ``on_failure`` picks the policy: ``"status"`` (default — report
    only, bit-identical hot path), ``"warn"`` (``jax.debug.print`` on
    failure), ``"raise"`` (a ``checkify.check``; eager calls raise
    immediately, jitted callers use ``odeint_checked``).  ``h0``
    overrides the automatic initial-stepsize heuristic of adaptive
    solvers (scalar, or (B,) under ``batch_axis``) — the
    ``solve_with_fallback`` retry ladder uses it to re-attempt a failed
    solve with a tighter first step.  See ``docs/robustness.md``.

    Adaptive-solver budgets: ``max_steps`` caps the number of *accepted*
    steps (it is also the checkpoint-buffer capacity, the paper's N_t
    bound — ``stats.overflow`` is set when the solve runs out before the
    last eval time); ``max_trials`` bounds the paper's inner stepsize
    search m, so the total ψ-trial budget of one solve is ``max_steps *
    max_trials``.  ``trial_budget`` (naive method only) overrides that
    product as the length of the differentiable solver tape: reverse-mode
    AD stores residuals for every budgeted trial, so it is *the* memory
    knob of the naive method.

    ``use_pallas=True`` enables the fused flat-state fast path: the
    state pytree is raveled once per solve and every ψ trial (stage
    increments, solution/error combine, scaled error norm) runs as
    fused Pallas kernels — always compiled on TPU, interpret-mode
    elsewhere (where ``repro.kernels.ops.set_interpret`` /
    REPRO_PALLAS_INTERPRET override the choice).  The fused step computes the same f32 arithmetic in the
    same accumulation order as the pytree path (bit-identical in the
    tested configurations; only the error-norm reduction is tiled, so a
    trial whose scaled error sits within ~1 ulp of the accept threshold
    could in principle decide differently) and gradients flow through
    all four methods.  States whose leaves mix dtypes (or are not
    inexact) silently fall back to the pytree path.

    ``batch_axis=a`` enables the per-sample batched mode: every leaf of
    ``z0`` carries a batch dimension at axis ``a`` (one shared batch
    size B) while ``f`` remains the per-sample vector field.  Adaptive
    solvers then give every element its own stepsize-controller state,
    accept/reject mask and checkpoint row inside one fused while_loop —
    matching ``jax.vmap`` of the unbatched solver instead of degrading
    the stepsize search to one lockstep decision — and all four
    gradient methods replay/re-integrate/invert per element.  Outputs gain the
    leading time axis as usual: ``ys[k]`` has the shape of the batched
    ``z0`` (batch at axis ``a`` of each state leaf), and ``stats``
    fields become (B,) per-element counters; an element that has landed
    on its last ``ts[k]`` stops accumulating f-evals while stragglers
    finish.  Composes with ``use_pallas`` (batched fused kernels with
    per-row error norms); fixed-grid solvers share one exact grid, so
    batching is lossless there.

    Under ``batch_axis``, ``rtol``/``atol`` may additionally be (B,)
    arrays — **per-element tolerances**: every batch row's stepsize
    controller (initial-stepsize heuristic, per-trial error norm,
    accept/reject) targets that row's own (rtol, atol), so tight- and
    loose-tolerance problems share one fused solve without lockstep
    waste — the per-request quality-of-service knob of the serving
    engine (``repro.serve.NodeServeEngine``).  A row at tolerance τ is
    **bitwise identical** to the same row in an all-τ batch (rows never
    interact; the loaded per-row tolerance computes the same f32
    arithmetic as the baked scalar), on both the pytree and the fused
    Pallas path.  Requires an adaptive solver (or ``mali``); not yet
    composable with ``mesh`` (the tolerance rows would replicate, not
    shard).  Tolerances never carry gradient.  See ``docs/serving.md``.

    ``checkpoint_segments=K`` (adaptive ACA only) bounds the trajectory-
    checkpoint state memory: instead of every accepted state (O(N_f ·
    dim)), the forward stores K coarse snapshots plus the full *scalar*
    grid, and the ACA backward re-integrates each segment from its
    snapshot with the saved stepsizes before replaying it in reverse —
    memory O((K + N_f/K) · dim) at ~1 extra ψ per accepted step, with
    gradients **bit-identical** to the full buffer (the replay re-takes
    the exact saved steps; there is no re-search).  ``"auto"`` picks the
    memory-optimal K = ⌈√max_steps⌉.  Composes with ``use_pallas`` and
    ``batch_axis``; raises for other grad methods (they keep no state
    checkpoints to bound) and for fixed-grid solvers.  See
    ``docs/memory.md``.

    ``interpolate_ts=True`` (adaptive solvers only) decouples the eval
    grid from the step grid: the controller advances on its *natural*
    accepted steps, clamped only to the final time, and interior
    ``ts[k]`` are read off each accepted step's local interpolant
    (4th-order for Dopri5 via its ``b_mid`` dense output, cubic Hermite
    otherwise) — dense eval grids stop inflating the step count.
    ``ys[0]``/``ys[-1]`` stay exact solver states; interior outputs
    carry the interpolant's O(h⁴) error on top of the solve tolerance.
    Gradients flow through the interpolants under all three methods
    (ACA replays interval + interpolant exactly).  Default off: the
    forced-landing trajectories are bit-compatible with earlier
    releases.  Composes with ``batch_axis``, ``use_pallas``,
    ``checkpoint_segments`` and descending ``ts``.

    ``grad_method="mali"`` (paired with ``solver="alf"`` — the default
    when ``solver`` is omitted) integrates with the reversible
    asynchronous-leapfrog pair stepper and reconstructs the trajectory
    in the backward sweep by *inverting* accepted steps from the
    terminal state — bitwise, via the fixed-point lattice pair of
    ``stepper.alf_step`` — so no state checkpoint buffer exists at all:
    state memory is O(dim) regardless of step count (only the cheap
    scalar t/h grid is kept).  One field evaluation per ψ trial, 2nd
    order.  Composes with ``batch_axis``, ``use_pallas`` and descending
    ``ts``; rejects ``checkpoint_segments`` (nothing to segment) and
    ``interpolate_ts``.  See ``docs/method-selection.md``.

    Descending ``ts`` runs the whole solve in reverse time by negating
    the clock (``dz/ds = -f(-s, z)`` over ascending ``s = -t``): the
    forward trajectory is bit-identical to the negated-time ascending
    solve, and all gradient methods apply unchanged.

    ``mesh=...`` (requires ``batch_axis``) shards the batch over the
    mesh's data-parallel axes via ``shard_map``: ``z0`` (and a (B,)
    ``h0``) split along the batch dim, ``ts``/``args`` replicate, and
    each device runs the per-sample batched engine on its shard with an
    *independent* while_loop trip count — the forward trajectory, the
    per-element ``stats`` and the z0-cotangents are exactly the
    unsharded batched solve's, shard-local end to end, for all four
    gradient methods; the shared-``args`` gradient additionally crosses
    devices once (psum of per-shard partial sums, inserted by
    ``shard_map``'s transpose — associativity reordering can move
    args-grads by ~1 ulp under naive/mali).  The mesh's batch axes come
    from ``shard_rules`` (default ``DEFAULT_TRAIN_RULES``: "batch" →
    ("pod", "data") ∩ mesh axes); the batch size must divide evenly by
    the shard count.  ``repro.distributed.shard_mesh()`` builds the
    flat 1-D data mesh over all devices.  See ``docs/distributed.md``.
    """
    if grad_method not in GRAD_METHODS:
        raise ValueError(f"grad_method must be one of {GRAD_METHODS}")
    if on_failure not in ON_FAILURE_POLICIES:
        raise ValueError(
            f"on_failure must be one of {ON_FAILURE_POLICIES}; got "
            f"{on_failure!r}")
    if solver is None:
        # mali integrates with the reversible ALF pair stepper; every
        # other method defaults to the paper's Dopri5
        solver = "alf" if grad_method == "mali" else "dopri5"
    if grad_method == "mali" and not _is_alf(solver):
        name = solver if isinstance(solver, str) else solver.name
        raise ValueError(
            f"grad_method='mali' integrates with the reversible "
            f"asynchronous-leapfrog pair stepper (solver='alf'), not an "
            f"RK tableau (got {name!r}); drop the solver argument or "
            "pass solver='alf'")
    if _is_alf(solver) and grad_method != "mali":
        raise ValueError(
            f"solver='alf' is the reversible pair integrator whose "
            f"inverse IS the gradient method — it pairs only with "
            f"grad_method='mali' (got {grad_method!r})")
    mali = grad_method == "mali"
    tab = None if mali else (
        get_tableau(solver) if isinstance(solver, str) else solver)
    ts = jnp.asarray(ts)
    if ts.ndim != 1 or ts.shape[0] < 2:
        raise ValueError("ts must be a 1D array of at least 2 times")
    if checkpoint_segments is not None and mali:
        raise ValueError(
            "checkpoint_segments is meaningless with grad_method='mali': "
            "MALI keeps no state checkpoints at all — its backward sweep "
            "reconstructs every state by inverting steps from the "
            "terminal pair in O(1) memory; drop checkpoint_segments")
    if checkpoint_segments is not None and (
            grad_method != "aca" or not tab.adaptive):
        raise ValueError(
            "checkpoint_segments requires grad_method='aca' with an "
            f"adaptive solver (got {grad_method!r} / {tab.name!r}): only "
            "the ACA trajectory checkpoint stores per-step states to "
            "segment")
    if interpolate_ts and mali:
        raise ValueError(
            "interpolate_ts is not supported with grad_method='mali': "
            "the reversible backward sweep reconstructs exact step "
            "landings only (no interpolant cotangent routing); use "
            "grad_method='aca' for dense-output gradients")
    if interpolate_ts and not tab.adaptive:
        raise ValueError(
            "interpolate_ts requires an adaptive solver (got "
            f"{tab.name!r}): fixed grids land on every eval time by "
            "construction, there is no stepsize search to relieve")
    if h0 is not None and not mali and not tab.adaptive:
        raise ValueError(
            f"h0 overrides the adaptive initial-stepsize heuristic; "
            f"fixed-grid solver {tab.name!r} has no stepsize controller "
            "— use steps_per_interval to refine its grid instead")
    if mesh is not None and batch_axis is None:
        raise ValueError(
            "mesh requires batch_axis: sharding distributes the "
            "per-sample batched solve over the mesh's data axes, so the "
            "state must carry a batch dimension — pass batch_axis=a "
            "(or drop mesh for a single-sample solve)")
    row_tol = jnp.ndim(rtol) > 0 or jnp.ndim(atol) > 0
    if row_tol:
        if batch_axis is None:
            raise ValueError(
                "array rtol/atol are *per-element* tolerances and "
                "require batch_axis: each entry pairs with one batch "
                "row's stepsize controller — pass batch_axis=a, or a "
                "scalar tolerance for a single-sample solve")
        if mesh is not None:
            raise ValueError(
                "per-element rtol/atol do not compose with mesh yet: "
                "the (B,) tolerance rows are closure-captured by the "
                "engine custom_vjp and would replicate — not shard — "
                "across devices inside shard_map, silently mispairing "
                "tolerances with batch rows; drop mesh or use a scalar "
                "tolerance")
        if not mali and not tab.adaptive:
            raise ValueError(
                f"per-element rtol/atol require an adaptive solver (got "
                f"{tab.name!r}): fixed grids have no error control to "
                "point a tolerance at — use steps_per_interval instead")
        rtol = jnp.asarray(rtol, jnp.float32)
        atol = jnp.asarray(atol, jnp.float32)
        if rtol.ndim > 1 or atol.ndim > 1:
            raise ValueError(
                "per-element rtol/atol must be rank-1 (one tolerance "
                f"per batch row); got shapes {jnp.shape(rtol)} / "
                f"{jnp.shape(atol)}")
    if _ts_direction(ts) < 0:
        # reverse time: solve the time-negated problem over ascending -ts
        f, ts = _negate_time(f), -ts

    cfg = ControllerConfig(max_steps=max_steps, max_trials=max_trials)
    if h0 is not None:
        h0 = jnp.asarray(h0, ts.dtype)

    if batch_axis is not None:
        out = _odeint_batched(
            f, z0, ts, args, tab=tab, grad_method=grad_method,
            batch_axis=batch_axis, rtol=rtol, atol=atol, cfg=cfg,
            steps_per_interval=steps_per_interval,
            trial_budget=trial_budget, use_pallas=use_pallas,
            checkpoint_segments=checkpoint_segments,
            interpolate_ts=interpolate_ts, h0=h0,
            mesh=mesh, shard_rules=shard_rules)
    elif mali:
        out = odeint_mali(f, z0, ts, args, rtol=rtol, atol=atol,
                          cfg=cfg, h0=h0, use_pallas=use_pallas)
    elif tab.adaptive:
        if grad_method == "aca":
            out = odeint_aca(f, z0, ts, args, solver=tab, rtol=rtol,
                             atol=atol, cfg=cfg, h0=h0,
                             use_pallas=use_pallas,
                             checkpoint_segments=checkpoint_segments,
                             interpolate_ts=interpolate_ts)
        elif grad_method == "adjoint":
            out = odeint_adjoint(f, z0, ts, args, solver=tab, rtol=rtol,
                                 atol=atol, cfg=cfg, h0=h0,
                                 use_pallas=use_pallas,
                                 interpolate_ts=interpolate_ts)
        else:
            out = odeint_naive(f, z0, ts, args, solver=tab, rtol=rtol,
                               atol=atol, cfg=cfg, h0=h0,
                               trial_budget=trial_budget,
                               use_pallas=use_pallas,
                               interpolate_ts=interpolate_ts)
    elif grad_method == "aca":
        out = odeint_aca_fixed(f, z0, ts, args, solver=tab,
                               steps_per_interval=steps_per_interval,
                               use_pallas=use_pallas)
    elif grad_method == "adjoint":
        out = odeint_adjoint_fixed(f, z0, ts, args, solver=tab,
                                   steps_per_interval=steps_per_interval,
                                   use_pallas=use_pallas)
    else:
        out = odeint_naive_fixed(f, z0, ts, args, solver=tab,
                                 steps_per_interval=steps_per_interval,
                                 use_pallas=use_pallas)
    return _apply_on_failure(out[0], out[1], on_failure)


def _odeint_batched(
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: PyTree,
    *,
    tab: Tableau,
    grad_method: str,
    batch_axis: int,
    rtol: float,
    atol: float,
    cfg: ControllerConfig,
    steps_per_interval: int,
    trial_budget: Optional[int],
    use_pallas: bool,
    checkpoint_segments: Optional[Union[int, str]] = None,
    interpolate_ts: bool = False,
    h0: Optional[jnp.ndarray] = None,
    mesh: Optional[Any] = None,
    shard_rules: Optional[Any] = None,
) -> Tuple[PyTree, SolveStats]:
    """Batched dispatch behind ``odeint(..., batch_axis=a)``.

    Normalizes the batch dim to axis 0, routes adaptive tableaus to the
    per-sample batched solvers and fixed grids to the (lossless) shared
    grid with a vmapped field, then restores the caller's batch axis in
    ``ys`` (which sits one axis deeper under the leading time axis).
    With ``mesh``, the whole dispatch runs inside one ``shard_map`` over
    the mesh's batch-partition axes — each shard solves its local batch
    rows independently (own while_loop trip counts, shard-local
    backward sweeps); only the shared-``args`` cotangent crosses
    devices, via the psum ``shard_map``'s transpose inserts for
    replicated inputs.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(z0)
    if not flat:
        raise ValueError("batch_axis requires a non-empty state")
    for path, leaf in flat:
        if jnp.ndim(leaf) == 0:
            raise ValueError(
                f"batch_axis={batch_axis} requires every state leaf to "
                f"carry a batch dimension, but leaf "
                f"{jax.tree_util.keystr(path) or '<root>'} is rank-0 "
                "(a scalar has no axis to batch over)")
    leaves = [leaf for _, leaf in flat]
    # normalize per leaf: leaves may have different ranks, and a negative
    # axis must resolve before the != 0 checks and the ys restore below
    axes = jax.tree.map(lambda l: batch_axis % l.ndim, z0)
    sizes = {l.shape[a] for l, a in zip(leaves, jax.tree.leaves(axes))}
    if len(sizes) != 1:
        raise ValueError(
            f"all state leaves must share one batch size at axis "
            f"{batch_axis}; got {sorted(sizes)}")
    B = sizes.pop()

    for tname, tol in (("rtol", rtol), ("atol", atol)):
        if jnp.ndim(tol) == 1 and jnp.shape(tol)[0] not in (1, B):
            raise ValueError(
                f"per-element {tname} must carry one entry per batch row "
                f"(B={B}) or a single broadcastable entry; got shape "
                f"{jnp.shape(tol)}")

    z0 = jax.tree.map(
        lambda l, a: jnp.moveaxis(l, a, 0) if a else l, z0, axes)

    if mesh is not None:
        # jax 0.4.x shard_map cannot carry rank-0 custom_vjp residuals
        # across the shard boundary (grad dies with a _SpecError), and
        # the engines save ``args`` verbatim in their residuals.  So
        # promote scalar args leaves to shape (1,) for the engines and
        # strip the axis again at each field call — user field code
        # still sees true scalars, and the promoting reshape sits
        # outside the shard_map so args cotangents come back rank-0.
        mask = jax.tree.map(lambda x: jnp.ndim(x) == 0, args)
        if any(jax.tree.leaves(mask)):
            args = jax.tree.map(
                lambda x, s: jnp.reshape(jnp.asarray(x), (1,)) if s
                else x, args, mask)
            tup_mask = _as_tuple(mask)
            inner_f = f

            def f(t, z, *a):
                a = tuple(
                    jax.tree.map(
                        lambda x, s: jnp.reshape(x, ()) if s else x,
                        ai, mi)
                    for ai, mi in zip(a, tup_mask))
                return inner_f(t, z, *a)

    def dispatch(z0, ts, args, h0):
        # batch leads axis 0 of every z0 leaf here; under a mesh this
        # body runs per shard on the shard-local rows
        if grad_method == "mali":  # tab is None: ALF pair integrator
            ys, stats = odeint_mali_batched(
                f, z0, ts, args, rtol=rtol, atol=atol, cfg=cfg, h0=h0,
                use_pallas=use_pallas)
        elif tab.adaptive:
            if grad_method == "aca":
                ys, stats = odeint_aca_batched(
                    f, z0, ts, args, solver=tab, rtol=rtol, atol=atol,
                    cfg=cfg, h0=h0, use_pallas=use_pallas,
                    checkpoint_segments=checkpoint_segments,
                    interpolate_ts=interpolate_ts)
            elif grad_method == "adjoint":
                ys, stats = odeint_adjoint_batched(
                    f, z0, ts, args, solver=tab, rtol=rtol, atol=atol,
                    cfg=cfg, h0=h0, use_pallas=use_pallas,
                    interpolate_ts=interpolate_ts)
            else:
                ys, stats = odeint_naive_batched(
                    f, z0, ts, args, solver=tab, rtol=rtol, atol=atol,
                    cfg=cfg, h0=h0, trial_budget=trial_budget,
                    use_pallas=use_pallas,
                    interpolate_ts=interpolate_ts)
        else:
            # fixed grids are identical for every element — lockstep IS
            # the per-sample grid; vmap the field over the batched state
            # and reuse the unbatched front-ends unchanged
            fb = lambda t, z, *a: jax.vmap(
                lambda zi: f(t, zi, *a), in_axes=0)(z)
            if grad_method == "aca":
                ys, stats = odeint_aca_fixed(
                    fb, z0, ts, args, solver=tab,
                    steps_per_interval=steps_per_interval,
                    use_pallas=use_pallas)
            elif grad_method == "adjoint":
                ys, stats = odeint_adjoint_fixed(
                    fb, z0, ts, args, solver=tab,
                    steps_per_interval=steps_per_interval,
                    use_pallas=use_pallas)
            else:
                ys, stats = odeint_naive_fixed(
                    fb, z0, ts, args, solver=tab,
                    steps_per_interval=steps_per_interval,
                    use_pallas=use_pallas)
            b = jax.tree.leaves(z0)[0].shape[0]  # shard-local under mesh
            stats = jax.tree.map(lambda s: jnp.broadcast_to(s, (b,)), stats)
        return ys, stats

    if mesh is None:
        ys, stats = dispatch(z0, ts, args, h0)
    else:
        ys, stats = _shard_map_solve(
            dispatch, mesh, shard_rules, z0, ts, args, h0, B)

    # ys leaves are (n_eval, B, ...): the batch dim sits one axis deeper
    # than it did in each z0 leaf, under the leading time axis
    ys = jax.tree.map(
        lambda l, a: jnp.moveaxis(l, 1, a + 1) if a else l, ys, axes)
    return ys, stats


def _shard_map_solve(dispatch, mesh, shard_rules, z0, ts, args, h0, B):
    """Wrap the batch-at-axis-0 dispatch in one ``shard_map``.

    Specs: ``z0`` (and a per-element ``h0``) split along dim 0 over the
    mesh's batch-partition axes; ``ts``/``args`` replicate; ``ys``
    leaves come back split along dim 1 (batch under the time axis) and
    ``stats`` fields along dim 0.  Replication checking is off (see
    ``shard_map_compat``) because the solver engines use ``custom_vjp``
    internally; the replicated-args cotangent psum is inserted by
    ``shard_map``'s transpose rule, so no collective appears in this
    forward code at all.
    """
    from jax.sharding import PartitionSpec

    from ..distributed.sharding import batch_partition_axes, \
        shard_map_compat

    axes = batch_partition_axes(mesh, shard_rules)
    if not axes:
        raise ValueError(
            f"mesh {tuple(mesh.shape.items())} has no data-parallel axis "
            "to shard the batch over (the sharding rules map 'batch' to "
            f"{('pod', 'data')}, none of which the mesh carries) — add a "
            "'data' axis, use repro.distributed.shard_mesh(), or pass "
            "shard_rules mapping 'batch' onto one of this mesh's axes")
    n_shard = 1
    for a in axes:
        n_shard *= mesh.shape[a]
    if B % n_shard:
        raise ValueError(
            f"batch size {B} does not divide evenly over the mesh's "
            f"{n_shard} batch shard(s) (axes {axes} of mesh "
            f"{tuple(mesh.shape.items())}): pad the batch to a multiple "
            f"of {n_shard} or drop devices from the mesh")
    dspec = axes[0] if len(axes) == 1 else axes
    bspec = PartitionSpec(dspec)   # batch-leading arrays: split dim 0
    rspec = PartitionSpec()        # replicated
    h0_spec = rspec if (h0 is None or jnp.ndim(h0) == 0) else bspec
    sharded = shard_map_compat(
        dispatch, mesh=mesh,
        in_specs=(bspec, rspec, rspec, h0_spec),
        out_specs=(PartitionSpec(None, dspec), bspec))
    return sharded(z0, ts, args, h0)


def _time_dtype(*times) -> jnp.dtype:
    """Float dtype for a time grid built from scalars: explicit dtypes
    win; weak Python floats resolve to the default float dtype, so
    ``JAX_ENABLE_X64`` solves get float64 endpoints instead of a
    silently-truncating hardcoded float32."""
    tdt = jnp.result_type(*times)
    if not jnp.issubdtype(tdt, jnp.floating):
        tdt = jnp.result_type(float)
    return tdt


def odeint_final(
    f: Callable,
    z0: PyTree,
    t0: float,
    t1: float,
    args: PyTree = (),
    **kw,
) -> Tuple[PyTree, SolveStats]:
    """Convenience: integrate [t0, t1], return only z(t1) (NODE block use).

    Accepts every ``odeint`` keyword, including ``batch_axis`` — the
    returned z(t1) then keeps the batch dimension where ``z0`` had it.
    ``t0 > t1`` runs the solve in reverse time (descending ``ts``).
    """
    ts = jnp.asarray([t0, t1], _time_dtype(t0, t1))
    ys, stats = odeint(f, z0, ts, args, **kw)
    return jax.tree.map(lambda y: y[-1], ys), stats


def odeint_checked(
    f: Callable,
    z0: PyTree,
    ts,
    args: PyTree = (),
    **kw,
) -> Tuple[PyTree, SolveStats]:
    """``odeint`` that *raises* on solve failure instead of returning a
    status code.

    Functionalizes ``odeint(..., on_failure="raise")`` with
    ``jax.experimental.checkify`` and throws the collected error on the
    host: a non-finite state, stepsize underflow, or budget exhaustion
    surfaces as ``checkify.JaxRuntimeError`` naming the failing status
    code(s).  Accepts every ``odeint`` keyword except ``on_failure``.

    Call it *outside* jit (the throw needs a concrete error value).  To
    keep the check inside your own jitted function, call
    ``odeint(..., on_failure="raise")`` there and wrap the whole
    function with ``checkify.checkify`` yourself.
    """
    kw.pop("on_failure", None)
    ts = jnp.asarray(ts)  # closed over: keeps reverse-time ts concrete

    def run(z0, args):
        return odeint(f, z0, ts, args, on_failure="raise", **kw)

    err, out = checkify.checkify(run, errors=checkify.user_checks)(
        z0, args)
    err.throw()
    return out


def default_fallback_ladder(ts, *, rtol: float = 1e-6,
                            atol: float = 1e-6) -> list:
    """The retry rungs ``solve_with_fallback`` tries after a failed
    solve, mildest first.

    Each rung is a dict of ``odeint`` keyword overrides (plus a
    ``"note"`` for the report): (1) tighten the initial step to
    span/1024 — recovers solves whose first trial overflowed before the
    controller found the stiff scale; (2) loosen rtol/atol 100× —
    trades accuracy for stability when the tolerance is unreachable;
    (3) drop to the lower-order ``bosh3`` pair (smaller stages, wider
    stability margin per unit error) with ACA gradients; (4) last
    resort: a fixed-grid ``rk4`` solve with a fine 64-step grid — no
    stepsize search left to fail, only non-finite states can remain.
    """
    span = abs(float(ts[-1]) - float(ts[0]))
    return [
        {"note": "tighten h0", "h0": span / 1024.0},
        {"note": "loosen tolerances 100x",
         "rtol": rtol * 100.0, "atol": atol * 100.0},
        {"note": "fall back to bosh3/aca",
         "solver": "bosh3", "grad_method": "aca"},
        {"note": "fixed rk4 grid", "solver": "rk4", "grad_method": "aca",
         "steps_per_interval": 64},
    ]


# odeint keywords that only adaptive solvers understand — dropped from a
# rung that falls back to a fixed-grid tableau
_ADAPTIVE_ONLY_KW = ("h0", "checkpoint_segments", "interpolate_ts",
                     "trial_budget")


def solve_with_fallback(
    f: Callable,
    z0: PyTree,
    ts,
    args: PyTree = (),
    *,
    ladder: Optional[list] = None,
    **kw,
) -> Tuple[PyTree, SolveStats, list]:
    """Host-level retry ladder around ``odeint``: re-attempt a failed
    solve under progressively more conservative configurations.

    Runs ``odeint(f, z0, ts, args, **kw)`` and reads ``stats.status``
    on the host; when any element is unhealthy, walks the ``ladder`` of
    keyword-override rungs (default: ``default_fallback_ladder`` —
    tighten h0, loosen tolerances, drop to bosh3, fixed rk4) until an
    attempt comes back all-OK with finite outputs.  Returns
    ``(ys, stats, report)`` where ``report`` is one dict per attempt
    (note, overrides, status codes, ok flag); if no rung recovers, the
    *original* attempt's (frozen, finite) outputs are returned and
    every report entry has ``ok=False``.

    Serving-layer tool: each rung is a fresh trace/compile and the
    status read is a host sync, so this is **not jittable** — call it
    from request handlers, not from inside a training step (there, use
    ``on_failure="status"`` + the train-loop skip-step guard).
    """
    kw.pop("on_failure", None)
    ts = jnp.asarray(ts)
    if ladder is None:
        ladder = default_fallback_ladder(
            ts, rtol=kw.get("rtol", 1e-6), atol=kw.get("atol", 1e-6))

    report: list = []
    first = None
    for rung in [{"note": "original"}] + list(ladder):
        over = {k: v for k, v in rung.items() if k != "note"}
        akw = {**kw, **over}
        solver = akw.get("solver")
        if solver is not None and not _is_alf(solver):
            tabl = get_tableau(solver) if isinstance(solver, str) \
                else solver
            if not tabl.adaptive:
                for k in _ADAPTIVE_ONLY_KW:
                    akw.pop(k, None)
        entry = {"note": rung.get("note", "attempt"), "overrides": over}
        try:
            ys, stats = odeint(f, z0, ts, args, **akw)
        except Exception as e:  # rung invalid for this configuration
            entry.update(error=repr(e), ok=False)
            report.append(entry)
            continue
        status = np.asarray(jax.device_get(stats.status))
        finite = all(
            bool(np.isfinite(np.asarray(leaf)).all())
            for leaf in jax.tree.leaves(jax.device_get(ys)))
        ok = bool((status == SolveStatus.OK).all()) and finite
        entry.update(
            status=status.tolist() if status.ndim else int(status),
            ok=ok)
        report.append(entry)
        if first is None:
            first = (ys, stats)
        if ok:
            return ys, stats, report
    if first is None:  # every attempt raised — nothing to return
        raise RuntimeError(
            f"solve_with_fallback: every attempt errored: {report}")
    ys, stats = first
    return ys, stats, report


class DenseSolution(NamedTuple):
    """A continuously-evaluable ODE solution (``odeint_dense``).

    Carries every accepted step's interpolant: ``t``/``h`` the interval
    start times and stepsizes *in internal (ascending) time*, ``coeffs``
    the fitted polynomial coefficients (``stepper.InterpCoeffs``; leaves
    lead with the step axis), ``n`` the number of valid steps and
    ``sign`` (+1/-1) mapping user time to internal time (-1 for a
    reverse-time solve over t1 < t0).  Slots past ``n`` are garbage.

    ``evaluate(t)`` interpolates at arbitrary times inside [t0, t1]
    (times outside clamp to the nearest endpoint); it is a pytree of
    plain jnp gathers + polynomial evaluation, so it jits/vmaps freely.
    The producing solve runs inside a ``lax.while_loop`` — treat the
    solution as *forward-only* (no gradients to z0/args through it; use
    ``odeint(..., interpolate_ts=True)`` when you need gradients at
    fixed eval times).
    """
    t: jnp.ndarray            # (max_steps,) interval start times
    h: jnp.ndarray            # (max_steps,) accepted stepsizes
    coeffs: Any               # InterpCoeffs, leaves (max_steps, ...)
    n: jnp.ndarray            # valid step count
    sign: jnp.ndarray         # +1.0 / -1.0 (user time = sign * internal)

    def evaluate(self, t) -> PyTree:
        """State at time(s) ``t`` — scalar or any-shape array; returned
        leaves lead with ``t``'s shape."""
        tdt = self.t.dtype
        tq = jnp.asarray(t, tdt) * self.sign
        qshape = tq.shape
        tq = tq.reshape(-1)
        # invalid slots -> +inf keeps the knot array sorted for the
        # bisection; clip lands every query on a valid interval
        slots = jnp.arange(self.t.shape[0])
        knots = jnp.where(slots < self.n, self.t,
                          jnp.asarray(jnp.inf, tdt))
        idx = jnp.clip(jnp.searchsorted(knots, tq, side="right") - 1,
                       0, jnp.maximum(self.n - 1, 0))
        t_i, h_i = self.t[idx], self.h[idx]
        tiny = jnp.asarray(jnp.finfo(tdt).eps, tdt)
        theta = jnp.clip((tq - t_i) / jnp.maximum(h_i, tiny), 0.0, 1.0)
        coeffs_q = jax.tree.map(lambda b: b[idx], self.coeffs)
        vals = interp_eval_aligned(InterpCoeffs(*coeffs_q), theta)
        return jax.tree.map(
            lambda v: v.reshape(qshape + v.shape[1:]), vals)


def odeint_dense(
    f: Callable,
    z0: PyTree,
    t0: float,
    t1: float,
    args: PyTree = (),
    *,
    solver: Union[str, Tableau] = "dopri5",
    rtol: float = 1e-6,
    atol: float = 1e-6,
    max_steps: int = 256,
    max_trials: int = 12,
    use_pallas: bool = False,
) -> Tuple[DenseSolution, SolveStats]:
    """Solve dz/dt = f(t, z, *args) over [t0, t1] once and return a
    ``DenseSolution`` for post-hoc evaluation at arbitrary times.

    The adaptive controller advances on its natural grid (no interior
    landings) and every accepted step's interpolant coefficients are
    stored — memory O(N_f · dim · 5) — so ``sol.evaluate(t)`` costs one
    bisection plus one polynomial evaluation per query, with the same
    accuracy contract as ``interpolate_ts``.  ``t1 < t0`` solves in
    reverse time; ``evaluate`` then takes user (descending-side) times.
    Forward/inference only — the producing while_loop is not
    reverse-differentiable.  ``stats.overflow`` set means the solve ran
    out of ``max_steps`` before reaching t1 (the solution is then only
    valid up to the last accepted step).
    """
    tab = get_tableau(solver) if isinstance(solver, str) else solver
    if not tab.adaptive:
        raise ValueError(
            f"odeint_dense requires an adaptive solver (got {tab.name!r})")
    tdt = _time_dtype(t0, t1)
    ts = jnp.asarray([t0, t1], tdt)
    if _ts_direction(ts) < 0:
        f, ts = _negate_time(f), -ts
        sign = jnp.asarray(-1.0, tdt)
    else:
        sign = jnp.asarray(1.0, tdt)

    cfg = ControllerConfig(max_steps=max_steps, max_trials=max_trials)
    f, z0, unravel, use_pallas = maybe_flatten(f, z0, use_pallas)
    _, ckpts, stats = adaptive_while_solve(
        tab, f, z0, ts, _as_tuple(args), rtol, atol, cfg,
        use_pallas=use_pallas, store_coeffs=True)
    coeffs = ckpts.coeffs
    if unravel is not None:
        coeffs = InterpCoeffs(*(jax.vmap(unravel)(c) for c in coeffs))
    sol = DenseSolution(t=ckpts.t, h=ckpts.h, coeffs=coeffs, n=ckpts.n,
                        sign=sign)
    return sol, stats
