"""Forward integration engines shared by every gradient method.

Two engines over the same Runge-Kutta stepper:

* ``adaptive_while_solve`` — ``lax.while_loop`` with a flattened
  trial/accept loop (the paper's Algorithm 1 with the inner stepsize search
  and outer time advance fused into one loop).  Dynamic trip count, *not*
  reverse-differentiable — used by ACA forward (with trajectory
  checkpoints), by the adjoint method's forward and backward solves, and
  for inference.  With ``use_pallas=True`` the trial step and its error
  norm run as fused flat-state Pallas kernels over the raveled state (see
  ``stepper.py``); the loop logic is identical.  Accepted discretization
  points (t_i, h_i, z_i) are written into a fixed-capacity buffer: the
  paper's trajectory checkpoint.  With ``checkpoint_segments=K`` the
  state buffer shrinks to K coarse snapshots (one every
  ``ceil(max_steps / K)`` accepted steps) while the scalar grid still
  records every step — the memory-bounded mode the segmented ACA
  backward sweep re-integrates from (``docs/memory.md``).

* ``batched_adaptive_while_solve`` — the per-sample batched engine behind
  ``odeint(..., batch_axis=0)``.  One fused ``lax.while_loop`` advances
  all live batch elements each iteration, but every element carries its
  *own* controller state (stepsize, PI memory, trial counter), its own
  accept/reject decision and its own ``Checkpoints`` row — Algorithm 1's
  stepsize search runs per trajectory, not in lockstep.  Rejected and
  finished elements are frozen with ``jnp.where`` masking (and h = 0
  through the stepper, an exact identity), so an element that has landed
  on its last ``ts[k]`` stops contributing f-evals to its ``SolveStats``
  and its buffers stay bit-stable while stragglers finish.  The loop
  terminates when *all* elements are done.  From a batch of twice
  ``COMPACT_FLOOR`` rows up, it runs in halving phases: each time the
  live rows fit half the block, they are gathered into a block of half
  the size, so finished rows stop riding along.

* ``fixed_grid_solve`` — ``lax.scan`` over a precomputed grid.  Fully
  differentiable (this is also the "naive" method for fixed-step solvers).

* ``mali_adaptive_solve`` / ``batched_mali_adaptive_solve`` — the
  reversible asynchronous-leapfrog engines behind ``odeint(...,
  grad_method="mali")``.  Same trial/accept loop shape as the RK
  engines, but the carried state is the integer-lattice pair (z, v) of
  ``stepper.alf_step`` and **no state checkpoint buffer exists at
  all**: only the scalar grid (t_i, h_i, out_idx_i) is recorded — the
  ``MaliGrid`` — because the backward sweep re-derives every accepted
  state by *inverting* steps from the terminal pair (bitwise, see the
  ALF section of ``stepper.py``).  State memory is O(dim), independent
  of the accepted-step count.

All engines integrate through a sorted array of evaluation times ``ts``
(the solver is forced to land exactly on each ``ts[k]``), supporting
latent-ODE style multi-time outputs.  States are arbitrary pytrees.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .controller import ControllerConfig, initial_stepsize, propose_stepsize
from .stepper import (
    ALF_ORDER,
    InterpCoeffs,
    alf_lattice_exponent,
    alf_lattice_exponent_batched,
    alf_step,
    alf_step_batched,
    error_ratio,
    field_eval,
    interp_eval,
    interp_fit,
    lattice_decode,
    lattice_encode,
    maybe_flatten,
    rk_step,
    rk_step_batched,
)
from .tableaus import Tableau

PyTree = Any

# named scope of the adaptive loops' trajectory-checkpoint writes (and of
# the segmented ACA backward's replay-buffer writes): their ops carry it
# in their HLO op_name
CKPT_WRITE_SCOPE = "ode_ckpt_write"


def _as_tuple(args) -> Tuple:
    """Normalize an ``args`` pytree to the *args tuple ``f`` receives —
    the one rule shared by every odeint entry point."""
    return args if isinstance(args, tuple) else (args,)


class SolveStatus:
    """Structured health codes for a solve (``SolveStats.status``).

    Int codes, ordered by severity (0 = healthy).  Scalar for an
    unbatched solve, per-element (B,) int32 for ``batch_axis`` solves:

    * ``OK`` — every requested eval time was reached normally.
    * ``NONFINITE_STATE`` — a trial step produced a non-finite state (or
      error norm) even at the minimum stepsize.  The solve *froze* the
      affected element at its last accepted state instead of integrating
      garbage: outputs at un-reached eval times repeat that last-good
      state, and the backward sweeps zero the element's cotangents.
    * ``STEPSIZE_UNDERFLOW`` — at least one forced-minimum step (h railed
      at ``h_min``) was accepted while still failing the error test; the
      solve completed but local accuracy is not guaranteed.
    * ``TRIAL_BUDGET_EXHAUSTED`` — the global ψ-trial budget
      (``max_steps * max_trials``) ran out before the last eval time.
    * ``CHECKPOINT_OVERFLOW`` — the accepted-step budget (``max_steps``,
      the checkpoint capacity) ran out before the last eval time
      (the condition previously only visible as ``stats.overflow``).
    """
    OK = 0
    NONFINITE_STATE = 1
    STEPSIZE_UNDERFLOW = 2
    TRIAL_BUDGET_EXHAUSTED = 3
    CHECKPOINT_OVERFLOW = 4

    _NAMES = {0: "OK", 1: "NONFINITE_STATE", 2: "STEPSIZE_UNDERFLOW",
              3: "TRIAL_BUDGET_EXHAUSTED", 4: "CHECKPOINT_OVERFLOW"}

    @classmethod
    def describe(cls, code) -> str:
        """Human-readable name for one (host-side) status code."""
        return cls._NAMES.get(int(code), f"UNKNOWN({int(code)})")


class SolveStats(NamedTuple):
    """Solver cost counters + health status for one solve.

    Scalars for an unbatched solve; shape (B,) per-element arrays for a
    batched solve (``batch_axis``), where a finished element's counters
    stop advancing while stragglers integrate on.  ``status`` holds a
    ``SolveStatus`` code per solve/element — 0 (OK) on the healthy path.

    ``n_rides`` (batched RK loop only) counts the iterations each row
    spent in the block the loop processed, finished or not: Σ n_trials ÷
    Σ n_rides is the share of processed row-slots that did a trial.
    """
    n_steps: jnp.ndarray      # accepted steps (paper's N_t)
    n_trials: jnp.ndarray     # total ψ trials (N_t * m)
    nfe: jnp.ndarray          # number of f evaluations
    overflow: jnp.ndarray     # bool: checkpoint buffer exhausted
    status: jnp.ndarray       # int32 SolveStatus code
    # batched adaptive loop only: iterations in which the row held a
    # slot of the processed block, live or frozen (None elsewhere)
    n_rides: Optional[jnp.ndarray] = None


class Checkpoints(NamedTuple):
    """The paper's trajectory checkpoint: accepted grid + states.

    ``z`` holds z_i at the *start* of accepted interval i; ``t``/``h`` its
    start time and accepted stepsize; ``out_idx`` the index into ``ts`` that
    the interval's endpoint landed on (or -1).  Only slots [0, n) are valid.

    With ``checkpoint_segments=K`` the scalar grids keep one slot per
    accepted step (they are cheap) but ``z`` holds only K coarse
    snapshots: slot s is the state at accepted step ``s * seg_len``,
    ``seg_len = ceil(max_steps / K)``.  The ACA backward sweep then
    re-integrates each segment from its snapshot with the *saved*
    stepsizes before replaying it in reverse (see ``docs/memory.md``).

    ``k0`` (segmented mode only) snapshots the first-stage derivative
    carry alongside each state snapshot, so the segment re-integration
    can chain FSAL first-stage reuse exactly as the forward loop did —
    the replayed trajectory is the forward trajectory *bitwise*, not
    just up to the FSAL algebraic identity.

    Batched solves reuse the same structure with a leading batch dim:
    ``t``/``h``/``out_idx`` become (B, max_steps), ``z`` leaves
    (B, max_steps, ...) — or (B, K, ...) snapshots — and ``n`` (B,);
    each element records its *own* accepted grid, which the ACA backward
    sweep replays per element.

    Natural-grid mode (``interpolate_ts``): interior eval times are no
    longer step landings, so ``out_idx`` marks only the *final* eval
    time; ``ev_lo``/``ev_hi`` record the half-open range of eval indices
    whose times fall inside accepted interval i — the ACA backward sweep
    re-injects those cotangents through the interval's interpolant.
    ``coeffs`` (dense-solution mode only) stores the fitted interpolant
    coefficients of every accepted step.
    """
    t: jnp.ndarray            # (max_steps,)
    h: jnp.ndarray            # (max_steps,)
    z: PyTree                 # (max_steps, ...) or (K, ...) per leaf
    out_idx: jnp.ndarray      # (max_steps,) int32
    n: jnp.ndarray            # number of valid slots
    k0: Optional[PyTree] = None   # (K, ...) stage-0 derivative snapshots
    ev_lo: Optional[jnp.ndarray] = None   # (max_steps,) int32
    ev_hi: Optional[jnp.ndarray] = None   # (max_steps,) int32
    coeffs: Optional[Any] = None  # InterpCoeffs of (max_steps, ...) buffers


def resolve_checkpoint_segments(spec, max_steps: int) -> Optional[int]:
    """Normalize a ``checkpoint_segments`` spec to an int K (or None).

    ``None`` keeps the full O(max_steps) state buffer; ``"auto"`` picks
    K = ceil(sqrt(max_steps)), the memory-optimal point of the
    O(K + max_steps/K) segmented cost model; an int is clamped into
    [1, max_steps].
    """
    if spec is None:
        return None
    if spec == "auto":
        return max(1, int(-(-max_steps ** 0.5 // 1)))  # ceil(sqrt)
    k = int(spec)
    if k < 1:
        raise ValueError(
            f"checkpoint_segments must be >= 1 or 'auto'; got {spec}")
    return min(k, max_steps)


def segment_length(n_segments: int, max_steps: int) -> int:
    """Steps per checkpoint segment: ceil(max_steps / K)."""
    return -(-max_steps // n_segments)


def resolve_segmentation(
        spec, max_steps: int) -> Tuple[Optional[int], Optional[int]]:
    """Resolve a ``checkpoint_segments`` spec to ``(n_seg, seg_len)``.

    Returns ``(None, None)`` for the full buffer — including the
    degenerate K >= max_steps case, where seg_len would be 1 and every
    step is snapshotted anyway, so the classic sweep is strictly better
    (no pointless per-step re-integration).
    """
    n_seg = resolve_checkpoint_segments(spec, max_steps)
    if n_seg is None:
        return None, None
    seg_len = segment_length(n_seg, max_steps)
    if seg_len == 1:
        return None, None
    return n_seg, seg_len


def _snapshot_layout(n_seg: Optional[int],
                     max_steps: int) -> Tuple[int, int]:
    """State-buffer layout of an adaptive engine: (n_state_slots,
    seg_len), where ``n_seg=None`` means the classic full buffer."""
    if n_seg is None:
        return max_steps, 1
    return n_seg, segment_length(n_seg, max_steps)


def _init_checkpoint_buffers(
    z0: PyTree,
    max_steps: int,
    tdt,
    n_state_slots: int,
    batch_size: Optional[int] = None,
):
    """Zero-initialized Checkpoints buffers shared by the solo and
    batched adaptive engines.

    The scalar grids (t, h, out_idx) always get ``max_steps`` slots —
    they cost O(N_f) scalars and the backward sweep needs every accepted
    stepsize.  The state buffer gets ``n_state_slots`` slots per element:
    ``max_steps`` for the classic full buffer, or K coarse snapshots
    under ``checkpoint_segments=K``.  Returns (t, h, z, out_idx).
    """
    if batch_size is None:
        shape = (max_steps,)
        z = jax.tree.map(
            lambda l: jnp.zeros((n_state_slots,) + l.shape, l.dtype), z0)
    else:
        shape = (batch_size, max_steps)
        z = jax.tree.map(
            lambda l: jnp.zeros((l.shape[0], n_state_slots) + l.shape[1:],
                                l.dtype), z0)
    t = jnp.zeros(shape, tdt)
    oi = jnp.full(shape, -1, jnp.int32)
    return t, jnp.zeros_like(t), z, oi


def _empty_buffer(z0: PyTree, max_steps: int) -> PyTree:
    return jax.tree.map(
        lambda l: jnp.zeros((max_steps,) + l.shape, l.dtype), z0)


def _buffer_set(buf: PyTree, i, val: PyTree) -> PyTree:
    return jax.tree.map(lambda b, v: b.at[i].set(v), buf, val)


def _buffer_slot(buf: PyTree, i) -> PyTree:
    return jax.tree.map(lambda b: b[i], buf)


def _where_tree(pred, a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _nonfinite_any(tree: PyTree) -> jnp.ndarray:
    """Scalar bool: any leaf of ``tree`` holds a NaN/Inf.  The cheap
    finite-mask read of the solve-health guards — pure reduction, no
    effect on the values it inspects."""
    out = None
    for leaf in jax.tree.leaves(tree):
        flag = jnp.any(~jnp.isfinite(leaf))
        out = flag if out is None else out | flag
    return out if out is not None else jnp.asarray(False)


def _nonfinite_rows(tree: PyTree) -> jnp.ndarray:
    """Per-element (B,) bool twin of ``_nonfinite_any`` over
    batch-leading leaves."""
    out = None
    for leaf in jax.tree.leaves(tree):
        flat = leaf.reshape((leaf.shape[0], -1))
        flag = jnp.any(~jnp.isfinite(flat), axis=1)
        out = flag if out is None else out | flag
    return out


def _compose_status(failed, uflow, finished, trials_out) -> jnp.ndarray:
    """Fold the engines' health flags into one ``SolveStatus`` code
    (elementwise for batched solves): non-finite failure dominates,
    then whichever budget truncated the solve, then the accepted-but-
    out-of-tolerance underflow warning."""
    budget = jnp.where(trials_out,
                       SolveStatus.TRIAL_BUDGET_EXHAUSTED,
                       SolveStatus.CHECKPOINT_OVERFLOW)
    tail = jnp.where(uflow, SolveStatus.STEPSIZE_UNDERFLOW, SolveStatus.OK)
    status = jnp.where(finished, tail, budget)
    return jnp.where(failed, SolveStatus.NONFINITE_STATE,
                     status).astype(jnp.int32)


def _mask_failed_cotangents(g_ys: PyTree, status: jnp.ndarray,
                            batched: bool = False) -> PyTree:
    """Zero the output cotangents of solves (or batch elements) whose
    status is ``NONFINITE_STATE`` before a backward sweep runs.

    A frozen solve's outputs are last-good placeholders, not solution
    values — their cotangents must not leak into dz0/dargs (for batched
    solves, into the *shared* dargs reduction).  Every backward sweep is
    linear in ``g_ys``, so zeroing here yields exact zeros for the
    failed element and leaves healthy elements bit-identical.
    ``g_ys`` leaves are (n_eval, ...) solo / (n_eval, B, ...) batched.
    """
    ok = status != SolveStatus.NONFINITE_STATE
    if not batched:
        return jax.tree.map(
            lambda g: jnp.where(ok, g, jnp.zeros_like(g)), g_ys)
    return jax.tree.map(
        lambda g: jnp.where(ok.reshape((1, -1) + (1,) * (g.ndim - 2)),
                            g, jnp.zeros_like(g)),
        g_ys)


def _freeze_fill(ys: PyTree, mask: jnp.ndarray, z_frozen: PyTree) -> PyTree:
    """Repeat a failed solve's last accepted state into its un-reached
    eval slots, so frozen elements return finite last-good values
    instead of zero-initialized buffer slots.  ``mask`` is (n_eval,)
    solo / (n_eval, B) batched; bitwise no-op where it is False."""
    return jax.tree.map(
        lambda b, v: jnp.where(
            mask.reshape(mask.shape + (1,) * (b.ndim - mask.ndim)),
            v[None], b),
        ys, z_frozen)


def natural_grid_outputs(ts, karr, tiny, t, t_new, h_use, accept, hit,
                         eval_idx, ys, z, z_next, k0, k1, z_mid):
    """One trial's output writes in natural-grid (``interpolate_ts``)
    mode, shared by the solo adaptive engine and the solo naive scan.

    Interior eval times covered by an accepted interval are read off its
    interpolant; ``ts[-1]`` stays an exact landing, and a final-landing
    ``hit`` covers every remaining interior time (θ clips to 1), so no
    eval index is ever skipped.  Returns ``(ys, coeffs, n_cov,
    eval_advance)`` — the updated output buffer, the fitted interpolant
    (for coefficient storage), the interior-cover count and the
    ``eval_idx`` increment.  All plain jnp: differentiable on the naive
    tape, masked no-op on rejected trials.
    """
    n_eval = ts.shape[0]
    covered = (accept & (karr >= eval_idx)
               & (karr < n_eval - 1) & ((ts <= t_new) | hit))
    # dtype pinned: x64 would promote a plain sum to int64 and break
    # the loop carry
    n_cov = jnp.sum(covered, dtype=jnp.int32)
    coeffs = interp_fit(z, z_next, k0, k1, h_use, z_mid)
    theta = jnp.clip((ts - t) / jnp.maximum(h_use, tiny), 0.0, 1.0)
    yint = interp_eval(coeffs, theta)
    ys = jax.tree.map(
        lambda b, v: jnp.where(
            covered.reshape((n_eval,) + (1,) * (v.ndim - 1)), v, b),
        ys, yint)
    ys = jax.tree.map(
        lambda b, v: b.at[n_eval - 1].set(
            jnp.where(hit, v, b[n_eval - 1])),
        ys, z_next)
    return ys, coeffs, n_cov, n_cov + hit.astype(jnp.int32)


def natural_grid_outputs_batched(ts, karr, tiny, rows, t, t_new, h_use,
                                 accept, hit, eval_idx, ys, z, z_next,
                                 k0, k1, z_mid):
    """Batched twin of ``natural_grid_outputs``: per-row times/steps,
    (n_eval, B) cover mask, per-row ``n_cov``/``eval_advance``.

    ``rows`` is None when the block is the whole batch in row order, or
    the (B_p,) batch rows of a compacted block, which then index the
    full-batch ``ys``."""
    n_eval = ts.shape[0]
    covered = (accept[None, :]
               & (karr[:, None] >= eval_idx[None, :])
               & (karr[:, None] < n_eval - 1)
               & ((ts[:, None] <= t_new[None, :])
                  | hit[None, :]))                      # (n_eval, B)
    n_cov = jnp.sum(covered, axis=0, dtype=jnp.int32)   # (B,)
    coeffs = interp_fit(z, z_next, k0, k1, h_use, z_mid)
    theta = jnp.clip(
        (ts[:, None] - t[None, :])
        / jnp.maximum(h_use, tiny)[None, :], 0.0, 1.0)
    yint = interp_eval(coeffs, theta)                   # (n_eval, B, ...)

    def cover(b, v):
        return jnp.where(
            covered.reshape(covered.shape + (1,) * (v.ndim - 2)), v, b)

    if rows is None:
        ys = jax.tree.map(cover, ys, yint)
        rows = jnp.arange(t.shape[0])
    else:
        ys = jax.tree.map(
            lambda b, v: b.at[:, rows].set(cover(b[:, rows], v)), ys, yint)
    ys = jax.tree.map(
        lambda b, v: b.at[n_eval - 1, rows].set(
            _bwhere(hit, v, b[n_eval - 1, rows])),
        ys, z_next)
    return ys, coeffs, n_cov, n_cov + hit.astype(jnp.int32)


def adaptive_while_solve(
    tab: Tableau,
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: Tuple,
    rtol: float,
    atol: float,
    cfg: ControllerConfig,
    h0: Optional[jnp.ndarray] = None,
    use_pallas: bool = False,
    checkpoint_segments: Optional[int] = None,
    interpolate_ts: bool = False,
    store_coeffs: bool = False,
    guard_nonfinite: bool = True,
) -> Tuple[PyTree, Checkpoints, SolveStats]:
    """Integrate dz/dt = f(t, z, *args) through increasing times ``ts``.

    Returns (ys, checkpoints, stats); ``ys`` is stacked over len(ts) with
    ys[0] = z0.  Not reverse-differentiable (while_loop) — wrap in
    custom_vjp (ACA / adjoint) or use only for inference.

    ``use_pallas`` selects the fused flat-state stepper path; callers
    pass an already-flat (N,) state (see ``stepper.flatten_problem``) —
    the trial step and its error norm then run as fused Pallas kernels
    and the while_loop carry/checkpoint buffers hold one flat array per
    slot.  Non-flat states silently use the pytree stepper.

    ``checkpoint_segments=K`` (an already-resolved int — see
    ``resolve_checkpoint_segments``) switches the state buffer to K
    coarse snapshots written every ``segment_length(K, max_steps)``
    accepted steps; the scalar grids still record every step so a
    segmented ACA backward sweep can re-integrate losslessly.

    ``interpolate_ts`` switches to the *natural-grid* mode: the stepper
    is clamped only to the final time ``ts[-1]`` (not to every interior
    eval time), and interior outputs are read off each accepted step's
    local interpolant (``stepper.interp_fit``) — dense eval grids stop
    inflating the accepted-step count.  ``ys[0]`` and ``ys[-1]`` stay
    exact solver states; the checkpoint records ``ev_lo``/``ev_hi`` per
    interval so the ACA backward sweep can re-inject interpolated-output
    cotangents.  ``store_coeffs`` additionally saves every accepted
    step's interpolant coefficients in ``Checkpoints.coeffs`` (the
    dense-solution mode of ``odeint_dense``); it implies the natural
    grid.

    ``guard_nonfinite`` (default on) arms the solve-health guards: a
    trial producing a non-finite state or error norm is never accepted
    (even a forced-minimum one), and once the stepsize has railed at
    ``h_min`` with the trial still non-finite the solve *freezes* at its
    last accepted state and reports ``SolveStatus.NONFINITE_STATE``.
    The whole guard is one ``isfinite`` read of the already-computed
    error ratio — a non-finite trial state always poisons it (every
    stage feeding ``z_next`` has a nonzero embedded-error weight, and an
    Inf state turns the scaled norm into Inf/Inf = NaN) — so the healthy
    path stays bit-identical at ~zero cost; ``False`` reproduces the
    unguarded loop (used by ``bench_failure_overhead`` to price the
    guards).
    """
    n_eval = ts.shape[0]
    tdt = ts.dtype
    max_steps = cfg.max_steps
    # trial budget: every accepted step costs >= 1 trial
    max_total_trials = max_steps * cfg.max_trials
    n_snap, seg_len = _snapshot_layout(checkpoint_segments, max_steps)
    natural = interpolate_ts or store_coeffs

    hinit_evals = 2 if h0 is None else 0  # hinit costs 2 f-evals
    if h0 is None:
        h0 = initial_stepsize(f, ts[0], z0, args, tab.order, rtol, atol)
    h0 = jnp.asarray(h0, tdt)

    ys = _empty_buffer(z0, n_eval)
    ys = _buffer_set(ys, 0, z0)

    ckpt_t, ckpt_h, ckpt_z, ckpt_oi = _init_checkpoint_buffers(
        z0, max_steps, tdt, n_snap)

    k0 = field_eval(f, ts[0], z0, *args)
    nfe0 = jnp.asarray(1 + hinit_evals, jnp.int32)

    # a non-finite initial state / derivative / h0 fails before stepping
    failed0 = _nonfinite_any((z0, k0, h0)) if guard_nonfinite \
        else jnp.asarray(False)

    carry0 = dict(
        t=ts[0], z=z0, k0=k0, h=h0,
        prev_ratio=jnp.asarray(1.0, jnp.float32),
        i=jnp.asarray(0, jnp.int32),            # accepted steps so far
        eval_idx=jnp.asarray(1, jnp.int32),     # next ts[] to hit
        trials=jnp.asarray(0, jnp.int32),
        nfe=nfe0,
        failed=failed0, uflow=jnp.asarray(False),
        ys=ys, ckpt_t=ckpt_t, ckpt_h=ckpt_h, ckpt_z=ckpt_z, ckpt_oi=ckpt_oi,
    )
    if checkpoint_segments is not None:
        # segmented replay re-chains FSAL reuse, so the k0 carry is
        # snapshotted next to the state at each segment boundary
        carry0["ckpt_k0"] = _empty_buffer(k0, n_snap)
    if natural:
        # per-interval half-open eval-index ranges for the ACA backward
        carry0["ckpt_elo"] = jnp.zeros((max_steps,), jnp.int32)
        carry0["ckpt_ehi"] = jnp.zeros((max_steps,), jnp.int32)
    if store_coeffs:
        carry0["ckpt_cf"] = InterpCoeffs(*(
            _empty_buffer(z0, max_steps) for _ in range(5)))

    tiny = jnp.asarray(jnp.finfo(tdt).eps, tdt)
    karr = jnp.arange(n_eval)

    def cond(c):
        return (
            (c["eval_idx"] < n_eval)
            & (c["i"] < max_steps)
            & (c["trials"] < max_total_trials)
            & ~c["failed"]
        )

    def body(c):
        t, z, h = c["t"], c["z"], c["h"]
        # natural grid: only the final time is a forced landing; the
        # controller otherwise picks its own accepted points
        t_target = ts[n_eval - 1] if natural else ts[c["eval_idx"]]
        # clamp trial step to land exactly on the target eval time
        h_min = 16.0 * tiny * jnp.maximum(jnp.abs(t), jnp.asarray(1.0, tdt))
        h_use = jnp.clip(h, h_min, t_target - t)
        res = rk_step(tab, f, t, z, h_use, args, k0=c["k0"],
                      use_pallas=use_pallas,
                      err_scale=(rtol, atol) if tab.adaptive else None,
                      dense=natural)
        nfe = c["nfe"] + (tab.stages - 1)

        if tab.adaptive:
            # fused path: the scaled norm came out of the combine kernel
            ratio = res.err_ratio if res.err_ratio is not None else \
                error_ratio(res.err, z, res.z_next, rtol, atol)
            railed = h_use <= h_min * (1 + 1e-3)
            if guard_nonfinite:
                # one scalar read guards the whole trial: a NaN/Inf
                # anywhere in the stage sums poisons the embedded error
                # (every stage feeding z_next carries a nonzero error
                # weight in our tableaus) and an Inf state makes the
                # scaled norm Inf/Inf = NaN — so ratio is non-finite
                # exactly when the trial is, at zero extra reductions
                bad = ~jnp.isfinite(ratio)
                # non-finite trials are never accepted; forced-minimum
                # steps are otherwise always accepted (cannot shrink)
                accept = ((ratio <= 1.0) | railed) & ~bad
            else:
                bad = jnp.asarray(False)
                accept = (ratio <= 1.0) | railed
        else:
            ratio = jnp.asarray(0.5, jnp.float32)
            # fixed-step: no retry possible, so a bad step is terminal
            railed = jnp.asarray(True)
            bad = _nonfinite_any(res.z_next) if guard_nonfinite \
                else jnp.asarray(False)
            accept = ~bad

        # health flags: railed + still non-finite -> freeze (terminal);
        # forced accept that still fails the error test -> underflow
        fail_now = bad & railed
        uflow_now = accept & railed & (ratio > 1.0)

        t_new = t + h_use
        hit = accept & (t_new >= t_target - 16.0 * tiny * jnp.maximum(
            jnp.abs(t_target), jnp.asarray(1.0, tdt)))

        # FSAL / first-stage reuse:
        #  - reject: (t, z) unchanged -> k0 still valid, 0 extra evals
        #  - accept + FSAL tableau: k0' = last stage of accepted step
        #  - accept + non-FSAL: recompute k0' = f(t', z')
        # (computed before the output writes: in natural-grid mode k0'
        # doubles as the interval-end derivative of the interpolant)
        if tab.fsal:
            k0_acc = res.k_last
            nfe_acc = nfe
        else:
            k0_acc = field_eval(f, t_new, res.z_next, *args)
            nfe_acc = nfe + 1

        # --- on accept: write trajectory checkpoint (t_i, h_i, z_i) -------
        i = c["i"]
        final_idx = jnp.asarray(n_eval - 1, jnp.int32)
        oi_val = jnp.where(hit, final_idx if natural else c["eval_idx"],
                           jnp.asarray(-1, jnp.int32))
        with jax.named_scope(CKPT_WRITE_SCOPE):
            ckpt_t = c["ckpt_t"].at[i].set(
                jnp.where(accept, t, c["ckpt_t"][i]))
            ckpt_h = c["ckpt_h"].at[i].set(
                jnp.where(accept, h_use, c["ckpt_h"][i]))
            ckpt_k0 = None
            if checkpoint_segments is None:
                ckpt_z = jax.tree.map(
                    lambda b, v: b.at[i].set(jnp.where(accept, v, b[i])),
                    c["ckpt_z"], z)
            else:
                # segmented: snapshot (z, k0) only at segment boundaries
                # (accepted step s * seg_len); c["k0"] is exactly the
                # first-stage derivative this accepted trial consumed
                s = jnp.minimum(i // seg_len, n_snap - 1)
                snap = accept & (i % seg_len == 0)
                ckpt_z = jax.tree.map(
                    lambda b, v: b.at[s].set(jnp.where(snap, v, b[s])),
                    c["ckpt_z"], z)
                ckpt_k0 = jax.tree.map(
                    lambda b, v: b.at[s].set(jnp.where(snap, v, b[s])),
                    c["ckpt_k0"], c["k0"])
            ckpt_oi = c["ckpt_oi"].at[i].set(
                jnp.where(accept, oi_val, c["ckpt_oi"][i]))

        # --- outputs ------------------------------------------------------
        extra = {}
        if natural:
            ys, coeffs, n_cov, eval_advance = natural_grid_outputs(
                ts, karr, tiny, t, t_new, h_use, accept, hit,
                c["eval_idx"], c["ys"], z, res.z_next, res.k_first,
                k0_acc, res.z_mid)
            with jax.named_scope(CKPT_WRITE_SCOPE):
                extra["ckpt_elo"] = c["ckpt_elo"].at[i].set(
                    jnp.where(accept, c["eval_idx"], c["ckpt_elo"][i]))
                extra["ckpt_ehi"] = c["ckpt_ehi"].at[i].set(
                    jnp.where(accept, c["eval_idx"] + n_cov,
                              c["ckpt_ehi"][i]))
                if store_coeffs:
                    extra["ckpt_cf"] = InterpCoeffs(*(
                        jax.tree.map(
                            lambda b, v: b.at[i].set(jnp.where(accept, v,
                                                               b[i])),
                            cb, cv)
                        for cb, cv in zip(c["ckpt_cf"], coeffs)))
        else:
            # --- on eval-time hit: record output --------------------------
            ys = jax.tree.map(
                lambda b, v: b.at[c["eval_idx"]].set(
                    jnp.where(hit, v, b[c["eval_idx"]])),
                c["ys"], res.z_next)
            eval_advance = hit.astype(jnp.int32)

        # --- stepsize control ---------------------------------------------
        # a non-finite error ratio would poison the controller's h chain
        # (NaN h never recovers); treat it as "error way too large" so
        # the retry shrinks at max rate.  Bitwise no-op when finite.
        ratio_c = jnp.where(bad, jnp.asarray(1e10, jnp.float32), ratio)
        h_next = propose_stepsize(
            cfg, h_use, ratio_c, c["prev_ratio"], tab.order)
        # (the paper's Algo 1: shrink and retry on reject; grow on accept)
        h_next = jnp.asarray(h_next, tdt)

        k0_new = _where_tree(accept, k0_acc, c["k0"])
        nfe = jnp.where(accept, nfe_acc, nfe)

        out = dict(
            t=jnp.where(accept, t_new, t),
            z=_where_tree(accept, res.z_next, z),
            k0=k0_new,
            h=h_next,
            prev_ratio=jnp.where(
                accept, jnp.maximum(ratio, 1e-10), c["prev_ratio"]),
            i=i + accept.astype(jnp.int32),
            eval_idx=c["eval_idx"] + eval_advance,
            trials=c["trials"] + 1,
            nfe=nfe,
            failed=c["failed"] | fail_now,
            uflow=c["uflow"] | uflow_now,
            ys=ys, ckpt_t=ckpt_t, ckpt_h=ckpt_h, ckpt_z=ckpt_z,
            ckpt_oi=ckpt_oi,
        )
        if ckpt_k0 is not None:
            out["ckpt_k0"] = ckpt_k0
        out.update(extra)
        return out

    c = jax.lax.while_loop(cond, body, carry0)

    overflow = c["eval_idx"] < n_eval
    status = _compose_status(c["failed"], c["uflow"], ~overflow,
                             c["trials"] >= max_total_trials)
    # frozen solve: repeat the last accepted state into un-reached slots
    ys_out = _freeze_fill(c["ys"], c["failed"] & (karr >= c["eval_idx"]),
                          c["z"])
    ckpts = Checkpoints(t=c["ckpt_t"], h=c["ckpt_h"], z=c["ckpt_z"],
                        out_idx=c["ckpt_oi"], n=c["i"],
                        k0=c.get("ckpt_k0"),
                        ev_lo=c.get("ckpt_elo"), ev_hi=c.get("ckpt_ehi"),
                        coeffs=c.get("ckpt_cf"))
    stats = SolveStats(n_steps=c["i"], n_trials=c["trials"], nfe=c["nfe"],
                       overflow=overflow, status=status)
    return ys_out, ckpts, stats


def _row_tolerances(rtol, atol, B):
    """Normalize a per-row tolerance pair to ((B,), (B,)) f32 arrays, or
    None when both are scalars (the classic solve-global path — kept
    untouched so scalar solves stay bit-compatible)."""
    if jnp.ndim(rtol) == 0 and jnp.ndim(atol) == 0:
        return None
    return (jnp.broadcast_to(jnp.asarray(rtol, jnp.float32), (B,)),
            jnp.broadcast_to(jnp.asarray(atol, jnp.float32), (B,)))


# smallest active block of the batched loop's row compaction: one row
# group of the batched rk_stage kernels, and MXU-sized; a batch under
# twice this runs as one loop over all of its rows
COMPACT_FLOOR = 128


def _block_sizes(B: int) -> list:
    """Static row counts of the batched loop's phases: B, then halvings
    down to ``COMPACT_FLOOR`` (just [B] below twice the floor)."""
    sizes = [B]
    while sizes[-1] // 2 >= COMPACT_FLOOR:
        sizes.append(sizes[-1] // 2)
    return sizes


def _bwhere(pred, a, b):
    """jnp.where with a (B,) predicate broadcast over batch-leading leaves."""
    return jnp.where(pred.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)


def _bwhere_tree(pred, a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: _bwhere(pred, x, y), a, b)


def batched_adaptive_while_solve(
    tab: Tableau,
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: Tuple,
    rtol: float,
    atol: float,
    cfg: ControllerConfig,
    h0: Optional[jnp.ndarray] = None,
    use_pallas: bool = False,
    checkpoint_segments: Optional[int] = None,
    interpolate_ts: bool = False,
    guard_nonfinite: bool = True,
) -> Tuple[PyTree, Checkpoints, SolveStats]:
    """Per-sample batched adaptive solve: one fused while_loop, one
    stepsize controller *per batch element*.

    ``z0`` leaves carry a leading batch dim B; ``f`` is the per-sample
    vector field (no batch dim — it is vmapped inside the stepper).
    Returns (ys, checkpoints, stats) where ``ys`` leaves are
    (len(ts), B, ...) with ys[0] = z0, checkpoints/stats carry per-element
    rows (see ``Checkpoints`` / ``SolveStats``).  Not
    reverse-differentiable (while_loop) — wrap in custom_vjp (ACA /
    adjoint) or use only for inference.

    Batch rows never interact (no cross-element reduction anywhere in
    the loop), so the solve is embarrassingly parallel over B: running
    it on a batch *shard* yields exactly the shard's rows of the full
    solve, with a shard-local trip count — the property
    ``odeint(..., mesh=...)`` builds its ``shard_map`` sharding on.

    Each iteration advances every *live* element one ψ trial with its own
    trial stepsize; per-element accept/reject masks (``jnp.where``
    freezing, h = 0 for dead rows) keep rejected and finished elements
    bit-stable, and the loop runs until all elements have landed on their
    last ``ts[k]`` (or exhausted their step/trial budget).  ``use_pallas``
    expects an already-flat (B, N) state (``stepper.maybe_flatten_batched``)
    and runs every trial through the batched fused kernels with per-row
    error norms.  ``checkpoint_segments`` as in ``adaptive_while_solve``:
    each element writes its own K snapshot rows at its own segment
    boundaries.  ``interpolate_ts`` as in ``adaptive_while_solve``:
    every element advances on its own natural grid and reads interior
    eval times off its own per-step interpolants (per-element
    ``ev_lo``/``ev_hi`` rows feed the batched ACA backward sweep).
    ``guard_nonfinite`` as in ``adaptive_while_solve``, per element: a
    failing element freezes (leaves the live set, h = 0 identity trials)
    and reports ``SolveStatus.NONFINITE_STATE`` in its status row while
    healthy elements integrate on bit-identically.

    ``rtol``/``atol`` may be scalars (one tolerance for the whole batch,
    the classic path) or (B,) arrays — then every element's stepsize
    controller targets its *own* tolerance (initial-stepsize heuristic
    and per-trial error norm included), the per-request QoS knob of the
    serving engine.  A row at tolerance τ is bitwise the all-τ batch's
    row either way.

    Row compaction: with B ≥ 2 · ``COMPACT_FLOOR`` the loop runs in
    phases over an active block of B, B/2, B/4, … rows, down to the
    floor.  A phase ends when its live rows fit the next block; the live
    rows (in batch order, ``row_id``) and finished rows to fill it carry
    their loop state into the next phase, and every buffer write indexes
    the full-batch ``ys``/``Checkpoints`` by ``row_id``.  Each row takes
    the same trials as in one loop over all B rows, so results are
    bitwise the same per row; ``SolveStats.n_rides`` counts the
    iterations each row held a slot of the block.
    """
    if not tab.adaptive:
        raise ValueError("batched_adaptive_while_solve requires an "
                         "embedded adaptive tableau")
    B = jax.tree.leaves(z0)[0].shape[0]
    n_eval = ts.shape[0]
    tdt = ts.dtype
    max_steps = cfg.max_steps
    max_total_trials = max_steps * cfg.max_trials
    n_snap, seg_len = _snapshot_layout(checkpoint_segments, max_steps)
    targs = args

    row_tol = _row_tolerances(rtol, atol, B)
    hinit_evals = 2 if h0 is None else 0  # hinit costs 2 f-evals per elt
    if h0 is None:
        if row_tol is not None:
            h0 = jax.vmap(lambda z, rt, at: initial_stepsize(
                f, ts[0], z, targs, tab.order, rt, at))(z0, *row_tol)
        else:
            h0 = jax.vmap(lambda z: initial_stepsize(
                f, ts[0], z, targs, tab.order, rtol, atol))(z0)
    h0 = jnp.broadcast_to(jnp.asarray(h0, tdt), (B,))

    ys = _buffer_set(_empty_buffer(z0, n_eval), 0, z0)

    ckpt_t, ckpt_h, ckpt_z, ckpt_oi = _init_checkpoint_buffers(
        z0, max_steps, tdt, n_snap, batch_size=B)

    fb0 = jax.vmap(lambda ti, zi: field_eval(f, ti, zi, *targs))
    k0 = fb0(jnp.full((B,), ts[0], tdt), z0)
    nfe0 = jnp.full((B,), 1 + hinit_evals, jnp.int32)

    # elements starting from a non-finite state/derivative/h0 fail at once
    failed0 = _nonfinite_rows((z0, k0, h0)) if guard_nonfinite \
        else jnp.zeros((B,), bool)

    # per-row loop state: a compacted phase carries its block's rows
    state = dict(
        t=jnp.full((B,), ts[0], tdt), z=z0, k0=k0, h=h0,
        prev_ratio=jnp.ones((B,), jnp.float32),
        i=jnp.zeros((B,), jnp.int32),           # accepted steps so far
        eval_idx=jnp.ones((B,), jnp.int32),     # next ts[] to hit
        trials=jnp.zeros((B,), jnp.int32),
        nfe=nfe0,
        failed=failed0, uflow=jnp.zeros((B,), bool),
        rides=jnp.zeros((B,), jnp.int32),       # iterations in the block
    )
    if row_tol is not None:
        state["rtol"], state["atol"] = row_tol
    # full-batch buffers, written by batch row in every phase
    bufs = dict(ys=ys, ckpt_t=ckpt_t, ckpt_h=ckpt_h, ckpt_z=ckpt_z,
                ckpt_oi=ckpt_oi)
    if checkpoint_segments is not None:
        # segmented replay re-chains FSAL reuse per element: snapshot
        # each element's k0 carry next to its state snapshots
        bufs["ckpt_k0"] = jax.tree.map(
            lambda l: jnp.zeros((l.shape[0], n_snap) + l.shape[1:],
                                l.dtype), k0)
    if interpolate_ts:
        # per-element half-open eval-index ranges per accepted interval
        bufs["ckpt_elo"] = jnp.zeros((B, max_steps), jnp.int32)
        bufs["ckpt_ehi"] = jnp.zeros((B, max_steps), jnp.int32)

    tiny = jnp.asarray(jnp.finfo(tdt).eps, tdt)
    karr = jnp.arange(n_eval)

    def live_mask(c):
        return (
            (c["eval_idx"] < n_eval)
            & (c["i"] < max_steps)
            & (c["trials"] < max_total_trials)
            & ~c["failed"]
        )

    def body(c):
        live = live_mask(c)
        # a compacted block's batch rows (None: the whole batch in order)
        rows = c.get("row_id")
        idx = jnp.arange(live.shape[0]) if rows is None else rows
        t, z, h = c["t"], c["z"], c["h"]
        # natural grid: only the final time is a forced landing
        t_target = ts[n_eval - 1] if interpolate_ts else \
            ts[jnp.minimum(c["eval_idx"], n_eval - 1)]          # (B,)
        h_min = 16.0 * tiny * jnp.maximum(jnp.abs(t), jnp.asarray(1.0, tdt))
        # dead elements step with h = 0: ψ degenerates to the identity
        h_use = jnp.where(live, jnp.clip(h, h_min, t_target - t),
                          jnp.zeros((), tdt))
        res = rk_step_batched(
            tab, f, t, z, h_use, targs, k0=c["k0"], use_pallas=use_pallas,
            err_scale=(rtol, atol) if row_tol is None
            else (c["rtol"], c["atol"]),
            dense=interpolate_ts)
        ratio = res.err_ratio                                   # (B,)
        railed = h_use <= h_min * (1 + 1e-3)
        if guard_nonfinite:
            # per-row scalar read: a non-finite row state forces a
            # non-finite row ratio (see adaptive_while_solve)
            bad = ~jnp.isfinite(ratio)
            accept = live & ((ratio <= 1.0) | railed) & ~bad
        else:
            bad = jnp.zeros_like(live)
            accept = live & ((ratio <= 1.0) | railed)
        # per-element health flags (dead rows: live False masks them out)
        fail_now = live & bad & railed
        uflow_now = accept & railed & (ratio > 1.0)

        t_new = t + h_use
        hit = accept & (t_new >= t_target - 16.0 * tiny * jnp.maximum(
            jnp.abs(t_target), jnp.asarray(1.0, tdt)))

        # FSAL / first-stage reuse, per element (hoisted before the
        # output writes: in natural-grid mode k0' doubles as the
        # interval-end derivative of each element's interpolant)
        if tab.fsal:
            k0_acc = res.k_last
            nfe_acc = jnp.zeros_like(c["nfe"])
        else:
            k0_acc = jax.vmap(
                lambda ti, zi: field_eval(f, ti, zi, *targs))(
                    t_new, res.z_next)
            nfe_acc = jnp.ones_like(c["nfe"])

        # --- on accept: write each element's own checkpoint row ----------
        i_c = jnp.minimum(c["i"], max_steps - 1)
        final_idx = jnp.asarray(n_eval - 1, jnp.int32)
        oi_val = jnp.where(hit,
                           final_idx if interpolate_ts else c["eval_idx"],
                           jnp.full_like(c["eval_idx"], -1))
        with jax.named_scope(CKPT_WRITE_SCOPE):
            ckpt_t = c["ckpt_t"].at[idx, i_c].set(
                jnp.where(accept, t, c["ckpt_t"][idx, i_c]))
            ckpt_h = c["ckpt_h"].at[idx, i_c].set(
                jnp.where(accept, h_use, c["ckpt_h"][idx, i_c]))
            ckpt_k0 = None
            if checkpoint_segments is None:
                ckpt_z = jax.tree.map(
                    lambda b, v: b.at[idx, i_c].set(_bwhere(accept, v,
                                                            b[idx, i_c])),
                    c["ckpt_z"], z)
            else:
                # segmented: each element snapshots (z, k0) at ITS OWN
                # boundaries; c["k0"] rows are exactly the first-stage
                # derivatives this accepted trial consumed
                s = jnp.minimum(i_c // seg_len, n_snap - 1)       # (B,)
                snap = accept & (i_c % seg_len == 0)
                ckpt_z = jax.tree.map(
                    lambda b, v: b.at[idx, s].set(_bwhere(snap, v,
                                                          b[idx, s])),
                    c["ckpt_z"], z)
                ckpt_k0 = jax.tree.map(
                    lambda b, v: b.at[idx, s].set(_bwhere(snap, v,
                                                          b[idx, s])),
                    c["ckpt_k0"], c["k0"])
            ckpt_oi = c["ckpt_oi"].at[idx, i_c].set(
                jnp.where(accept, oi_val, c["ckpt_oi"][idx, i_c]))

        # --- outputs ------------------------------------------------------
        extra = {}
        if interpolate_ts:
            # each element reads the eval times its accepted interval
            # covers off its own interpolant
            ys, _, n_cov, eval_advance = natural_grid_outputs_batched(
                ts, karr, tiny, rows, t, t_new, h_use, accept, hit,
                c["eval_idx"], c["ys"], z, res.z_next, res.k_first,
                k0_acc, res.z_mid)
            with jax.named_scope(CKPT_WRITE_SCOPE):
                extra["ckpt_elo"] = c["ckpt_elo"].at[idx, i_c].set(
                    jnp.where(accept, c["eval_idx"],
                              c["ckpt_elo"][idx, i_c]))
                extra["ckpt_ehi"] = c["ckpt_ehi"].at[idx, i_c].set(
                    jnp.where(accept, c["eval_idx"] + n_cov,
                              c["ckpt_ehi"][idx, i_c]))
        else:
            # --- on eval-time hit: record that element's output ----------
            e_c = jnp.minimum(c["eval_idx"], n_eval - 1)
            ys = jax.tree.map(
                lambda b, v: b.at[e_c, idx].set(
                    _bwhere(hit, v, b[e_c, idx])),
                c["ys"], res.z_next)
            eval_advance = hit.astype(jnp.int32)

        # --- per-element stepsize control ---------------------------------
        # sanitize non-finite ratios so the per-element h chain cannot
        # absorb a NaN (max-rate shrink instead); bitwise no-op when finite
        ratio_c = jnp.where(bad, jnp.asarray(1e10, jnp.float32), ratio)
        h_next = propose_stepsize(
            cfg, h_use, ratio_c, c["prev_ratio"], tab.order)
        h_next = jnp.asarray(h_next, tdt)

        k0_new = _bwhere_tree(accept, k0_acc, c["k0"])
        # finished elements take the h=0 identity trial for free: only
        # live elements pay f-evals in the per-element stats
        nfe = c["nfe"] + jnp.where(live, tab.stages - 1, 0) \
            + jnp.where(accept, nfe_acc, 0)

        out = dict(
            c,
            t=jnp.where(accept, t_new, t),
            z=_bwhere_tree(accept, res.z_next, z),
            k0=k0_new,
            h=jnp.where(live, h_next, h),
            prev_ratio=jnp.where(
                accept, jnp.maximum(ratio, 1e-10), c["prev_ratio"]),
            i=c["i"] + accept.astype(jnp.int32),
            eval_idx=c["eval_idx"] + eval_advance,
            trials=c["trials"] + live.astype(jnp.int32),
            nfe=nfe,
            failed=c["failed"] | fail_now,
            uflow=c["uflow"] | uflow_now,
            rides=c["rides"] + 1,
            ys=ys, ckpt_t=ckpt_t, ckpt_h=ckpt_h, ckpt_z=ckpt_z,
            ckpt_oi=ckpt_oi,
        )
        if ckpt_k0 is not None:
            out["ckpt_k0"] = ckpt_k0
        out.update(extra)
        return out

    # Row compaction: phase p loops over a block of sizes[p] rows and
    # ends once at most sizes[p + 1] of them are live; the next block
    # gathers the live rows (batch order kept) and finished ones to fill
    # it.  Rows never interact, so every row's trials are the same as in
    # one loop over the whole batch.
    sizes = _block_sizes(B)
    for p, size in enumerate(sizes):
        if p == 0:
            row_id = None
            carry = dict(state, **bufs)
        else:
            row_id = jnp.argsort((~live_mask(state)).astype(jnp.int32),
                                 stable=True)[:size].astype(jnp.int32)
            carry = dict(jax.tree.map(lambda x: x[row_id], state),
                         row_id=row_id, **bufs)
        n_next = sizes[p + 1] if p + 1 < len(sizes) else 0

        def cond(c, n_next=n_next):
            return jnp.sum(live_mask(c), dtype=jnp.int32) > n_next

        c = jax.lax.while_loop(cond, body, carry)
        bufs = {k: c[k] for k in bufs}
        blk = {k: c[k] for k in state}
        state = blk if row_id is None else jax.tree.map(
            lambda full, b: full.at[row_id].set(b), state, blk)

    c = state
    overflow = c["eval_idx"] < n_eval
    status = _compose_status(c["failed"], c["uflow"], ~overflow,
                             c["trials"] >= max_total_trials)
    fill = c["failed"][None, :] & (karr[:, None] >= c["eval_idx"][None, :])
    ys_out = _freeze_fill(bufs["ys"], fill, c["z"])
    ckpts = Checkpoints(t=bufs["ckpt_t"], h=bufs["ckpt_h"], z=bufs["ckpt_z"],
                        out_idx=bufs["ckpt_oi"], n=c["i"],
                        k0=bufs.get("ckpt_k0"),
                        ev_lo=bufs.get("ckpt_elo"), ev_hi=bufs.get("ckpt_ehi"))
    stats = SolveStats(n_steps=c["i"], n_trials=c["trials"], nfe=c["nfe"],
                       overflow=overflow, status=status, n_rides=c["rides"])
    return ys_out, ckpts, stats


def make_fixed_grid(ts: jnp.ndarray, steps_per_interval: int) -> jnp.ndarray:
    """Uniform sub-grid with ``steps_per_interval`` steps between each pair
    of eval times.  Returns (n_intervals * steps,) array of (t, h) pairs as
    two arrays (t_grid, h_grid)."""
    t_lo = ts[:-1]
    t_hi = ts[1:]
    frac = jnp.arange(steps_per_interval) / steps_per_interval
    # (n_intervals, steps)
    t_grid = t_lo[:, None] + (t_hi - t_lo)[:, None] * frac[None, :]
    h_grid = jnp.broadcast_to(
        ((t_hi - t_lo) / steps_per_interval)[:, None], t_grid.shape)
    return t_grid.reshape(-1), h_grid.reshape(-1)


def fixed_grid_solve(
    tab: Tableau,
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: Tuple,
    steps_per_interval: int,
    use_pallas: bool = False,
) -> Tuple[PyTree, SolveStats]:
    """Differentiable fixed-grid integration via ``lax.scan``.

    Outputs at every ``ts``; ys[0] = z0.  Reverse-mode AD through the scan
    is the naive method for fixed-step solvers.

    ``use_pallas`` ravels the state once (``stepper.flatten_problem``)
    and runs every step through the fused flat-state kernels; the
    unravel is applied to the stacked outputs.  Fully differentiable —
    the flatten/unravel are plain jnp reshapes on the AD path.
    """
    f, z0, unravel, use_pallas = maybe_flatten(f, z0, use_pallas)

    t_grid, h_grid = make_fixed_grid(ts, steps_per_interval)
    n_intervals = ts.shape[0] - 1

    def step_fn(z, t_h):
        t, h = t_h
        z_next = rk_step(tab, f, t, z, h, args,
                         use_pallas=use_pallas).z_next
        return z_next, None

    # scan per interval so we can emit outputs
    def interval(z, idx):
        t_seg = jax.lax.dynamic_slice_in_dim(
            t_grid, idx * steps_per_interval, steps_per_interval)
        h_seg = jax.lax.dynamic_slice_in_dim(
            h_grid, idx * steps_per_interval, steps_per_interval)
        z_end, _ = jax.lax.scan(step_fn, z, (t_seg, h_seg))
        return z_end, z_end

    _, ys_tail = jax.lax.scan(interval, z0, jnp.arange(n_intervals))
    ys = jax.tree.map(
        lambda z0l, tail: jnp.concatenate([z0l[None], tail], axis=0),
        z0, ys_tail)
    if unravel is not None:
        ys = jax.vmap(unravel)(ys)

    n_steps = n_intervals * steps_per_interval
    # fixed grids have no trial/accept loop to guard: the health check
    # is a single post-hoc finite-mask read over the outputs
    status = jnp.where(_nonfinite_any(ys),
                       SolveStatus.NONFINITE_STATE,
                       SolveStatus.OK).astype(jnp.int32)
    stats = SolveStats(
        n_steps=jnp.asarray(n_steps, jnp.int32),
        n_trials=jnp.asarray(n_steps, jnp.int32),
        nfe=jnp.asarray(n_steps * tab.stages, jnp.int32),
        overflow=jnp.asarray(False),
        status=status,
    )
    return ys, stats


# --------------------------------------------------------------------------
# MALI engines: reversible asynchronous-leapfrog adaptive solving
# --------------------------------------------------------------------------


class MaliGrid(NamedTuple):
    """The MALI solve's reverse-reconstruction record: scalars only.

    Where ACA's ``Checkpoints`` stores every accepted *state*, MALI
    stores none: ``t``/``h``/``out_idx`` are the accepted scalar grid
    (same conventions as ``Checkpoints`` — interval start time, accepted
    stepsize, eval-time landing index or -1; slots [0, n) valid), and
    ``zT``/``vT`` are the single terminal lattice pair the backward
    sweep starts inverting from.  ``scale_exp`` pins the per-solve
    lattice (``stepper.alf_lattice_exponent``) so the backward decodes
    on the identical quantum.  Batched solves carry a leading batch dim
    on the scalar grids ((B, max_steps)), per-element ``n`` (B,),
    batch-leading ``zT``/``vT`` leaves and per-element ``scale_exp``
    (B,) — each element quantizes on its own lattice, exactly as
    ``jax.vmap`` of the solo solve would.
    """
    t: jnp.ndarray            # (max_steps,) interval start times
    h: jnp.ndarray            # (max_steps,) accepted stepsizes
    out_idx: jnp.ndarray      # (max_steps,) int32 eval landing (or -1)
    n: jnp.ndarray            # number of valid slots
    zT: PyTree                # terminal position, integer lattice
    vT: PyTree                # terminal velocity, integer lattice
    scale_exp: jnp.ndarray    # lattice scale exponent (float32 scalar)


def mali_adaptive_solve(
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: Tuple,
    rtol: float,
    atol: float,
    cfg: ControllerConfig,
    h0: Optional[jnp.ndarray] = None,
    guard_nonfinite: bool = True,
) -> Tuple[PyTree, MaliGrid, SolveStats]:
    """Adaptive asynchronous-leapfrog solve through increasing ``ts``.

    Same flattened trial/accept ``lax.while_loop`` as
    ``adaptive_while_solve`` (Algorithm 1's stepsize search), but the
    carry is the integer-lattice pair (z, v) of ``stepper.alf_step`` and
    the only per-step record is the scalar grid — O(dim) state memory at
    any horizon.  The embedded error is the free Euler-comparator gap
    h·(w − v); one f evaluation per trial (accepted or rejected — ALF
    has no extra stages and no FSAL to chain).  Returns (ys, grid,
    stats) with ``ys[0] = z0`` exactly; interior/final outputs are the
    decoded lattice states (within one quantum of the float trajectory).
    Not reverse-differentiable — ``odeint_mali`` wraps it in custom_vjp.
    """
    n_eval = ts.shape[0]
    tdt = ts.dtype
    max_steps = cfg.max_steps
    max_total_trials = max_steps * cfg.max_trials
    targs = args

    v0 = f(ts[0], z0, *targs)
    scale_exp = alf_lattice_exponent(z0, v0)
    zq0 = lattice_encode(z0, scale_exp)
    vq0 = lattice_encode(v0, scale_exp)

    hinit_evals = 2 if h0 is None else 0  # hinit costs 2 f-evals
    if h0 is None:
        h0 = initial_stepsize(f, ts[0], z0, targs, ALF_ORDER, rtol, atol)
    h0 = jnp.asarray(h0, tdt)

    ys = _buffer_set(_empty_buffer(z0, n_eval), 0, z0)

    failed0 = _nonfinite_any((z0, v0, h0)) if guard_nonfinite \
        else jnp.asarray(False)

    carry0 = dict(
        t=ts[0], zq=zq0, vq=vq0, h=h0,
        prev_ratio=jnp.asarray(1.0, jnp.float32),
        i=jnp.asarray(0, jnp.int32),
        eval_idx=jnp.asarray(1, jnp.int32),
        trials=jnp.asarray(0, jnp.int32),
        nfe=jnp.asarray(1 + hinit_evals, jnp.int32),  # + the v0 eval
        failed=failed0, uflow=jnp.asarray(False),
        ys=ys,
        grid_t=jnp.zeros((max_steps,), tdt),
        grid_h=jnp.zeros((max_steps,), tdt),
        grid_oi=jnp.full((max_steps,), -1, jnp.int32),
    )

    tiny = jnp.asarray(jnp.finfo(tdt).eps, tdt)

    def cond(c):
        return (
            (c["eval_idx"] < n_eval)
            & (c["i"] < max_steps)
            & (c["trials"] < max_total_trials)
            & ~c["failed"]
        )

    def body(c):
        t, h = c["t"], c["h"]
        t_target = ts[c["eval_idx"]]
        h_min = 16.0 * tiny * jnp.maximum(jnp.abs(t), jnp.asarray(1.0, tdt))
        h_use = jnp.clip(h, h_min, t_target - t)
        res = alf_step(f, t, h_use, c["zq"], c["vq"], scale_exp, z0,
                       targs)
        z_f = lattice_decode(c["zq"], scale_exp, z0)
        ratio = error_ratio(res.err, z_f, res.z_next, rtol, atol)
        railed = h_use <= h_min * (1 + 1e-3)
        if guard_nonfinite:
            # the lattice encode launders NaN ints into finite garbage,
            # so the decoded state is useless as a detector — but the
            # raw f eval still poisons res.err, so the ratio read is
            # both the cheap AND the only sound guard here
            bad = ~jnp.isfinite(ratio)
            accept = ((ratio <= 1.0) | railed) & ~bad
        else:
            bad = jnp.asarray(False)
            accept = (ratio <= 1.0) | railed
        fail_now = bad & railed
        uflow_now = accept & railed & (ratio > 1.0)

        t_new = t + h_use
        hit = accept & (t_new >= t_target - 16.0 * tiny * jnp.maximum(
            jnp.abs(t_target), jnp.asarray(1.0, tdt)))

        # --- on accept: record the scalar grid slot (t_i, h_i, oi) -----
        i = c["i"]
        grid_t = c["grid_t"].at[i].set(jnp.where(accept, t, c["grid_t"][i]))
        grid_h = c["grid_h"].at[i].set(
            jnp.where(accept, h_use, c["grid_h"][i]))
        oi_val = jnp.where(hit, c["eval_idx"], jnp.asarray(-1, jnp.int32))
        grid_oi = c["grid_oi"].at[i].set(
            jnp.where(accept, oi_val, c["grid_oi"][i]))

        # --- on eval-time hit: record the decoded output ---------------
        ys = jax.tree.map(
            lambda b, v: b.at[c["eval_idx"]].set(
                jnp.where(hit, v, b[c["eval_idx"]])),
            c["ys"], res.z_next)

        ratio_c = jnp.where(bad, jnp.asarray(1e10, jnp.float32), ratio)
        h_next = jnp.asarray(propose_stepsize(
            cfg, h_use, ratio_c, c["prev_ratio"], ALF_ORDER), tdt)

        return dict(
            t=jnp.where(accept, t_new, t),
            zq=_where_tree(accept, res.zq_next, c["zq"]),
            vq=_where_tree(accept, res.vq_next, c["vq"]),
            h=h_next,
            prev_ratio=jnp.where(
                accept, jnp.maximum(ratio, 1e-10), c["prev_ratio"]),
            i=i + accept.astype(jnp.int32),
            eval_idx=c["eval_idx"] + hit.astype(jnp.int32),
            trials=c["trials"] + 1,
            nfe=c["nfe"] + 1,  # one midpoint eval per ALF trial
            failed=c["failed"] | fail_now,
            uflow=c["uflow"] | uflow_now,
            ys=ys, grid_t=grid_t, grid_h=grid_h, grid_oi=grid_oi,
        )

    c = jax.lax.while_loop(cond, body, carry0)

    overflow = c["eval_idx"] < n_eval
    status = _compose_status(c["failed"], c["uflow"], ~overflow,
                             c["trials"] >= max_total_trials)
    karr = jnp.arange(n_eval)
    ys_out = _freeze_fill(c["ys"], c["failed"] & (karr >= c["eval_idx"]),
                          lattice_decode(c["zq"], scale_exp, z0))
    grid = MaliGrid(t=c["grid_t"], h=c["grid_h"], out_idx=c["grid_oi"],
                    n=c["i"], zT=c["zq"], vT=c["vq"], scale_exp=scale_exp)
    stats = SolveStats(n_steps=c["i"], n_trials=c["trials"], nfe=c["nfe"],
                       overflow=overflow, status=status)
    return ys_out, grid, stats


def batched_mali_adaptive_solve(
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: Tuple,
    rtol: float,
    atol: float,
    cfg: ControllerConfig,
    h0: Optional[jnp.ndarray] = None,
    guard_nonfinite: bool = True,
) -> Tuple[PyTree, MaliGrid, SolveStats]:
    """Per-sample batched MALI forward: ``odeint(..., batch_axis=0,
    grad_method="mali")``.

    One fused while_loop, one controller per batch element (the
    ``batched_adaptive_while_solve`` contract), with the integer-lattice
    pair carried per element on a per-element lattice (``scale_exp``
    (B,) — each element quantizes exactly as a solo solve of its row
    would).  Freezing differs from the RK engines: an h = 0
    ALF trial is *not* the identity in v (the reflection still fires),
    so rejected/finished elements are frozen purely by the accept mask —
    integer ``where`` keeps their pair bit-stable.  Per-element scalar
    grids feed the per-element backward inversion.
    """
    B = jax.tree.leaves(z0)[0].shape[0]
    rows = jnp.arange(B)
    n_eval = ts.shape[0]
    tdt = ts.dtype
    max_steps = cfg.max_steps
    max_total_trials = max_steps * cfg.max_trials
    targs = args

    fb0 = jax.vmap(lambda ti, zi: f(ti, zi, *targs))
    v0 = fb0(jnp.full((B,), ts[0], tdt), z0)
    scale_exp = alf_lattice_exponent_batched(z0, v0)     # (B,)
    zq0 = lattice_encode(z0, scale_exp)
    vq0 = lattice_encode(v0, scale_exp)

    row_tol = _row_tolerances(rtol, atol, B)
    hinit_evals = 2 if h0 is None else 0  # hinit costs 2 f-evals per elt
    if h0 is None:
        if row_tol is not None:
            h0 = jax.vmap(lambda z, rt, at: initial_stepsize(
                f, ts[0], z, targs, ALF_ORDER, rt, at))(z0, *row_tol)
        else:
            h0 = jax.vmap(lambda z: initial_stepsize(
                f, ts[0], z, targs, ALF_ORDER, rtol, atol))(z0)
    h0 = jnp.broadcast_to(jnp.asarray(h0, tdt), (B,))

    ys = _buffer_set(_empty_buffer(z0, n_eval), 0, z0)

    failed0 = _nonfinite_rows((z0, v0, h0)) if guard_nonfinite \
        else jnp.zeros((B,), bool)

    carry0 = dict(
        t=jnp.full((B,), ts[0], tdt), zq=zq0, vq=vq0, h=h0,
        prev_ratio=jnp.ones((B,), jnp.float32),
        i=jnp.zeros((B,), jnp.int32),
        eval_idx=jnp.ones((B,), jnp.int32),
        trials=jnp.zeros((B,), jnp.int32),
        nfe=jnp.full((B,), 1 + hinit_evals, jnp.int32),
        failed=failed0, uflow=jnp.zeros((B,), bool),
        ys=ys,
        grid_t=jnp.zeros((B, max_steps), tdt),
        grid_h=jnp.zeros((B, max_steps), tdt),
        grid_oi=jnp.full((B, max_steps), -1, jnp.int32),
    )

    tiny = jnp.asarray(jnp.finfo(tdt).eps, tdt)

    def live_mask(c):
        return (
            (c["eval_idx"] < n_eval)
            & (c["i"] < max_steps)
            & (c["trials"] < max_total_trials)
            & ~c["failed"]
        )

    def cond(c):
        return jnp.any(live_mask(c))

    def body(c):
        live = live_mask(c)
        t, h = c["t"], c["h"]
        t_target = ts[jnp.minimum(c["eval_idx"], n_eval - 1)]     # (B,)
        h_min = 16.0 * tiny * jnp.maximum(jnp.abs(t), jnp.asarray(1.0, tdt))
        h_use = jnp.where(live, jnp.clip(h, h_min, t_target - t),
                          jnp.zeros((), tdt))
        res = alf_step_batched(f, t, h_use, c["zq"], c["vq"], scale_exp,
                               z0, targs)
        z_f = lattice_decode(c["zq"], scale_exp, z0)
        if row_tol is not None:
            ratio = jax.vmap(error_ratio)(
                res.err, z_f, res.z_next, *row_tol)               # (B,)
        else:
            ratio = jax.vmap(
                lambda e, a, b: error_ratio(e, a, b, rtol, atol))(
                    res.err, z_f, res.z_next)                     # (B,)
        railed = h_use <= h_min * (1 + 1e-3)
        if guard_nonfinite:
            # per-row ratio read (see mali_adaptive_solve: the decoded
            # lattice state can't carry the NaN, res.err does)
            bad = ~jnp.isfinite(ratio)
            accept = live & ((ratio <= 1.0) | railed) & ~bad
        else:
            bad = jnp.zeros((B,), bool)
            accept = live & ((ratio <= 1.0) | railed)
        fail_now = live & bad & railed
        uflow_now = accept & railed & (ratio > 1.0)

        t_new = t + h_use
        hit = accept & (t_new >= t_target - 16.0 * tiny * jnp.maximum(
            jnp.abs(t_target), jnp.asarray(1.0, tdt)))

        # --- on accept: record each element's scalar grid row ----------
        i_c = jnp.minimum(c["i"], max_steps - 1)
        grid_t = c["grid_t"].at[rows, i_c].set(
            jnp.where(accept, t, c["grid_t"][rows, i_c]))
        grid_h = c["grid_h"].at[rows, i_c].set(
            jnp.where(accept, h_use, c["grid_h"][rows, i_c]))
        oi_val = jnp.where(hit, c["eval_idx"], jnp.full((B,), -1,
                                                        jnp.int32))
        grid_oi = c["grid_oi"].at[rows, i_c].set(
            jnp.where(accept, oi_val, c["grid_oi"][rows, i_c]))

        # --- on eval-time hit: record that element's decoded output ----
        e_c = jnp.minimum(c["eval_idx"], n_eval - 1)
        ys = jax.tree.map(
            lambda b, v: b.at[e_c, rows].set(_bwhere(hit, v, b[e_c, rows])),
            c["ys"], res.z_next)

        ratio_c = jnp.where(bad, jnp.asarray(1e10, jnp.float32), ratio)
        h_next = jnp.asarray(propose_stepsize(
            cfg, h_use, ratio_c, c["prev_ratio"], ALF_ORDER), tdt)

        return dict(
            t=jnp.where(accept, t_new, t),
            zq=_bwhere_tree(accept, res.zq_next, c["zq"]),
            vq=_bwhere_tree(accept, res.vq_next, c["vq"]),
            h=jnp.where(live, h_next, h),
            prev_ratio=jnp.where(
                accept, jnp.maximum(ratio, 1e-10), c["prev_ratio"]),
            i=c["i"] + accept.astype(jnp.int32),
            eval_idx=c["eval_idx"] + hit.astype(jnp.int32),
            trials=c["trials"] + live.astype(jnp.int32),
            nfe=c["nfe"] + live.astype(jnp.int32),
            failed=c["failed"] | fail_now,
            uflow=c["uflow"] | uflow_now,
            ys=ys, grid_t=grid_t, grid_h=grid_h, grid_oi=grid_oi,
        )

    c = jax.lax.while_loop(cond, body, carry0)

    overflow = c["eval_idx"] < n_eval
    status = _compose_status(c["failed"], c["uflow"], ~overflow,
                             c["trials"] >= max_total_trials)
    karr = jnp.arange(n_eval)
    fill = c["failed"][None, :] & (karr[:, None] >= c["eval_idx"][None, :])
    ys_out = _freeze_fill(c["ys"], fill,
                          lattice_decode(c["zq"], scale_exp, z0))
    grid = MaliGrid(t=c["grid_t"], h=c["grid_h"], out_idx=c["grid_oi"],
                    n=c["i"], zT=c["zq"], vT=c["vq"], scale_exp=scale_exp)
    stats = SolveStats(n_steps=c["i"], n_trials=c["trials"], nfe=c["nfe"],
                       overflow=overflow, status=status)
    return ys_out, grid, stats
