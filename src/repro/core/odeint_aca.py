"""Adaptive Checkpoint Adjoint (ACA) — the paper's contribution, in JAX.

Forward pass (paper Algorithm 2 / Appendix A):
  * integrate with the adaptive solver (``adaptive_while_solve``); the
    stepsize search happens inside a ``lax.while_loop`` and is therefore
    *structurally* excluded from differentiation — the JAX realization of
    "delete local computation graphs to search for optimal stepsize";
  * keep only the accepted discretization points {t_i}, stepsizes
    {h_i = t_{i+1} - t_i} and states {z_i} in a fixed-capacity trajectory
    checkpoint buffer:  memory O(N_f + N_t).

Backward pass:
  * initialize λ(T) = ∂J/∂z(T)  (Eq. 6; we carry +∂J/∂z, the sign
    convention of Appendix A's  λ = -∂J/∂z(T)  is folded into the update);
  * walk the saved grid in reverse; for each interval re-take ONE local
    step ψ(t_i, z_i, h_i) with the saved stepsize (no search — the paper's
    "m+1"-th evaluation), back-propagate through it with ``jax.vjp``, and
    update λ and dL/dθ (discretized Eq. 7 / Eq. 8);
  * the local graph is freed after each step: depth O(N_f), total
    computation O(N_f · N_t · (m+1)).

Because the reverse sweep replays the *forward* trajectory exactly, the
gradient equals the true gradient of the numerical solution
(discretize-then-optimize) — no reverse-time re-integration error
(Theorem 3.2's e_k pathology does not arise).

Memory-bounded mode (``checkpoint_segments=K``): the forward keeps only
K coarse state snapshots (the scalar grid still covers every step) and
the backward re-integrates each segment from its snapshot with the
*saved* stepsizes before replaying it in reverse — state memory drops
from O(N_f) to O(K + N_f/K) at ~1 extra ψ per step, with gradients
bit-identical to the full buffer (no re-search, so the replayed
trajectory is the forward trajectory).  See ``docs/memory.md``.

Sharding contract (relied on by ``odeint(..., mesh=...)``): the batched
engine's forward search, checkpoint buffer and backward replay touch
each batch row independently — no cross-element reduction anywhere —
so a batch shard replays **shard-local** under ``shard_map`` and the
only cross-device traffic is the psum of the shared-``args`` cotangent
inserted by the transpose.  See ``docs/distributed.md``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .controller import ControllerConfig
from .integrate import (
    CKPT_WRITE_SCOPE,
    Checkpoints,
    SolveStats,
    SolveStatus,
    _as_tuple,
    _buffer_slot,
    _bwhere,
    _mask_failed_cotangents,
    _nonfinite_any,
    adaptive_while_solve,
    batched_adaptive_while_solve,
    make_fixed_grid,
    resolve_segmentation,
)
from .stepper import (
    field_eval,
    interp_eval,
    interp_fit,
    maybe_flatten,
    maybe_flatten_batched,
    rk_step,
    rk_step_batched,
)
from .tableaus import Tableau

PyTree = Any

# named scope of the whole ACA backward sweep (segment re-integration,
# reverse replay and its buffer reads): every op the custom_vjp's
# backward rule builds carries it in its HLO op_name
ACA_BACKWARD_SCOPE = "ode_aca_backward"


def _local_step_dense(tab, f, t_i, h_i, z_i, a, ts, use_pallas):
    """Replay one ψ with the saved stepsize AND rebuild its interpolant,
    evaluated at every eval time (natural-grid ACA backward).

    Returns (z_next, y_all) with ``y_all`` leaves (n_eval, ...): the
    interval's interpolant read at all of ``ts`` (θ clipped to [0, 1];
    out-of-interval slots get masked-zero cotangents by the caller, so
    their values are irrelevant but finite).  The recomputed k0/k1 are
    bit-identical to the forward's FSAL-chained carries, so the
    interpolant equals the forward interpolant bitwise.
    """
    targs = _as_tuple(a)
    res = rk_step(tab, f, t_i, z_i, h_i, targs, use_pallas=use_pallas,
                  dense=True)
    if tab.fsal:
        k1 = res.k_last
    else:
        k1 = field_eval(f, t_i + h_i, res.z_next, *targs)
    coeffs = interp_fit(z_i, res.z_next, res.k_first, k1, h_i, res.z_mid)
    tiny = jnp.asarray(jnp.finfo(ts.dtype).eps, ts.dtype)
    theta = jnp.clip((ts - t_i) / jnp.maximum(h_i, tiny), 0.0, 1.0)
    return res.z_next, interp_eval(coeffs, theta)


def _mask_cotangents(g_ys: PyTree, mask: jnp.ndarray) -> PyTree:
    """Zero every g_ys slot outside ``mask`` (mask aligns with the
    leading eval axis — or (n_eval, B) for batched cotangents)."""
    return jax.tree.map(
        lambda g: jnp.where(
            mask.reshape(mask.shape + (1,) * (g.ndim - mask.ndim)),
            g, jnp.zeros((), g.dtype)),
        g_ys)


def _aca_backward_sweep(
    tab: Tableau,
    f: Callable,
    ckpts: Checkpoints,
    args: PyTree,
    g_ys: PyTree,
    n_steps,
    use_pallas: bool = False,
    ts: Optional[jnp.ndarray] = None,
):
    """Reverse sweep over the trajectory checkpoints.

    Returns (dL/dz0, dL/dargs).  ``g_ys`` are the output cotangents, one
    slot per eval time (g_ys[k] injected into λ when the sweep crosses
    eval time ts[k]).  ``use_pallas`` replays each local ψ through the
    fused flat-state kernels (their custom_vjp makes them legal under
    the jax.vjp below).

    Natural-grid checkpoints (``ckpts.ev_lo`` present; requires ``ts``)
    additionally route the cotangents of *interpolated* outputs through
    each interval's rebuilt interpolant: the local vjp differentiates
    (z_i, args) ↦ (z_next, interpolated y's), with g_ys masked to the
    interval's recorded [ev_lo, ev_hi) eval range.
    """
    interp = ckpts.ev_lo is not None

    def local_step(t_i, h_i, z_i, a):
        # one ψ with the SAVED stepsize; k0 recomputed so its gradient flows
        return rk_step(tab, f, t_i, z_i, h_i, _as_tuple(a),
                       use_pallas=use_pallas).z_next

    lam0 = jax.tree.map(jnp.zeros_like, _buffer_slot(g_ys, 0))
    gargs0 = jax.tree.map(jnp.zeros_like, args)
    karr = jnp.arange(jax.tree.leaves(g_ys)[0].shape[0])

    def body(j, carry):
        lam, gargs = carry
        i = n_steps - 1 - j
        t_i = ckpts.t[i]
        h_i = ckpts.h[i]
        z_i = jax.tree.map(lambda b: b[i], ckpts.z)
        oi = ckpts.out_idx[i]

        # inject the cotangent of any output that lands on this interval's
        # endpoint:  λ(t_{i+1}) += ∂J/∂y_k
        def add_out(lam):
            g_k = jax.tree.map(lambda g: g[oi], g_ys)
            return jax.tree.map(jnp.add, lam, g_k)

        lam = jax.lax.cond(oi >= 0, add_out, lambda l: l, lam)

        # local forward + local backward (paper Algorithm 2, backward-pass)
        if interp:
            mask = (karr >= ckpts.ev_lo[i]) & (karr < ckpts.ev_hi[i])
            _, vjp_fn = jax.vjp(
                lambda z, a: _local_step_dense(tab, f, t_i, h_i, z, a,
                                               ts, use_pallas), z_i, args)
            dlam, dargs = vjp_fn((lam, _mask_cotangents(g_ys, mask)))
        else:
            _, vjp_fn = jax.vjp(lambda z, a: local_step(t_i, h_i, z, a),
                                z_i, args)
            dlam, dargs = vjp_fn(lam)
        gargs = jax.tree.map(jnp.add, gargs, dargs)
        return (dlam, gargs)

    lam, gargs = jax.lax.fori_loop(0, n_steps, body, (lam0, gargs0))
    # cotangent of ys[0] = z0 (identity path)
    lam = jax.tree.map(lambda l, g: l + g[0], lam, g_ys)
    return lam, gargs


def _aca_backward_sweep_segmented(
    tab: Tableau,
    f: Callable,
    ckpts: Checkpoints,
    args: PyTree,
    g_ys: PyTree,
    n_steps,
    seg_len: int,
    use_pallas: bool = False,
    ts: Optional[jnp.ndarray] = None,
):
    """Segmented (O(K)-state) reverse sweep: ``checkpoint_segments=K``.

    ``ckpts.z`` holds only K coarse snapshots (slot s = state at
    accepted step ``s * seg_len``, with the matching first-stage
    derivative carry in ``ckpts.k0``); the scalar grids ``t``/``h``/
    ``out_idx`` still cover every accepted step.  Walking segments last
    to first, each segment is first re-integrated forward from its
    snapshot with the *saved* stepsizes and re-chained FSAL first-stage
    reuse (no stepsize search, same k0 carry — replayed ψ steps are
    bit-identical to the forward solve, so the discretize-then-optimize
    gradient is bit-identical to the full-buffer sweep), filling a
    ``seg_len``-slot local state buffer; then its local ψ steps are
    replayed in reverse exactly as in ``_aca_backward_sweep``.  Peak
    state memory is O(K + seg_len) = O(K + N_f/K) instead of O(N_f),
    for one extra ψ per accepted step.

    Natural-grid checkpoints (``ckpts.ev_lo`` present; requires ``ts``)
    route interpolated-output cotangents through each replayed
    interval's interpolant, exactly as in ``_aca_backward_sweep``.

    Returns (dL/dz0, dL/dargs).
    """
    interp = ckpts.ev_lo is not None

    def local_step(t_i, h_i, z_i, a):
        # one ψ with the SAVED stepsize; k0 recomputed so its gradient flows
        return rk_step(tab, f, t_i, z_i, h_i, _as_tuple(a),
                       use_pallas=use_pallas).z_next

    lam0 = jax.tree.map(jnp.zeros_like, _buffer_slot(g_ys, 0))
    gargs0 = jax.tree.map(jnp.zeros_like, args)
    karr = jnp.arange(jax.tree.leaves(g_ys)[0].shape[0])
    # the O(seg_len) replay buffer — the N_f/K term of the cost model
    zbuf0 = jax.tree.map(
        lambda b: jnp.zeros((seg_len,) + b.shape[1:], b.dtype), ckpts.z)
    n_segments = (n_steps + seg_len - 1) // seg_len
    targs = _as_tuple(args)

    def seg_body(jseg, carry):
        lam, gargs = carry
        s = n_segments - 1 - jseg
        i0 = s * seg_len
        i1 = jnp.minimum(i0 + seg_len, n_steps)
        cnt = i1 - i0

        # --- forward re-integration of segment s from its snapshot ----
        # the k0 carry chains exactly as in adaptive_while_solve (FSAL
        # reuse / post-accept recompute), so every replayed state is the
        # forward state bitwise
        z_start = _buffer_slot(ckpts.z, s)
        k0_start = _buffer_slot(ckpts.k0, s)

        def fwd_body(q, zc):
            z, k0, zbuf = zc
            i = i0 + q
            t_i, h_i = ckpts.t[i], ckpts.h[i]
            with jax.named_scope(CKPT_WRITE_SCOPE):
                zbuf = jax.tree.map(lambda b, v: b.at[q].set(v), zbuf, z)
            res = rk_step(tab, f, t_i, z, h_i, targs, k0=k0,
                          use_pallas=use_pallas)
            if tab.fsal:
                k0_new = res.k_last
            else:
                k0_new = field_eval(f, t_i + h_i, res.z_next, *targs)
            return (res.z_next, k0_new, zbuf)

        _, _, zbuf = jax.lax.fori_loop(
            0, cnt, fwd_body, (z_start, k0_start, zbuf0))

        # --- reverse replay of the segment's local ψ steps ------------
        def rev_body(r, carry):
            lam, gargs = carry
            i = i1 - 1 - r
            t_i = ckpts.t[i]
            h_i = ckpts.h[i]
            z_i = _buffer_slot(zbuf, i - i0)
            oi = ckpts.out_idx[i]

            def add_out(lam):
                g_k = jax.tree.map(lambda g: g[oi], g_ys)
                return jax.tree.map(jnp.add, lam, g_k)

            lam = jax.lax.cond(oi >= 0, add_out, lambda l: l, lam)
            if interp:
                mask = (karr >= ckpts.ev_lo[i]) & (karr < ckpts.ev_hi[i])
                _, vjp_fn = jax.vjp(
                    lambda z, a: _local_step_dense(tab, f, t_i, h_i, z,
                                                   a, ts, use_pallas),
                    z_i, args)
                dlam, dargs = vjp_fn((lam, _mask_cotangents(g_ys, mask)))
            else:
                _, vjp_fn = jax.vjp(
                    lambda z, a: local_step(t_i, h_i, z, a), z_i, args)
                dlam, dargs = vjp_fn(lam)
            gargs = jax.tree.map(jnp.add, gargs, dargs)
            return (dlam, gargs)

        return jax.lax.fori_loop(0, cnt, rev_body, (lam, gargs))

    lam, gargs = jax.lax.fori_loop(0, n_segments, seg_body, (lam0, gargs0))
    # cotangent of ys[0] = z0 (identity path)
    lam = jax.tree.map(lambda l, g: l + g[0], lam, g_ys)
    return lam, gargs


def _local_step_dense_batched(tab, f, t_i, h_i, z_i, a, ts, use_pallas):
    """Batched twin of ``_local_step_dense``: per-row saved stepsizes,
    returns (z_next (B, ...), y_all (n_eval, B, ...)).  Frozen rows
    (h = 0) produce finite garbage interpolants whose cotangents the
    caller masks to zero."""
    targs = _as_tuple(a)
    res = rk_step_batched(tab, f, t_i, z_i, h_i, targs,
                          use_pallas=use_pallas, dense=True)
    if tab.fsal:
        k1 = res.k_last
    else:
        k1 = jax.vmap(lambda ti, zi: field_eval(f, ti, zi, *targs))(
            t_i + h_i, res.z_next)
    coeffs = interp_fit(z_i, res.z_next, res.k_first, k1, h_i, res.z_mid)
    tiny = jnp.asarray(jnp.finfo(ts.dtype).eps, ts.dtype)
    theta = jnp.clip(
        (ts[:, None] - t_i[None, :])
        / jnp.maximum(h_i, tiny)[None, :], 0.0, 1.0)    # (n_eval, B)
    return res.z_next, interp_eval(coeffs, theta)


def _aca_backward_sweep_batched(
    tab: Tableau,
    f: Callable,
    ckpts: Checkpoints,
    args: PyTree,
    g_ys: PyTree,
    n_steps,
    use_pallas: bool = False,
    ts: Optional[jnp.ndarray] = None,
):
    """Per-element reverse sweep: each batch element replays *its own*
    accepted checkpoint grid.

    ``ckpts`` rows are per element (t/h/out_idx (B, S), z (B, S, ...),
    n (B,)); ``g_ys`` leaves are (n_eval, B, ...).  The shared
    ``fori_loop`` runs max(n_steps) iterations; element b replays slot
    n_b - 1 - j at iteration j and is frozen with h = 0 once j ≥ n_b —
    the h = 0 local ψ is the exact identity in z (and contributes a zero
    cotangent to args), so short trajectories finish early without
    touching their λ.  Returns (dL/dz0 (B, ...), dL/dargs summed over
    the batch — args are shared).

    Natural-grid checkpoints (``ckpts.ev_lo`` present; requires ``ts``)
    route interpolated-output cotangents through each element's rebuilt
    per-interval interpolant, masked to that element's recorded
    [ev_lo, ev_hi) eval range.
    """
    B = n_steps.shape[0]
    rows = jnp.arange(B)
    interp = ckpts.ev_lo is not None

    def local_step(t_i, h_i, z_i, a):
        # one batched ψ with each element's SAVED stepsize (no search);
        # k0 recomputed so its gradient flows
        return rk_step_batched(tab, f, t_i, z_i, h_i, _as_tuple(a),
                               use_pallas=use_pallas).z_next

    lam0 = jax.tree.map(jnp.zeros_like, _buffer_slot(g_ys, 0))  # (B, ...)
    gargs0 = jax.tree.map(jnp.zeros_like, args)
    n_max = jnp.max(n_steps)
    karr = jnp.arange(jax.tree.leaves(g_ys)[0].shape[0])

    def body(j, carry):
        lam, gargs = carry
        i = n_steps - 1 - j                  # (B,), negative when done
        live = i >= 0
        i_c = jnp.maximum(i, 0)
        t_i = ckpts.t[rows, i_c]
        h_i = jnp.where(live, ckpts.h[rows, i_c],
                        jnp.zeros((), ckpts.h.dtype))
        z_i = jax.tree.map(lambda b: b[rows, i_c], ckpts.z)
        oi = jnp.where(live, ckpts.out_idx[rows, i_c], -1)

        # inject each element's output cotangent where its interval's
        # endpoint landed on an eval time:  λ_b(t_{i+1}) += ∂J/∂y_{oi_b}
        oi_c = jnp.maximum(oi, 0)
        lam = jax.tree.map(
            lambda l, g: l + jnp.where(
                (oi >= 0).reshape((-1,) + (1,) * (l.ndim - 1)),
                g[oi_c, rows], jnp.zeros_like(l)),
            lam, g_ys)

        # batched local forward + local backward; frozen rows are the
        # identity, so dlam == lam and dargs == 0 for them exactly
        if interp:
            mask = (live[None, :]
                    & (karr[:, None] >= ckpts.ev_lo[rows, i_c][None, :])
                    & (karr[:, None] < ckpts.ev_hi[rows, i_c][None, :]))
            _, vjp_fn = jax.vjp(
                lambda z, a: _local_step_dense_batched(
                    tab, f, t_i, h_i, z, a, ts, use_pallas), z_i, args)
            dlam, dargs = vjp_fn((lam, _mask_cotangents(g_ys, mask)))
        else:
            _, vjp_fn = jax.vjp(lambda z, a: local_step(t_i, h_i, z, a),
                                z_i, args)
            dlam, dargs = vjp_fn(lam)
        gargs = jax.tree.map(jnp.add, gargs, dargs)
        return (dlam, gargs)

    lam, gargs = jax.lax.fori_loop(0, n_max, body, (lam0, gargs0))
    # cotangent of ys[0] = z0 (identity path)
    lam = jax.tree.map(lambda l, g: l + g[0], lam, g_ys)
    return lam, gargs


def _aca_backward_sweep_segmented_batched(
    tab: Tableau,
    f: Callable,
    ckpts: Checkpoints,
    args: PyTree,
    g_ys: PyTree,
    n_steps,
    seg_len: int,
    use_pallas: bool = False,
    ts: Optional[jnp.ndarray] = None,
):
    """Batched segmented reverse sweep (``checkpoint_segments`` +
    ``batch_axis``).

    Elements record different step counts n_b, so their segment
    boundaries don't align.  To keep the gradient *bit-identical* to the
    full-buffer batched sweep, the replay windows are **end-aligned per
    element**: at global reverse iteration J = j·seg_len + r, element b
    replays its step n_b − 1 − J — exactly the pairing (and therefore
    the cross-batch dargs summation order) of
    ``_aca_backward_sweep_batched``.  Every ``seg_len`` iterations each
    element refills its local state buffer by re-integrating from the
    nearest *start-aligned* snapshot at or before its window (≤ 2·seg_len
    saved-stepsize ψ steps, since a window can straddle one snapshot
    stride), with finished elements frozen at h = 0 as usual.  Peak
    state memory O(B · (K + seg_len)); the re-integration costs at most
    2 ψ per accepted step.

    Natural-grid checkpoints (``ckpts.ev_lo`` present; requires ``ts``)
    route interpolated-output cotangents through each element's rebuilt
    per-interval interpolant, as in ``_aca_backward_sweep_batched``.

    Returns (dL/dz0 (B, ...), dL/dargs summed over the batch).
    """
    B = n_steps.shape[0]
    rows = jnp.arange(B)
    S = ckpts.t.shape[1]
    n_snap = jax.tree.leaves(ckpts.z)[0].shape[1]
    hdt = ckpts.h.dtype
    interp = ckpts.ev_lo is not None
    karr = jnp.arange(jax.tree.leaves(g_ys)[0].shape[0])

    def local_step(t_i, h_i, z_i, a):
        # one batched ψ with each element's SAVED stepsize (no search);
        # k0 recomputed so its gradient flows
        return rk_step_batched(tab, f, t_i, z_i, h_i, _as_tuple(a),
                               use_pallas=use_pallas).z_next

    lam0 = jax.tree.map(jnp.zeros_like, _buffer_slot(g_ys, 0))  # (B, ...)
    gargs0 = jax.tree.map(jnp.zeros_like, args)
    zbuf0 = jax.tree.map(
        lambda b: jnp.zeros((B, seg_len) + b.shape[2:], b.dtype), ckpts.z)
    n_max = jnp.max(n_steps)
    n_outer = (n_max + seg_len - 1) // seg_len
    targs = _as_tuple(args)

    def outer(j, carry):
        lam, gargs = carry
        g_hi = n_steps - j * seg_len             # (B,) window end (excl.)
        g_lo = jnp.maximum(g_hi - seg_len, 0)    # (B,) window start

        # --- refill: re-integrate [snapshot .. g_hi) per element ------
        # the k0 carry chains exactly as in batched_adaptive_while_solve
        # (FSAL reuse / post-accept recompute), so every replayed state
        # is that element's forward state bitwise
        s = jnp.clip(g_lo // seg_len, 0, n_snap - 1)
        a0 = s * seg_len                         # snapshot's global step
        z = jax.tree.map(lambda b: b[rows, s], ckpts.z)
        k0 = jax.tree.map(lambda b: b[rows, s], ckpts.k0)

        def fwd_body(q, zc):
            z, k0, zbuf = zc
            i = a0 + q                           # (B,)
            live = i < g_hi                      # done rows: g_hi <= 0
            i_c = jnp.minimum(i, S - 1)
            t_i = ckpts.t[rows, i_c]
            h_i = jnp.where(live, ckpts.h[rows, i_c], jnp.zeros((), hdt))
            in_win = live & (i >= g_lo)
            slot = jnp.clip(i - g_lo, 0, seg_len - 1)
            with jax.named_scope(CKPT_WRITE_SCOPE):
                zbuf = jax.tree.map(
                    lambda b, v: b.at[rows, slot].set(
                        _bwhere(in_win, v, b[rows, slot])), zbuf, z)
            # h = 0 makes ψ the exact identity for rows outside their
            # window, so the carry stays bit-stable without extra masking
            res = rk_step_batched(tab, f, t_i, z, h_i, targs, k0=k0,
                                  use_pallas=use_pallas)
            if tab.fsal:
                k0_new = res.k_last
            else:
                k0_new = jax.vmap(
                    lambda ti, zi: field_eval(f, ti, zi, *targs))(
                        t_i + h_i, res.z_next)
            return (res.z_next, k0_new, zbuf)

        _, _, zbuf = jax.lax.fori_loop(0, 2 * seg_len, fwd_body,
                                       (z, k0, zbuf0))

        # --- reverse replay, global iteration J = j*seg_len + r -------
        def rev_body(r, carry):
            lam, gargs = carry
            i = n_steps - 1 - (j * seg_len + r)  # (B,), < 0 when done
            live = i >= 0
            i_c = jnp.maximum(i, 0)
            t_i = ckpts.t[rows, i_c]
            h_i = jnp.where(live, ckpts.h[rows, i_c], jnp.zeros((), hdt))
            slot = jnp.clip(i - g_lo, 0, seg_len - 1)
            z_i = jax.tree.map(lambda b: b[rows, slot], zbuf)
            oi = jnp.where(live, ckpts.out_idx[rows, i_c], -1)

            oi_c = jnp.maximum(oi, 0)
            lam = jax.tree.map(
                lambda l, g: l + jnp.where(
                    (oi >= 0).reshape((-1,) + (1,) * (l.ndim - 1)),
                    g[oi_c, rows], jnp.zeros_like(l)),
                lam, g_ys)

            if interp:
                mask = (live[None, :]
                        & (karr[:, None]
                           >= ckpts.ev_lo[rows, i_c][None, :])
                        & (karr[:, None]
                           < ckpts.ev_hi[rows, i_c][None, :]))
                _, vjp_fn = jax.vjp(
                    lambda z, a: _local_step_dense_batched(
                        tab, f, t_i, h_i, z, a, ts, use_pallas),
                    z_i, args)
                dlam, dargs = vjp_fn((lam, _mask_cotangents(g_ys, mask)))
            else:
                _, vjp_fn = jax.vjp(
                    lambda z, a: local_step(t_i, h_i, z, a), z_i, args)
                dlam, dargs = vjp_fn(lam)
            # all-frozen trailing iterations leave gargs bit-untouched
            any_live = jnp.any(live)
            gargs = jax.tree.map(
                lambda g, d: jnp.where(any_live, g + d, g), gargs, dargs)
            return (dlam, gargs)

        return jax.lax.fori_loop(0, seg_len, rev_body, (lam, gargs))

    lam, gargs = jax.lax.fori_loop(0, n_outer, outer, (lam0, gargs0))
    # cotangent of ys[0] = z0 (identity path)
    lam = jax.tree.map(lambda l, g: l + g[0], lam, g_ys)
    return lam, gargs


def odeint_aca_batched(
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: PyTree = (),
    *,
    solver: Tableau,
    rtol: float = 1e-6,
    atol: float = 1e-6,
    cfg: Optional[ControllerConfig] = None,
    h0: Optional[jnp.ndarray] = None,
    use_pallas: bool = False,
    checkpoint_segments=None,
    interpolate_ts: bool = False,
) -> Tuple[PyTree, SolveStats]:
    """Per-sample batched ACA: ``odeint(..., batch_axis=0)``'s adaptive
    ACA path.

    ``z0`` leaves carry a leading batch dim B and ``f`` is the
    per-sample vector field.  Forward: ``batched_adaptive_while_solve``
    — every element records its own checkpoint grid.  Backward: each
    element's grid is replayed in reverse (``_aca_backward_sweep_batched``),
    so the per-element discretize-then-optimize property of ACA is
    preserved exactly — gradients match ``jax.vmap`` of the unbatched
    solver.  Returns (ys, stats) with ys leaves (len(ts), B, ...) and
    per-element stats.

    ``checkpoint_segments`` (int, ``"auto"`` or None) bounds per-element
    state memory to K snapshots + one seg_len replay buffer; the
    end-aligned segmented sweep keeps gradients bit-identical to the
    full buffer (see ``_aca_backward_sweep_segmented_batched``).

    ``interpolate_ts`` advances every element on its own natural grid
    and reads interior eval times off per-step interpolants; the
    backward sweeps route those outputs' cotangents through the rebuilt
    interpolants (see ``odeint_aca``).
    """
    if cfg is None:
        cfg = ControllerConfig()
    if not solver.adaptive:
        raise ValueError(
            "odeint_aca_batched requires an embedded adaptive tableau; "
            "fixed-grid solvers batch losslessly through odeint_aca_fixed")
    n_seg, seg_len = resolve_segmentation(checkpoint_segments,
                                          cfg.max_steps)

    f, z0, unravel, use_pallas = maybe_flatten_batched(f, z0, use_pallas)

    @jax.custom_vjp
    def solve(z0, args, ts):
        ys, _, stats = batched_adaptive_while_solve(
            solver, f, z0, ts, _as_tuple(args), rtol, atol, cfg,
            h0=h0, use_pallas=use_pallas, checkpoint_segments=n_seg,
            interpolate_ts=interpolate_ts)
        return ys, stats

    def solve_fwd(z0, args, ts):
        ys, ckpts, stats = batched_adaptive_while_solve(
            solver, f, z0, ts, _as_tuple(args), rtol, atol, cfg,
            h0=h0, use_pallas=use_pallas, checkpoint_segments=n_seg,
            interpolate_ts=interpolate_ts)
        return (ys, stats), (ckpts, args, ts, stats.status)

    @jax.named_scope(ACA_BACKWARD_SCOPE)
    def solve_bwd(res, cot):
        ckpts, args, ts, status = res
        g_ys, _g_stats = cot  # stats are integer outputs; cotangent ignored
        # failed elements: frozen placeholder outputs carry no gradient
        g_ys = _mask_failed_cotangents(g_ys, status, batched=True)
        if n_seg is None:
            dz0, dargs = _aca_backward_sweep_batched(
                solver, f, ckpts, args, g_ys, ckpts.n,
                use_pallas=use_pallas, ts=ts)
        else:
            dz0, dargs = _aca_backward_sweep_segmented_batched(
                solver, f, ckpts, args, g_ys, ckpts.n, seg_len,
                use_pallas=use_pallas, ts=ts)
        return dz0, dargs, jnp.zeros_like(ts)

    solve.defvjp(solve_fwd, solve_bwd)
    ys, stats = solve(z0, args, ts)
    if unravel is not None:
        ys = jax.vmap(jax.vmap(unravel))(ys)
    return ys, stats


def odeint_aca(
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: PyTree = (),
    *,
    solver: Tableau,
    rtol: float = 1e-6,
    atol: float = 1e-6,
    cfg: Optional[ControllerConfig] = None,
    h0: Optional[jnp.ndarray] = None,
    use_pallas: bool = False,
    checkpoint_segments=None,
    interpolate_ts: bool = False,
) -> Tuple[PyTree, SolveStats]:
    """Solve dz/dt = f(t, z, *args) with ACA gradients.

    Returns (ys, stats) with ys stacked over ``ts`` (ys[0] = z0).
    Differentiable w.r.t. ``z0`` and ``args``; ``ts`` is treated as
    constant (the paper differentiates neither t nor the accepted h).

    ``use_pallas`` ravels the state once per solve and runs the trial
    loop, the checkpoint buffer and the backward replay on the fused
    flat-state kernel path; the ravel/unravel sit *outside* the
    custom_vjp so cotangents flow through them as plain jnp reshapes.

    ``checkpoint_segments`` (int K, ``"auto"`` or None) bounds the state
    checkpoint memory: the forward stores K snapshots instead of every
    accepted state and the backward re-integrates each segment from its
    snapshot with the saved stepsizes before replaying it — gradients
    are bit-identical to the full buffer at ~1 extra ψ per step (see
    ``docs/memory.md``).

    ``interpolate_ts`` advances on the controller's natural grid and
    reads interior eval times off each accepted step's interpolant
    (``stepper.interp_fit``) instead of forcing step landings; the
    backward sweep replays each interval *and* its interpolant, so the
    gradient is still the exact discretize-then-optimize gradient of
    the interpolated solution map.  ``ys[0]``/``ys[-1]`` remain exact
    solver states.
    """
    if cfg is None:
        cfg = ControllerConfig()

    if not solver.adaptive:
        raise ValueError(
            "odeint_aca requires an embedded adaptive tableau; use "
            "odeint_aca_fixed for fixed-grid solvers")
    n_seg, seg_len = resolve_segmentation(checkpoint_segments,
                                          cfg.max_steps)

    f, z0, unravel, use_pallas = maybe_flatten(f, z0, use_pallas)

    # ``ts`` is threaded as an explicit custom_vjp argument (closures over
    # trace-time values are illegal inside scan/grad — e.g. NODE blocks
    # inside a scanned layer stack).
    @jax.custom_vjp
    def solve(z0, args, ts):
        ys, _, stats = adaptive_while_solve(
            solver, f, z0, ts, _as_tuple(args), rtol, atol, cfg, h0=h0,
            use_pallas=use_pallas, checkpoint_segments=n_seg,
            interpolate_ts=interpolate_ts)
        return ys, stats

    def solve_fwd(z0, args, ts):
        ys, ckpts, stats = adaptive_while_solve(
            solver, f, z0, ts, _as_tuple(args), rtol, atol, cfg, h0=h0,
            use_pallas=use_pallas, checkpoint_segments=n_seg,
            interpolate_ts=interpolate_ts)
        return (ys, stats), (ckpts, args, ts, stats.status)

    @jax.named_scope(ACA_BACKWARD_SCOPE)
    def solve_bwd(res, cot):
        ckpts, args, ts, status = res
        g_ys, _g_stats = cot  # stats are integer outputs; cotangent ignored
        # a frozen (NONFINITE_STATE) solve's placeholder outputs carry
        # no gradient: zero the cotangents before the replay sweep
        g_ys = _mask_failed_cotangents(g_ys, status)
        if n_seg is None:
            dz0, dargs = _aca_backward_sweep(
                solver, f, ckpts, args, g_ys, ckpts.n,
                use_pallas=use_pallas, ts=ts)
        else:
            dz0, dargs = _aca_backward_sweep_segmented(
                solver, f, ckpts, args, g_ys, ckpts.n, seg_len,
                use_pallas=use_pallas, ts=ts)
        return dz0, dargs, jnp.zeros_like(ts)

    solve.defvjp(solve_fwd, solve_bwd)
    ys, stats = solve(z0, args, ts)
    if unravel is not None:
        ys = jax.vmap(unravel)(ys)
    return ys, stats


def odeint_aca_fixed(
    f: Callable,
    z0: PyTree,
    ts: jnp.ndarray,
    args: PyTree = (),
    *,
    solver: Tableau,
    steps_per_interval: int = 8,
    use_pallas: bool = False,
) -> Tuple[PyTree, SolveStats]:
    """Fixed-grid ACA: checkpoint every grid state during the forward scan,
    replay one step at a time in the backward sweep.

    Versus naive AD through the scan this stores only {z_i} (not the stage
    intermediates), trading one extra ψ per step — the classic
    checkpoint-recompute profile, with the same discretize-then-optimize
    gradient.  Used by NODE-mode model stacks where a static step count is
    required for multi-pod lowering.  ``use_pallas`` as in ``odeint_aca``.
    """
    f, z0, unravel, use_pallas = maybe_flatten(f, z0, use_pallas)

    n_intervals = ts.shape[0] - 1
    n_steps = n_intervals * steps_per_interval
    # static (numpy!) index plans — a jnp array created here would be a
    # trace-local constant tracer and leak into the bwd closure
    out_idx = np.where(
        (np.arange(n_steps) + 1) % steps_per_interval == 0,
        (np.arange(n_steps) + 1) // steps_per_interval,
        -1).astype(np.int32)
    idx_clamped = np.minimum(
        np.arange(1, n_intervals + 1) * steps_per_interval, n_steps - 1)

    def _fwd(z0, args, t_grid, h_grid):
        def step_fn(z, th):
            t, h = th
            z_next = rk_step(solver, f, t, z, h, _as_tuple(args),
                             use_pallas=use_pallas).z_next
            return z_next, z  # checkpoint the START state of each step

        z_end, z_ckpt = jax.lax.scan(step_fn, z0, (t_grid, h_grid))
        # outputs at eval times: gather the step-start states of the steps
        # following each eval time + final state

        def gather(zc, zl_end, zl0):
            tail = zc[idx_clamped]
            tail = tail.at[-1].set(zl_end)
            return jnp.concatenate([zl0[None], tail], axis=0)

        ys = jax.tree.map(gather, z_ckpt, z_end, z0)
        return ys, z_ckpt

    # the time grid is threaded as an explicit custom_vjp argument
    # (closures over trace-time values are illegal under scan/grad)
    @jax.custom_vjp
    def solve(z0, args, t_grid, h_grid):
        ys, _ = _fwd(z0, args, t_grid, h_grid)
        return ys

    def solve_fwd(z0, args, t_grid, h_grid):
        ys, z_ckpt = _fwd(z0, args, t_grid, h_grid)
        return ys, (z_ckpt, args, t_grid, h_grid)

    @jax.named_scope(ACA_BACKWARD_SCOPE)
    def solve_bwd(res, g_ys):
        z_ckpt, args, t_grid, h_grid = res
        ckpts = Checkpoints(
            t=t_grid, h=h_grid, z=z_ckpt, out_idx=jnp.asarray(out_idx),
            n=jnp.asarray(n_steps, jnp.int32))
        dz0, dargs = _aca_backward_sweep(
            solver, f, ckpts, args, g_ys, n_steps, use_pallas=use_pallas)
        return dz0, dargs, jnp.zeros_like(t_grid), jnp.zeros_like(h_grid)

    solve.defvjp(solve_fwd, solve_bwd)
    t_grid, h_grid = make_fixed_grid(ts, steps_per_interval)
    ys = solve(z0, args, t_grid, h_grid)
    if unravel is not None:
        ys = jax.vmap(unravel)(ys)
    # fixed grids have no trial loop to guard: post-hoc finite check
    status = jnp.where(_nonfinite_any(jax.lax.stop_gradient(ys)),
                       SolveStatus.NONFINITE_STATE,
                       SolveStatus.OK).astype(jnp.int32)
    stats = SolveStats(
        n_steps=jnp.asarray(n_steps, jnp.int32),
        n_trials=jnp.asarray(n_steps, jnp.int32),
        nfe=jnp.asarray(n_steps * solver.stages, jnp.int32),
        overflow=jnp.asarray(False),
        status=status,
    )
    return ys, stats
