"""Generic explicit Runge-Kutta step  ψ_h(t, z)  over arbitrary pytrees.

One ``rk_step`` evaluates all stages of a tableau and returns the advanced
state plus (for embedded pairs) the local error estimate.  This is the ψ of
the paper's Algorithm 1; every gradient method (naive / adjoint / ACA) calls
the same stepper so forward trajectories are bit-identical across methods.

Two execution paths, selected per call:

* **Flat-array fast path** (``use_pallas=True`` *and* the state is a
  single 1-D inexact array): the stage accumulations  z + h·Σ a_ij k_j,
  the solution/error combine and — when ``err_scale=(rtol, atol)`` is
  given — the scaled error norm of ``error_ratio`` are each one fused
  Pallas kernel (``repro.kernels.rk_stage``), cutting the memory-bound
  traffic of the trial loop roughly in half.  The fused norm is returned
  as ``StepResult.err_ratio`` so the accept/reject loop skips its extra
  full-array pass.  The kernels are wrapped in custom_vjp (backward =
  the bit-matching jnp twin), so this path is differentiable and legal
  inside the ACA backward replay and the naive method's scan.
* **Pytree fallback** (default): pure ``jax.tree`` arithmetic over any
  state structure/dtype mix; ``err_ratio`` is None and callers compute
  ``error_ratio`` themselves.

``flatten_problem`` is the per-solve adapter: it ravels a pytree state
once (one ``ravel_pytree`` per solve, not per step), wraps the vector
field to operate on the flat vector, and hands back the unravel for the
outputs — solver loops then carry a single (N,) array, which also
shrinks the while_loop carry the checkpoint writer updates every trial.
States with mixed or non-inexact dtypes return None and stay on the
pytree path.

``rk_step_batched`` is the per-sample batched twin of ``rk_step`` for
``odeint(..., batch_axis=0)``: every leaf carries a leading batch dim B,
``t`` and ``h`` are (B,) — each element takes ψ with its *own* time and
trial stepsize — and ``err_ratio`` is (B,), one scaled error norm per
element.  ``maybe_flatten_batched`` is the matching fallback rule: the
fused path carries a (B, N) array through the batched Pallas kernels.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from .tableaus import Tableau

PyTree = Any
VecField = Callable[..., PyTree]  # f(t, z, *args) -> dz/dt


# named scope of every vector-field evaluation of the RK solvers: the
# field's ops carry it in their HLO op_name, and so do their transposes
# when the ACA backward sweep differentiates a replayed step
FIELD_SCOPE = "ode_field"


def field_eval(f: VecField, t, z: PyTree, *args) -> PyTree:
    """``f(t, z, *args)`` under the ``ode_field`` named scope."""
    with jax.named_scope(FIELD_SCOPE):
        return f(t, z, *args)


def _tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """y + alpha * x elementwise over pytrees, preserving y's dtype
    (an f32 stepsize scalar must not upcast a bf16 model state)."""
    return jax.tree.map(
        lambda xi, yi: yi + (alpha * xi).astype(yi.dtype), x, y)


def _weighted_sum(ks: Tuple[PyTree, ...], ws) -> PyTree:
    """Σ_i ws[i] * ks[i] over pytrees, skipping exact-zero weights."""
    acc = None
    for w, k in zip(ws, ks):
        if isinstance(w, float) and w == 0.0:
            continue
        term = jax.tree.map(lambda ki: w * ki, k)
        acc = term if acc is None else jax.tree.map(jnp.add, acc, term)
    if acc is None:
        acc = jax.tree.map(jnp.zeros_like, ks[0])
    return acc


class StepResult(NamedTuple):
    z_next: PyTree
    err: Optional[PyTree]  # local error estimate (None for fixed-step)
    k_last: PyTree         # last stage derivative (FSAL reuse)
    # scaled error norm from the fused kernel (flat fast path with
    # err_scale only); None -> caller computes error_ratio itself
    err_ratio: Optional[jnp.ndarray] = None
    # dense-output extras (``dense=True`` only): the first-stage
    # derivative actually used (k0 input or freshly computed) and — for
    # tableaus carrying ``b_mid`` — the step-midpoint solution
    # z + h·Σ b_mid_i k_i.  Feed ``interp_fit``.
    k_first: Optional[PyTree] = None
    z_mid: Optional[PyTree] = None


def _is_flat_array(z: PyTree) -> bool:
    return (isinstance(z, jax.Array) and z.ndim == 1
            and jnp.issubdtype(z.dtype, jnp.inexact))


def flatten_problem(f: VecField, z0: PyTree):
    """Per-solve flat-state adapter for the fused kernel path.

    Returns ``(f_flat, z0_flat, unravel)`` — the vector field over the
    raveled (N,) state, the raveled initial state, and the inverse map
    for outputs/checkpoints — or None when the state cannot be raveled
    losslessly (mixed dtypes would be promoted, non-inexact leaves have
    no kernel path); callers then fall back to the pytree path.
    """
    leaves = jax.tree.leaves(z0)
    if not leaves:
        return None
    try:
        dtypes = {jnp.result_type(leaf) for leaf in leaves}
    except TypeError:
        return None
    if len(dtypes) != 1 or not jnp.issubdtype(dtypes.pop(), jnp.inexact):
        return None
    z0_flat, unravel = ravel_pytree(z0)

    def f_flat(t, zf, *args):
        return ravel_pytree(f(t, unravel(zf), *args))[0]

    return f_flat, z0_flat, unravel


def maybe_flatten(f: VecField, z0: PyTree, use_pallas: bool):
    """Flag-gated ``flatten_problem``: the one fallback rule shared by
    every solver entry point.

    Returns ``(f, z0, unravel, use_pallas)`` — the flat problem with
    ``use_pallas=True`` when raveling is possible and requested, else
    the inputs unchanged with ``unravel=None`` and ``use_pallas=False``
    (pytree path).
    """
    flat = flatten_problem(f, z0) if use_pallas else None
    if flat is None:
        return f, z0, None, False
    f_flat, z0_flat, unravel = flat
    return f_flat, z0_flat, unravel, True


def _rk_step_flat(
    tab: Tableau,
    f: VecField,
    t,
    z: jnp.ndarray,
    h,
    args: Tuple,
    k0: Optional[jnp.ndarray],
    err_scale: Optional[Tuple[float, float]],
    dense: bool = False,
) -> StepResult:
    """Fused-kernel ψ over a flat (N,) state (see module docstring)."""
    # deferred: importing repro.kernels at module scope would cycle
    # through kernels.ref -> repro.models -> repro.core
    from repro.kernels import ops

    k0v = k0 if k0 is not None else field_eval(f, t, z, *args)
    ks = jnp.zeros((tab.stages,) + z.shape, k0v.dtype).at[0].set(k0v)
    for i in range(1, tab.stages):
        zi = ops.rk_stage_increment(z, ks[:i], h, tab.a[i])
        ks = ks.at[i].set(field_eval(f, t + tab.c[i] * h, zi, *args))

    ratio = None
    if tab.b_err is not None and err_scale is not None:
        rtol, atol = err_scale
        # with_err=False: the accept/reject loop reads only z_next and
        # the fused norm — the (N,) err buffer is never materialized
        z_next, err, sq_sum = ops.rk_stage_combine_err(
            z, ks, h, tab.b, tab.b_err, rtol, atol, with_err=False)
        ratio = jnp.sqrt(sq_sum / z.size)
    else:
        # no consumer for err here (fixed tableaus have none; the ACA
        # backward replay reads only z_next): the solution combine is
        # the increment kernel with the b row — skips the N-sized err
        # store on this memory-bound loop
        z_next = ops.rk_stage_increment(z, ks, h, tab.b)
        err = None
    k_last = ks[-1] if tab.fsal else ks[0]
    k_first = z_mid = None
    if dense:
        k_first = k0v
        if tab.b_mid is not None:
            # the midpoint combine is the increment kernel with b_mid
            z_mid = ops.rk_stage_increment(z, ks, h, tab.b_mid)
    return StepResult(z_next=z_next, err=err, k_last=k_last,
                      err_ratio=ratio, k_first=k_first, z_mid=z_mid)


def rk_step(
    tab: Tableau,
    f: VecField,
    t,
    z: PyTree,
    h,
    args: Tuple = (),
    k0: Optional[PyTree] = None,
    *,
    use_pallas: bool = False,
    err_scale: Optional[Tuple[float, float]] = None,
    dense: bool = False,
) -> StepResult:
    """One explicit RK step of ``tab`` from (t, z) with stepsize h.

    ``k0`` optionally supplies the first stage derivative (FSAL).
    Returns z_{n+1}, the embedded error estimate (h·Σ b_err_i k_i) and the
    final stage derivative for FSAL chaining.

    ``use_pallas=True`` dispatches to the fused Pallas kernels when the
    state is a single flat inexact array (see ``flatten_problem``);
    other states silently take the pytree path.  With ``err_scale=(rtol,
    atol)`` the fused path additionally returns the scaled error norm in
    ``StepResult.err_ratio``; *without* err_scale the fused path returns
    ``err=None`` even for embedded tableaus (the err buffer is not
    materialized — adaptive callers always pass err_scale).

    ``dense=True`` additionally returns the dense-output inputs of
    ``interp_fit``: ``k_first`` (the stage-0 derivative this step
    consumed) and, for tableaus with ``b_mid``, the midpoint solution
    ``z_mid = z + h·Σ b_mid_i k_i``.  The advancing arithmetic is
    untouched — z_next is bit-identical with and without ``dense``.
    """
    if use_pallas and _is_flat_array(z):
        return _rk_step_flat(tab, f, t, z, h, args, k0, err_scale,
                             dense=dense)
    ks = []
    for i in range(tab.stages):
        if i == 0:
            ki = k0 if k0 is not None else field_eval(f, t, z, *args)
        else:
            zi = z
            incr = _weighted_sum(tuple(ks), tab.a[i])
            zi = _tree_axpy(h, incr, z)
            ki = field_eval(f, t + tab.c[i] * h, zi, *args)
        ks.append(ki)
    ks = tuple(ks)

    z_next = _tree_axpy(h, _weighted_sum(ks, tab.b), z)

    err = None
    if tab.b_err is not None:
        err = jax.tree.map(lambda e: h * e, _weighted_sum(ks, tab.b_err))

    if tab.fsal:
        k_last = ks[-1]
    else:
        k_last = ks[0]
    k_first = z_mid = None
    if dense:
        k_first = ks[0]
        if tab.b_mid is not None:
            z_mid = _tree_axpy(h, _weighted_sum(ks, tab.b_mid), z)
    return StepResult(z_next=z_next, err=err, k_last=k_last,
                      k_first=k_first, z_mid=z_mid)


def _is_flat_batched(z: PyTree) -> bool:
    return (isinstance(z, jax.Array) and z.ndim == 2
            and jnp.issubdtype(z.dtype, jnp.inexact))


def maybe_flatten_batched(f: VecField, z0: PyTree, use_pallas: bool):
    """Batched twin of ``maybe_flatten``: ``z0`` leaves carry a leading
    batch dim B and ``f`` is the *per-sample* vector field.

    Returns ``(f, z0, unravel, use_pallas)``: on success ``f`` is the
    per-sample field over the raveled (N,) state, ``z0`` the (B, N)
    batch of raveled states and ``unravel`` the per-sample inverse map
    (vmap it over outputs); otherwise the inputs come back unchanged
    with ``unravel=None`` and ``use_pallas=False`` (same fallback rules
    as ``flatten_problem``: single inexact dtype or bust).
    """
    if not use_pallas:
        return f, z0, None, False
    sample = jax.tree.map(lambda l: l[0], z0)
    flat = flatten_problem(f, sample)
    if flat is None:
        return f, z0, None, False
    f_flat, _, unravel = flat
    z0_flat = jax.vmap(lambda z: ravel_pytree(z)[0])(z0)
    return f_flat, z0_flat, unravel, True


def _tree_baxpy(h, x: PyTree, y: PyTree) -> PyTree:
    """Per-row y + h_b * x over batch-leading pytrees, h of shape (B,)."""
    return jax.tree.map(
        lambda xi, yi: yi + (h.reshape((-1,) + (1,) * (xi.ndim - 1))
                             * xi).astype(yi.dtype), x, y)


def _rk_step_flat_batched(
    tab: Tableau,
    fb: Callable,
    t: jnp.ndarray,
    z: jnp.ndarray,
    h: jnp.ndarray,
    k0: Optional[jnp.ndarray],
    err_scale: Optional[Tuple[float, float]],
    dense: bool = False,
) -> StepResult:
    """Fused batched ψ over a (B, N) state: per-row stepsizes, per-row
    error norms.  ``fb`` maps ((B,), (B, N)) -> (B, N)."""
    from repro.kernels import ops

    k0v = k0 if k0 is not None else fb(t, z)
    ks = jnp.zeros((tab.stages,) + z.shape, k0v.dtype).at[0].set(k0v)
    for i in range(1, tab.stages):
        zi = ops.rk_stage_increment_batched(z, ks[:i], h, tab.a[i])
        ks = ks.at[i].set(fb(t + tab.c[i] * h, zi))

    ratio = None
    if tab.b_err is not None and err_scale is not None:
        rtol, atol = err_scale
        z_next, sq_sum = ops.rk_stage_combine_err_batched(
            z, ks, h, tab.b, tab.b_err, rtol, atol)
        ratio = jnp.sqrt(sq_sum / z.shape[-1])
        err = None
    else:
        z_next = ops.rk_stage_increment_batched(z, ks, h, tab.b)
        err = None
    k_last = ks[-1] if tab.fsal else ks[0]
    k_first = z_mid = None
    if dense:
        k_first = k0v
        if tab.b_mid is not None:
            z_mid = ops.rk_stage_increment_batched(z, ks, h, tab.b_mid)
    return StepResult(z_next=z_next, err=err, k_last=k_last,
                      err_ratio=ratio, k_first=k_first, z_mid=z_mid)


def rk_step_batched(
    tab: Tableau,
    f: VecField,
    t: jnp.ndarray,
    z: PyTree,
    h: jnp.ndarray,
    args: Tuple = (),
    k0: Optional[PyTree] = None,
    *,
    use_pallas: bool = False,
    err_scale: Optional[Tuple[float, float]] = None,
    dense: bool = False,
) -> StepResult:
    """One explicit RK step per batch element: ψ_{h_b}(t_b, z_b) for all
    b at once.

    ``f`` is the per-sample vector field (no batch dim); leaves of ``z``
    carry a leading batch dim B; ``t`` and ``h`` are (B,).  With
    ``err_scale=(rtol, atol)`` the result's ``err_ratio`` is the (B,)
    vector of per-element scaled error norms (then ``err`` is None — no
    consumer); ``rtol``/``atol`` may themselves be (B,) arrays, scaling
    each element's norm against its own tolerance (the per-request QoS
    path — equal-tolerance rows stay bitwise identical to the scalar
    form).  An element whose h_b is 0 passes through unchanged
    bit-exactly: the masking contract the batched adaptive loop and the
    ACA batched backward sweep use to freeze finished elements.

    ``use_pallas=True`` dispatches (B, N) inexact states to the batched
    fused kernels; other states take the vmapped pytree path.
    ``dense=True`` as in ``rk_step`` (per-row ``k_first`` / ``z_mid``).
    """
    fb = jax.vmap(lambda ti, zi: field_eval(f, ti, zi, *args))
    if use_pallas and _is_flat_batched(z):
        return _rk_step_flat_batched(tab, fb, t, z, h, k0, err_scale,
                                     dense=dense)

    ks = []
    for i in range(tab.stages):
        if i == 0:
            ki = k0 if k0 is not None else fb(t, z)
        else:
            incr = _weighted_sum(tuple(ks), tab.a[i])
            zi = _tree_baxpy(h, incr, z)
            ki = fb(t + tab.c[i] * h, zi)
        ks.append(ki)
    ks = tuple(ks)

    z_next = _tree_baxpy(h, _weighted_sum(ks, tab.b), z)

    err = None
    ratio = None
    if tab.b_err is not None:
        err = jax.tree.map(
            lambda e: h.reshape((-1,) + (1,) * (e.ndim - 1)) * e,
            _weighted_sum(ks, tab.b_err))
        if err_scale is not None:
            rtol, atol = err_scale
            if jnp.ndim(rtol) > 0 or jnp.ndim(atol) > 0:
                # per-row tolerances (per-request QoS): each element's
                # error norm is scaled against its own (rtol, atol) —
                # same arithmetic per row as the scalar path, so
                # equal-tolerance rows stay bitwise identical
                bsz = h.shape[0]
                rt = jnp.broadcast_to(
                    jnp.asarray(rtol, jnp.float32), (bsz,))
                at = jnp.broadcast_to(
                    jnp.asarray(atol, jnp.float32), (bsz,))
                ratio = jax.vmap(error_ratio)(err, z, z_next, rt, at)
            else:
                ratio = jax.vmap(
                    lambda e, a, b: error_ratio(e, a, b, rtol, atol))(
                        err, z, z_next)
            err = None

    k_last = ks[-1] if tab.fsal else ks[0]
    k_first = z_mid = None
    if dense:
        k_first = ks[0]
        if tab.b_mid is not None:
            z_mid = _tree_baxpy(h, _weighted_sum(ks, tab.b_mid), z)
    return StepResult(z_next=z_next, err=err, k_last=k_last,
                      err_ratio=ratio, k_first=k_first, z_mid=z_mid)


def error_ratio(err: PyTree, z0: PyTree, z1: PyTree, rtol: float,
                atol: float):
    """RMS norm of err scaled by atol + rtol*max(|z0|,|z1|) (Hairer I.4).

    Returns a scalar; an accepted step has ratio <= 1.
    """
    def _scaled_sq(e, a, b):
        scale = atol + rtol * jnp.maximum(jnp.abs(a), jnp.abs(b))
        r = (e / scale).astype(jnp.float32)
        return jnp.sum(r * r), r.size

    leaves_sq, sizes = zip(*(
        _scaled_sq(e, a, b)
        for e, a, b in zip(jax.tree.leaves(err), jax.tree.leaves(z0),
                           jax.tree.leaves(z1))
    ))
    total = sum(leaves_sq)
    n = sum(sizes)
    return jnp.sqrt(total / n)


# --------------------------------------------------------------------------
# Dense output: per-step polynomial interpolants
# --------------------------------------------------------------------------
#
# Every accepted step carries enough information for a local polynomial
# z(t + θh) ≈ P(θ), θ ∈ [0, 1], built from quantities the solver loop
# already computed:
#
#   * cubic Hermite (any tableau): endpoints z0, z1 and endpoint
#     derivatives k0 = f(t, z0), k1 = f(t+h, z1) — both free: k0 is the
#     first stage, k1 is the FSAL last stage (or the post-accept k0'
#     recompute for non-FSAL pairs).  Local error O(h⁴).
#   * quartic fit (tableaus with ``b_mid``, i.e. Dopri5): adds the
#     midpoint solution z_mid = z0 + h·Σ b_mid_i k_i, giving the classic
#     4th-order dense output whose error tracks the pair's tolerance.
#
# Both are expressed as one coefficient 5-tuple (c4..c0) with
# P(θ) = (((c4·θ + c3)·θ + c2)·θ + c1)·θ + c0, so downstream code
# (interpolated eval-time reads, DenseSolution storage, the ACA backward
# sweep's interpolated-output vjp) handles one representation.  P(0) is
# z0 *bitwise* (c0 = z0); P(1) recovers z1 algebraically.


class InterpCoeffs(NamedTuple):
    """Polynomial coefficients of one step interpolant (pytrees, highest
    degree first): P(θ) = c4·θ⁴ + c3·θ³ + c2·θ² + c1·θ + c0."""
    c4: PyTree
    c3: PyTree
    c2: PyTree
    c1: PyTree
    c0: PyTree


def _hb(h, leaf):
    """Reshape h (scalar or (B,)) to broadcast against a state leaf,
    cast to the leaf dtype (a float64 time grid under JAX_ENABLE_X64
    must not upcast a float32 state — same rule as ``_tree_axpy``)."""
    h = jnp.asarray(h, leaf.dtype)
    return h.reshape(h.shape + (1,) * (leaf.ndim - h.ndim))


def interp_fit(z0: PyTree, z1: PyTree, k0: PyTree, k1: PyTree, h,
               z_mid: Optional[PyTree] = None) -> InterpCoeffs:
    """Fit the step interpolant from endpoint (and midpoint) data.

    ``h`` is the accepted stepsize — a scalar, or (B,) for batch-leading
    pytrees (per-row steps).  With ``z_mid`` (tableaus carrying
    ``b_mid``) this is the 4th-order quartic fit matching z0, z1, z_mid,
    k0 and k1; without it, the cubic Hermite through z0, z1, k0, k1
    (c4 = 0).  All arithmetic is plain jnp — differentiable everywhere,
    including under the ACA backward sweep's local vjp.
    """
    # h·k cast to the STATE leaf dtype (not k's): under x64 a float64
    # time can promote f's output, and the coefficients must match z —
    # the _tree_axpy convention
    hk0 = jax.tree.map(lambda k, z: (_hb(h, z) * k).astype(z.dtype),
                       k0, z0)
    hk1 = jax.tree.map(lambda k, z: (_hb(h, z) * k).astype(z.dtype),
                       k1, z0)
    if z_mid is None:
        c4 = jax.tree.map(jnp.zeros_like, z0)
        c3 = jax.tree.map(
            lambda a, b, p, q: 2.0 * (a - b) + p + q, z0, z1, hk0, hk1)
        c2 = jax.tree.map(
            lambda a, b, p, q: 3.0 * (b - a) - 2.0 * p - q,
            z0, z1, hk0, hk1)
    else:
        c4 = jax.tree.map(
            lambda p, q, a, b, m: 2.0 * (q - p) - 8.0 * (a + b)
            + 16.0 * m, hk0, hk1, z0, z1, z_mid)
        c3 = jax.tree.map(
            lambda p, q, a, b, m: 5.0 * p - 3.0 * q + 18.0 * a
            + 14.0 * b - 32.0 * m, hk0, hk1, z0, z1, z_mid)
        c2 = jax.tree.map(
            lambda p, q, a, b, m: q - 4.0 * p - 11.0 * a - 5.0 * b
            + 16.0 * m, hk0, hk1, z0, z1, z_mid)
    return InterpCoeffs(c4=c4, c3=c3, c2=c2, c1=hk0, c0=z0)


def interp_eval(coeffs: InterpCoeffs, theta: jnp.ndarray) -> PyTree:
    """Evaluate P at ``theta``, stacking theta's *leading* axis onto the
    output: theta (T,) over solo leaves (...) -> (T, ...); theta (T, B)
    over batch-leading leaves (B, ...) -> (T, B, ...)."""
    def ev(c4, c3, c2, c1, c0):
        th = theta.astype(c0.dtype).reshape(
            theta.shape + (1,) * (c0.ndim - (theta.ndim - 1)))
        return (((c4 * th + c3) * th + c2) * th + c1) * th + c0

    return jax.tree.map(ev, *coeffs)


def interp_eval_aligned(coeffs: InterpCoeffs,
                        theta: jnp.ndarray) -> PyTree:
    """Evaluate P elementwise: theta's axes align with the *leading*
    leaf axes (theta (T,) over leaves (T, ...) -> (T, ...)).  Used by
    ``DenseSolution.evaluate`` after gathering per-query coefficients."""
    def ev(c4, c3, c2, c1, c0):
        th = theta.astype(c0.dtype).reshape(
            theta.shape + (1,) * (c0.ndim - theta.ndim))
        return (((c4 * th + c3) * th + c2) * th + c1) * th + c0

    return jax.tree.map(ev, *coeffs)


def fixed_step_fn(tab: Tableau, f: VecField) -> Callable:
    """Returns step(t, z, h, args) -> z_next for fixed-grid integration."""
    def step(t, z, h, args=()):
        return rk_step(tab, f, t, z, h, args).z_next
    return step


# --------------------------------------------------------------------------
# Asynchronous-leapfrog (ALF) stepper — the reversible pair integrator
# behind ``odeint(..., grad_method="mali")``
# --------------------------------------------------------------------------
#
# One ALF step advances the paired state (z, v), v ≈ dz/dt (MALI, Zhuang
# et al. 2021):
#
#     u  = z + (h/2)·v           half-position drift
#     w  = f(t + h/2, u)         one midpoint field evaluation
#     v' = 2w − v                velocity reflection
#     z' = u + (h/2)·v'          half-position drift with the NEW velocity
#
# (algebraically z' = z + h·w — second order, ONE f-eval per trial).  The
# step is *algebraically* self-inverse: u = z' − (h/2)·v' recovers the
# midpoint from the advanced pair, so the same w can be recomputed and the
# whole step peeled off — the basis of MALI's O(1)-memory exact-reverse
# gradient.
#
# Floating-point addition, however, is lossy (fl(fl(a+b)−b) ≠ a in
# general: the map a ↦ fl(a+b) is not injective), so NO deterministic
# float implementation of the algebraic inverse can be bit-exact.  To make
# ψ⁻¹∘ψ the identity *bitwise* — the contract the MALI backward sweep is
# built on — the pair is carried on a **fixed-point integer lattice**
# (Levesque & Verlet 1993, "bit-reversible" integration): both z and v are
# stored as int32/int64 multiples of a per-solve quantum
# δ = 2^(scale_exp − frac), every drift/reflection update is a *wrapping
# integer add* of an increment recomputed identically on both sides, and
# integer addition is a bijection — the inverse subtracts the same
# integers and recovers the previous pair exactly, for any input
# (over/underflow included).  The field f is evaluated on the decoded
# (float) midpoint; determinism of f gives bit-equal w in both directions.
#
# The quantization costs one δ-rounding per f-eval: δ is the state scale
# × 2⁻²⁴ (f32/bf16 leaves, i32 lattice) or × 2⁻⁵² (f64 leaves, i64
# lattice) — at or below one float ulp at the state's scale, far below
# any solver tolerance this repo runs.  The differentiable twin
# ``alf_step_float`` (the function the MALI backward sweep takes
# ``jax.vjp`` of, linearized at the exactly-reconstructed states) treats
# the δ-rounding as identity — the standard straight-through convention.

ALF_ORDER = 2  # ALF is second order; embedded Euler comparator is order 1


def _lattice_frac(fdt) -> int:
    """Fractional bits of the lattice for a float leaf dtype: the quantum
    is δ = 2^(scale_exp − frac)."""
    return 52 if fdt == jnp.float64 else 24


def _lattice_int_dtype(fdt):
    return jnp.int64 if fdt == jnp.float64 else jnp.int32


def _lattice_clip_bound(fdt) -> float:
    # largest float of the lattice dtype that casts safely to the int
    # dtype (2^31 / 2^63 themselves would overflow the cast)
    return float(2 ** 62) if fdt == jnp.float64 else float(2 ** 31 - 128)


def alf_lattice_exponent(z0: PyTree, v0: PyTree) -> jnp.ndarray:
    """Per-solve lattice scale exponent: ⌈log₂ max(|z0|, |v0|, 1)⌉.

    One float32 scalar shared by every leaf (the quantum is
    δ_leaf = 2^(scale_exp − frac(dtype))); saved in the solve's grid so
    the backward sweep decodes on the identical lattice.  The i32
    lattice then spans ±128× the initial scale at a resolution of one
    f32 ulp at that scale — states wandering far beyond the initial
    scale wrap (deterministically; the error estimator rejects such
    steps long before).
    """
    def leaf_max(l):
        return jnp.max(jnp.abs(l.astype(jnp.float32))) if l.size else \
            jnp.float32(0.0)

    mx = jnp.asarray(1.0, jnp.float32)
    for leaf in jax.tree.leaves(z0) + jax.tree.leaves(v0):
        mx = jnp.maximum(mx, leaf_max(leaf))
    return jnp.ceil(jnp.log2(mx))


def alf_lattice_exponent_batched(z0: PyTree, v0: PyTree) -> jnp.ndarray:
    """Per-element lattice exponents (B,) over batch-leading leaves —
    the same reduction as ``alf_lattice_exponent`` restricted to each
    row, so a batched solve quantizes exactly like ``jax.vmap`` of the
    solo solve (per-row conditioning included)."""
    def leaf_max(l):
        flat = jnp.abs(l.astype(jnp.float32)).reshape(l.shape[0], -1)
        return jnp.max(flat, axis=1) if l.size else \
            jnp.zeros((l.shape[0],), jnp.float32)

    leaves = jax.tree.leaves(z0) + jax.tree.leaves(v0)
    mx = jnp.ones((leaves[0].shape[0],), jnp.float32)
    for leaf in leaves:
        mx = jnp.maximum(mx, leaf_max(leaf))
    return jnp.ceil(jnp.log2(mx))


def _se_b(scale_exp, leaf: jnp.ndarray) -> jnp.ndarray:
    """Reshape a scale exponent — scalar, or (B,) over batch-leading
    leaves — to broadcast against ``leaf`` (the ``_hb`` convention)."""
    se = jnp.asarray(scale_exp, jnp.float32)
    return se.reshape(se.shape + (1,) * (leaf.ndim - se.ndim))


def _lattice_quantize_leaf(x: jnp.ndarray, scale_exp) -> jnp.ndarray:
    """Round a float leaf to its integer lattice coordinate (the ONE
    quantization rule — forward and inverse must call exactly this)."""
    fdt = x.dtype
    inv_delta = jnp.exp2(
        jnp.asarray(_lattice_frac(fdt), jnp.float32) - _se_b(scale_exp, x)
    ).astype(fdt)
    q = jnp.round(x * inv_delta)
    lim = jnp.asarray(_lattice_clip_bound(fdt), fdt)
    return jnp.clip(q, -lim, lim).astype(_lattice_int_dtype(fdt))


def _lattice_decode_leaf(q: jnp.ndarray, scale_exp, fdt) -> jnp.ndarray:
    delta = jnp.exp2(
        _se_b(scale_exp, q) - jnp.asarray(_lattice_frac(fdt), jnp.float32)
    ).astype(fdt)
    return q.astype(fdt) * delta


def lattice_encode(x: PyTree, scale_exp) -> PyTree:
    """Float pytree -> integer-lattice pytree (i32 per f32/bf16 leaf,
    i64 per f64 leaf), quantum δ = 2^(scale_exp − frac)."""
    return jax.tree.map(lambda l: _lattice_quantize_leaf(l, scale_exp), x)


def lattice_decode(q: PyTree, scale_exp, proto: PyTree) -> PyTree:
    """Integer-lattice pytree -> float pytree with ``proto``'s leaf
    dtypes (the exact inverse scaling of ``lattice_encode``'s grid)."""
    return jax.tree.map(
        lambda ql, pl: _lattice_decode_leaf(ql, scale_exp, pl.dtype),
        q, proto)


def _drift_increment(h, v_float: PyTree, scale_exp) -> PyTree:
    """Quantized half-drift increment Q((h/2)·v), per leaf, as lattice
    integers.  ``h`` may be scalar or (B,) over batch-leading leaves;
    it is cast to each leaf's dtype (an x64 time grid must not promote
    an f32 state — the ``_tree_axpy`` convention)."""
    def leaf(v):
        hh = _hb(h, v) * jnp.asarray(0.5, v.dtype)
        return _lattice_quantize_leaf(hh * v, scale_exp)

    return jax.tree.map(leaf, v_float)


def _tree_iadd(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def _tree_isub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def _alf_midpoint_t(t, h):
    """t + h/2 — defined once so forward and inverse compute the same
    bits."""
    return t + 0.5 * h


class AlfResult(NamedTuple):
    """One ALF trial over the lattice pair.

    ``zq_next``/``vq_next`` are the advanced lattice coordinates (carry
    them); ``z_next`` the decoded float state (outputs / error scale);
    ``err`` the embedded error estimate h·(w − v) — the gap between the
    2nd-order midpoint update z + h·w and the 1st-order Euler predictor
    z + h·v, the zero-cost analog of an embedded RK pair.
    """
    zq_next: PyTree
    vq_next: PyTree
    z_next: PyTree
    err: PyTree


def alf_step(f: VecField, t, h, zq: PyTree, vq: PyTree, scale_exp,
             proto: PyTree, args: Tuple = ()) -> AlfResult:
    """One asynchronous-leapfrog step on the integer lattice.

    ``zq``/``vq`` are lattice pytrees (``lattice_encode``), ``proto`` a
    float pytree fixing the leaf dtypes, ``t``/``h`` scalars.  Every
    state update is a wrapping integer add, so
    ``alf_step_inverse(alf_step(s)) == s`` **bitwise** for any state —
    see the section comment.  Exactly one f evaluation.
    """
    vf = lattice_decode(vq, scale_exp, proto)
    uq = _tree_iadd(zq, _drift_increment(h, vf, scale_exp))
    uf = lattice_decode(uq, scale_exp, proto)
    w = f(_alf_midpoint_t(t, h), uf, *args)
    # velocity reflection v' = 2w − v on the lattice (Q(2w) exact int sub)
    vq_next = _tree_isub(
        jax.tree.map(
            lambda wl: _lattice_quantize_leaf(
                jnp.asarray(2.0, wl.dtype) * wl, scale_exp), w),
        vq)
    vf_next = lattice_decode(vq_next, scale_exp, proto)
    zq_next = _tree_iadd(uq, _drift_increment(h, vf_next, scale_exp))
    err = jax.tree.map(
        lambda wl, vl: _hb(h, vl) * (wl.astype(vl.dtype) - vl), w, vf)
    return AlfResult(zq_next=zq_next, vq_next=vq_next,
                     z_next=lattice_decode(zq_next, scale_exp, proto),
                     err=err)


def alf_step_inverse(f: VecField, t, h, zq_next: PyTree, vq_next: PyTree,
                     scale_exp, proto: PyTree,
                     args: Tuple = ()) -> Tuple[PyTree, PyTree]:
    """Exact inverse of ``alf_step``: recovers the pre-step pair bitwise.

    Mirrors the forward update in reverse: each quantized increment is
    recomputed from the side the inverse already knows (v' for the
    second drift, the recovered v for the first) and subtracted with the
    same wrapping integer arithmetic — ints in, identical ints out.
    """
    vf_next = lattice_decode(vq_next, scale_exp, proto)
    uq = _tree_isub(zq_next, _drift_increment(h, vf_next, scale_exp))
    uf = lattice_decode(uq, scale_exp, proto)
    w = f(_alf_midpoint_t(t, h), uf, *args)
    vq = _tree_isub(
        jax.tree.map(
            lambda wl: _lattice_quantize_leaf(
                jnp.asarray(2.0, wl.dtype) * wl, scale_exp), w),
        vq_next)
    vf = lattice_decode(vq, scale_exp, proto)
    zq = _tree_isub(uq, _drift_increment(h, vf, scale_exp))
    return zq, vq


def alf_step_float(f: VecField, t, h, z: PyTree, v: PyTree,
                   args: Tuple = (), *,
                   use_pallas: bool = False) -> Tuple[PyTree, PyTree]:
    """Differentiable float twin of ``alf_step`` (δ-rounding treated as
    identity — the straight-through convention).

    The MALI backward sweep takes ``jax.vjp`` of this map at the
    exactly-reconstructed (z_i, v_i); its primal differs from the
    lattice step by at most one quantum per operation.  With
    ``use_pallas`` and a flat (N,) state the two half-drifts reuse the
    fused ``rk_stage_increment`` kernel (a one-stage row with weight ½,
    already custom_vjp wrapped); the reflection is one cheap jnp axpy.
    """
    if use_pallas and _is_flat_array(z):
        from repro.kernels import ops
        u = ops.rk_stage_increment(z, v[None], h, (0.5,))
        w = f(_alf_midpoint_t(t, h), u, *args)
        v_next = 2.0 * w - v
        z_next = ops.rk_stage_increment(u, v_next[None], h, (0.5,))
        return z_next, v_next
    half = jax.tree.map(lambda vl: 0.5 * vl, v)
    u = _tree_axpy(h, half, z)
    w = f(_alf_midpoint_t(t, h), u, *args)
    v_next = jax.tree.map(lambda wl, vl: 2.0 * wl - vl, w, v)
    z_next = _tree_axpy(h, jax.tree.map(lambda vl: 0.5 * vl, v_next), u)
    return z_next, v_next


def alf_step_batched(f: VecField, t: jnp.ndarray, h: jnp.ndarray,
                     zq: PyTree, vq: PyTree, scale_exp, proto: PyTree,
                     args: Tuple = ()) -> AlfResult:
    """Per-sample batched ALF trial: leaves carry a leading batch dim B,
    ``t``/``h`` are (B,) — each element drifts with its own stepsize.

    Same lattice arithmetic as ``alf_step`` (the increments broadcast
    h per row), so per-row inversion is bitwise exact.  Callers gate the
    carry on per-row accept masks (integer ``where`` is bit-stable);
    a frozen row's trial is simply discarded — note the h = 0 ALF step
    is *not* the identity in v (the reflection still fires), so masking,
    not zero-stepping, is the freezing contract here.
    """
    fb = jax.vmap(lambda ti, zi: f(ti, zi, *args))
    vf = lattice_decode(vq, scale_exp, proto)
    uq = _tree_iadd(zq, _drift_increment(h, vf, scale_exp))
    uf = lattice_decode(uq, scale_exp, proto)
    w = fb(_alf_midpoint_t(t, h), uf)
    vq_next = _tree_isub(
        jax.tree.map(
            lambda wl: _lattice_quantize_leaf(
                jnp.asarray(2.0, wl.dtype) * wl, scale_exp), w),
        vq)
    vf_next = lattice_decode(vq_next, scale_exp, proto)
    zq_next = _tree_iadd(uq, _drift_increment(h, vf_next, scale_exp))
    err = jax.tree.map(
        lambda wl, vl: _hb(h, vl) * (wl.astype(vl.dtype) - vl), w, vf)
    return AlfResult(zq_next=zq_next, vq_next=vq_next,
                     z_next=lattice_decode(zq_next, scale_exp, proto),
                     err=err)


def alf_step_inverse_batched(
        f: VecField, t: jnp.ndarray, h: jnp.ndarray, zq_next: PyTree,
        vq_next: PyTree, scale_exp, proto: PyTree,
        args: Tuple = ()) -> Tuple[PyTree, PyTree]:
    """Batched twin of ``alf_step_inverse`` (per-row t/h)."""
    fb = jax.vmap(lambda ti, zi: f(ti, zi, *args))
    vf_next = lattice_decode(vq_next, scale_exp, proto)
    uq = _tree_isub(zq_next, _drift_increment(h, vf_next, scale_exp))
    uf = lattice_decode(uq, scale_exp, proto)
    w = fb(_alf_midpoint_t(t, h), uf)
    vq = _tree_isub(
        jax.tree.map(
            lambda wl: _lattice_quantize_leaf(
                jnp.asarray(2.0, wl.dtype) * wl, scale_exp), w),
        vq_next)
    vf = lattice_decode(vq, scale_exp, proto)
    zq = _tree_isub(uq, _drift_increment(h, vf, scale_exp))
    return zq, vq


def alf_step_float_batched(
        f: VecField, t: jnp.ndarray, h: jnp.ndarray, z: PyTree,
        v: PyTree, args: Tuple = (), *,
        use_pallas: bool = False) -> Tuple[PyTree, PyTree]:
    """Batched differentiable float twin (per-row t/h); with
    ``use_pallas`` and a (B, N) state the drifts reuse the fused
    ``rk_stage_increment_batched`` kernel."""
    fb = jax.vmap(lambda ti, zi: f(ti, zi, *args))
    if use_pallas and _is_flat_batched(z):
        from repro.kernels import ops
        u = ops.rk_stage_increment_batched(z, v[None], h, (0.5,))
        w = fb(_alf_midpoint_t(t, h), u)
        v_next = 2.0 * w - v
        z_next = ops.rk_stage_increment_batched(u, v_next[None], h, (0.5,))
        return z_next, v_next
    half = jax.tree.map(lambda vl: 0.5 * vl, v)
    u = _tree_baxpy(h, half, z)
    w = fb(_alf_midpoint_t(t, h), u)
    v_next = jax.tree.map(lambda wl, vl: 2.0 * wl - vl, w, v)
    z_next = _tree_baxpy(h, jax.tree.map(lambda vl: 0.5 * vl, v_next), u)
    return z_next, v_next
