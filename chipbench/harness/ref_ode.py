"""Plain reference of a per-row adaptive explicit Runge-Kutta solve and
of the gradient that ACA computes: the gradient of the discrete map over
the accepted steps, with the step sizes held as constants.

Written from the method's description (Hairer, Norsett and Wanner I.4
and II.4: embedded pair, RMS error norm scaled by atol + rtol*max(|z|,
|z_next|), PI step control, Hairer's starting step), in plain
``jax.numpy`` on a (B, N) state: one row is one problem.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp


class Tableau(NamedTuple):
    a: Sequence[Sequence[float]]
    b: Sequence[float]
    e: Sequence[float]          # b - b_hat: error weights
    c: Sequence[float]
    order: int                  # the controller's exponent is 1/order
    fsal: bool


class Controller(NamedTuple):
    safety: float = 0.9
    min_factor: float = 0.2
    max_factor: float = 10.0
    pi_coeff: float = 0.04
    max_trials_per_step: int = 12


def _rms(x):
    return jnp.sqrt(jnp.mean(jnp.square(x), axis=-1))


def initial_step(field: Callable, t0, z0, order: int, rtol, atol):
    """Hairer's starting step size, per row.  ``rtol``/``atol`` are
    scalars or (B,) arrays."""
    rtol = jnp.reshape(jnp.asarray(rtol, jnp.float32), (-1, 1))
    atol = jnp.reshape(jnp.asarray(atol, jnp.float32), (-1, 1))
    scale = atol + rtol * jnp.abs(z0)
    f0 = field(t0, z0)
    d0 = _rms(z0 / scale)
    d1 = _rms(f0 / scale)
    h0 = jnp.where((d0 < 1e-5) | (d1 < 1e-5), 1e-6, 0.01 * d0 / d1)
    f1 = field(t0 + h0, z0 + h0[:, None] * f0)
    d2 = _rms((f1 - f0) / scale) / h0
    dmax = jnp.maximum(d1, d2)
    h1 = jnp.where(dmax <= 1e-15, jnp.maximum(1e-6, h0 * 1e-3),
                   (0.01 / dmax) ** (1.0 / (order + 1.0)))
    return jnp.minimum(100.0 * h0, h1)


def _stages(field, tab: Tableau, t, z, h, k0):
    hc = h[:, None]
    ks = [k0]
    for i in range(1, len(tab.c)):
        zi = z
        for j, aij in enumerate(tab.a[i]):
            if aij != 0.0:
                zi = zi + hc * (aij * ks[j])
        ks.append(field(t + tab.c[i] * h, zi))
    return ks


def _combine(z, ks, h, w):
    acc = jnp.zeros_like(z)
    for wi, k in zip(w, ks):
        if wi != 0.0:
            acc = acc + wi * k
    return z + h[:, None] * acc


class Solution(NamedTuple):
    z1: jnp.ndarray        # (B, N) state at t1
    hs: jnp.ndarray        # (B, max_steps) accepted step sizes, 0 after
    n_steps: jnp.ndarray   # (B,) accepted steps
    n_trials: jnp.ndarray  # (B,) trials
    ok: jnp.ndarray        # (B,) reached t1 within the budgets


def solve(field: Callable, z0, t0: float, t1: float, tab: Tableau, rtol,
          atol, max_steps: int, ctl: Controller = Controller(),
          h0=None) -> Solution:
    """Adaptive solve of every row of ``z0`` from t0 to t1 (t1 > t0).

    ``field(t (B,), z (B, N)) -> (B, N)``.  ``h0`` (B,) overrides the
    starting step.
    """
    B = z0.shape[0]
    rows = jnp.arange(B)
    eps = jnp.finfo(jnp.float32).eps
    rt = jnp.broadcast_to(jnp.asarray(rtol, jnp.float32), (B,))
    at = jnp.broadcast_to(jnp.asarray(atol, jnp.float32), (B,))
    t_start = jnp.full((B,), t0, jnp.float32)
    if h0 is None:
        h0 = initial_step(field, t_start, z0, tab.order, rt, at)
    k0 = field(t_start, z0)
    budget = max_steps * ctl.max_trials_per_step
    t_end = jnp.float32(t1)
    land = 16.0 * eps * jnp.maximum(jnp.abs(t_end), 1.0)

    def live_of(c):
        return (~c["done"]) & (c["n"] < max_steps) & (c["trials"] < budget)

    def body(c):
        live = live_of(c)
        t, z, h = c["t"], c["z"], c["h"]
        h_min = 16.0 * eps * jnp.maximum(jnp.abs(t), 1.0)
        h_use = jnp.where(live, jnp.clip(h, h_min, t_end - t), 0.0)
        ks = _stages(field, tab, t, z, h_use, c["k0"])
        z_new = _combine(z, ks, h_use, tab.b)
        err = _combine(jnp.zeros_like(z), ks, h_use, tab.e)
        scale = at[:, None] + rt[:, None] * jnp.maximum(jnp.abs(z),
                                                         jnp.abs(z_new))
        ratio = _rms(err / scale)
        railed = h_use <= h_min * (1 + 1e-3)
        accept = live & ((ratio <= 1.0) | railed)
        t_new = t + h_use
        hit = accept & (t_new >= t_end - land)
        k_acc = ks[-1] if tab.fsal else field(t_new, z_new)
        r = jnp.maximum(ratio, 1e-10)
        factor = jnp.clip(ctl.safety * r ** (-1.0 / tab.order)
                          * c["prev"] ** ctl.pi_coeff,
                          ctl.min_factor, ctl.max_factor)
        i = jnp.minimum(c["n"], max_steps - 1)
        hs = c["hs"].at[rows, i].set(jnp.where(accept, h_use,
                                               c["hs"][rows, i]))
        return dict(
            t=jnp.where(accept, t_new, t),
            z=jnp.where(accept[:, None], z_new, z),
            k0=jnp.where(accept[:, None], k_acc, c["k0"]),
            h=jnp.where(live, h_use * factor, h),
            prev=jnp.where(accept, r, c["prev"]),
            n=c["n"] + accept.astype(jnp.int32),
            trials=c["trials"] + live.astype(jnp.int32),
            done=c["done"] | hit, hs=hs)

    c0 = dict(t=t_start, z=z0, k0=k0, h=jnp.asarray(h0, jnp.float32),
              prev=jnp.ones((B,), jnp.float32),
              n=jnp.zeros((B,), jnp.int32), trials=jnp.zeros((B,), jnp.int32),
              done=jnp.zeros((B,), bool),
              hs=jnp.zeros((B, max_steps), jnp.float32))
    c = jax.lax.while_loop(lambda c: jnp.any(live_of(c)), body, c0)
    return Solution(z1=c["z"], hs=c["hs"], n_steps=c["n"],
                    n_trials=c["trials"], ok=c["done"])


def replay(field: Callable, z0, t0: float, hs, tab: Tableau):
    """State after the accepted steps ``hs`` (B, S): the discrete map ACA
    differentiates.  A step of size 0 is the identity.  Differentiable
    in ``z0`` and in whatever ``field`` closes over; ``hs`` is held
    constant."""
    hs = jax.lax.stop_gradient(hs)

    @jax.checkpoint
    def step(carry, h):
        t, z = carry
        ks = _stages(field, tab, t, z, h, field(t, z))
        return (t + h, _combine(z, ks, h, tab.b)), None

    t = jnp.full((z0.shape[0],), t0, jnp.float32)
    (_, z1), _ = jax.lax.scan(step, (t, z0), hs.T)
    return z1
