"""Plain reference of the ``mlp256`` configuration: its vector field and
its problem generator, in ``jax.numpy``.

The field: 255 coupled states x and one constant log-stiffness slot,
``dx/dt = -exp(logk) x + 0.5 tanh(x w)`` with a (255, 255) coupling w,
computed in float32 at the precision the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

def _bf16_split(a):
    """a = hi + lo + rest with hi and lo bfloat16 values held in float32
    (``reduce_precision`` is an explicit rounding the compiler keeps)."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def dot(x, w, precision):
    """x @ w in float32 at ``precision``: "highest" is the platform's
    full float32 product; "high" is spelled out as three bfloat16
    products (hi*hi + hi*lo + lo*hi, each exact, float32 accumulation),
    the same on every platform."""
    mm = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return mm(x, w)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    xh, xl = _bf16_split(x)
    wh, wl = _bf16_split(w)
    return mm(xh, wh) + (mm(xh, wl) + mm(xl, wh))


def row_field(precision):
    """Per-row field f(t, z (N,), w) -> (N,), the form the solver under
    test vmaps itself."""

    def f(t, z, w):
        x, logk = z[:-1], z[-1]
        xw = dot(x, w, precision)
        dx = -jnp.exp(logk) * x + 0.5 * jnp.tanh(xw)
        return jnp.concatenate([dx, jnp.zeros((1,), z.dtype)])

    return f


def batch_field(precision, w):
    """Batched field F(t (B,), Z (B, N)) -> (B, N) for the reference."""
    def f(t, z):
        x, logk = z[:, :-1], z[:, -1:]
        xw = dot(x, w, precision)
        dx = -jnp.exp(logk) * x + 0.5 * jnp.tanh(xw)
        return jnp.concatenate([dx, jnp.zeros_like(logk)], axis=1)

    return f


def coupling(key, config):
    d = config["dim"] - 1
    return jax.random.normal(key, (d, d), jnp.float32) * (
        config["coupling_scale"] / config["dim"] ** 0.5)


def rows(key, n, config, logk_lo, logk_span, logk_power):
    """``n`` initial states.  The log-stiffnesses are the same multiset for
    every key (lo + span * u^power at the stratified u = (i + 1/2)/n);
    the key shuffles them over the rows and draws x0."""
    kx, kp = jax.random.split(key)
    x0 = jax.random.normal(kx, (n, config["dim"] - 1), jnp.float32) \
        * config["x0_scale"]
    u = (jnp.arange(n, dtype=jnp.float32) + 0.5) / n
    logk = jax.random.permutation(kp, logk_lo + logk_span * u ** logk_power)
    return jnp.concatenate([x0, logk[:, None]], axis=1)
