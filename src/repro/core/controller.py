"""PI stepsize controller for adaptive embedded RK solvers.

Implements the standard proportional-integral controller (Hairer & Wanner,
"Solving ODEs II", IV.2) used by production solvers: the next stepsize is

    h_next = h * clip(safety * ratio^{-k_I} * prev_ratio^{k_P}, dfac, ifac)

with ratio the scaled error norm of the current trial.  This generalizes the
paper's ``h <- h * decay_factor(e_hat)`` (Algorithm 1): the pure-P controller
is recovered with pi_coeff=0.  Also provides the classical initial-stepsize
selection of Hairer I.4 (algorithm ``hinit``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from .stepper import field_eval


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    """PI stepsize-controller settings + the solve's step/trial budgets.

    ``max_steps`` bounds *accepted* steps (= checkpoint-buffer capacity,
    the paper's N_t); ``max_trials`` bounds the inner stepsize search per
    step (the paper's m), so one solve performs at most ``max_steps *
    max_trials`` ψ trials.
    """
    safety: float = 0.9
    min_factor: float = 0.2     # max shrink per retry
    max_factor: float = 10.0    # max growth after accept
    pi_coeff: float = 0.04      # k_P (integral-of-log smoothing); 0 = plain P
    max_steps: int = 256        # checkpoint-buffer capacity (paper's N_t bound)
    max_trials: int = 12        # bound on the paper's m (inner search)


def propose_stepsize(cfg: ControllerConfig, h, ratio, prev_ratio, order: int):
    """Next stepsize after a trial with scaled error ``ratio``.

    Used both for shrink-on-reject and grow-on-accept; the PI term uses the
    previous accepted step's ratio.
    """
    order = float(order)
    k_i = 1.0 / order
    k_p = cfg.pi_coeff
    # guard against ratio == 0 (exact solution) -> max growth
    ratio = jnp.maximum(ratio, 1e-10)
    prev_ratio = jnp.maximum(prev_ratio, 1e-10)
    factor = cfg.safety * ratio ** (-k_i) * prev_ratio ** k_p
    factor = jnp.clip(factor, cfg.min_factor, cfg.max_factor)
    return h * factor


def initial_stepsize(f, t0, z0, args, order: int, rtol: float, atol: float):
    """Hairer I.4 'starting step size' heuristic, pytree-valued states."""
    def _norm(x):
        leaves = jax.tree.leaves(x)
        sq = sum(jnp.sum((l.astype(jnp.float32)) ** 2) for l in leaves)
        n = sum(l.size for l in leaves)
        return jnp.sqrt(sq / n)

    scale = jax.tree.map(
        lambda z: atol + rtol * jnp.abs(z), z0)

    f0 = field_eval(f, t0, z0, *args)
    d0 = _norm(jax.tree.map(lambda z, s: z / s, z0, scale))
    d1 = _norm(jax.tree.map(lambda g, s: g / s, f0, scale))
    h0 = jnp.where((d0 < 1e-5) | (d1 < 1e-5), 1e-6, 0.01 * d0 / d1)

    z1 = jax.tree.map(lambda z, g: z + h0 * g, z0, f0)
    f1 = field_eval(f, t0 + h0, z1, *args)
    d2 = _norm(jax.tree.map(lambda a, b, s: (a - b) / s, f1, f0, scale)) / h0
    dmax = jnp.maximum(d1, d2)
    # Hairer I.4 step (f): h1 = (0.01 / max(d1, d2))^(1/(p+1)) — the
    # exponent is 1/(order + 1), matching the local error O(h^{p+1})
    h1 = jnp.where(
        dmax <= 1e-15,
        jnp.maximum(1e-6, h0 * 1e-3),
        (0.01 / dmax) ** (1.0 / (float(order) + 1.0)),
    )
    return jnp.minimum(100.0 * h0, h1)
