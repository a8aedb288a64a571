"""Quickstart: solve an ODE, differentiate through it with ACA, and
compare the three gradient methods (paper Eq. 27-29).

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import odeint

# --- 1. solve dz/dt = k z ---------------------------------------------
k, T = -2.0, 3.0


def f(t, z, k):
    return k * z


ts = jnp.linspace(0.0, T, 5)
ys, stats = odeint(f, jnp.float32(1.5), ts, (jnp.float32(k),),
                   solver="dopri5", grad_method="aca",
                   rtol=1e-6, atol=1e-6)
print("z(t):", np.round(np.asarray(ys), 5))
print("exact:", np.round(1.5 * np.exp(k * np.asarray(ts)), 5))
print(f"accepted steps: {int(stats.n_steps)}, NFE: {int(stats.nfe)}")

# --- 2. gradients: ACA vs adjoint vs naive vs MALI ---------------------
analytic = 2 * 1.5 * np.exp(2 * k * T)
print(f"\nanalytic dL/dz0 = {analytic:.6e}   (L = z(T)^2)")
for method in ("aca", "adjoint", "naive", "mali"):
    def loss(z0):
        # mali integrates with the reversible ALF pair stepper (no RK
        # tableau): solver resolves to "alf", and its 2nd-order steps
        # need a larger accepted-step budget at this tolerance
        ys, _ = odeint(f, z0, jnp.array([0.0, T]), (jnp.float32(k),),
                       solver=None if method == "mali" else "dopri5",
                       grad_method=method,
                       max_steps=4096 if method == "mali" else 256,
                       rtol=1e-5, atol=1e-5)
        return (ys[-1] ** 2).sum()

    g = float(jax.grad(loss)(jnp.float32(1.5)))
    print(f"{method:8s} dL/dz0 = {g:.6e}   "
          f"rel err = {abs(g - analytic) / abs(analytic):.2e}")

# --- 3. a NODE block: continuous-depth layer (paper Eq. 30 -> 31) ------
from repro.core import NodeConfig, node_block_apply

params = {"w1": jax.random.normal(jax.random.PRNGKey(0), (8, 32)) * 0.3,
          "w2": jax.random.normal(jax.random.PRNGKey(1), (32, 8)) * 0.3}


def block_fn(p, z, t):
    return jnp.tanh(z @ p["w1"]) @ p["w2"]


z = jax.random.normal(jax.random.PRNGKey(2), (4, 8))
zT, stats = node_block_apply(block_fn, params, z,
                             NodeConfig(enabled=True, solver="heun_euler",
                                        grad_method="aca"))
print("\nNODE block: in", z.shape, "-> out", zT.shape,
      "| param count unchanged:", sum(p.size for p in params.values()),
      "| field evaluations:", int(stats.nfe))
