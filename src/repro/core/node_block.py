"""Continuous-depth (NODE) block for model stacks.

The paper's ResNet→NODE transformation (Eq. 30 → Eq. 31): a residual block
``y = x + f(x, θ)`` becomes an ODE block ``z(1) = z(0) + ∫₀¹ f(z(t), θ) dt``
with the *same* parameter count.  Here ``f`` is any per-layer apply function
(a transformer block, conv block, ...) and the integral is solved with the
configured solver + gradient method — ACA by default.

For multi-pod lowering, NODE mode supports two regimes:

* ``adaptive`` — HeunEuler/RK23/RK45 with a dynamic (while_loop) trip
  count; legal under jit/pjit, used for single-host training exactly like
  the paper.
* ``fixed``   — a static grid (odeint_aca_fixed): static step count, the
  regime used for the 512-device dry-run and at pod scale where a static
  schedule keeps collectives deterministic across hosts (a straggler/
  determinism requirement, not a correctness one).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .api import odeint_final
from .integrate import SolveStats

PyTree = Any


@dataclasses.dataclass(frozen=True)
class NodeConfig:
    """Solver/gradient configuration of one continuous-depth (NODE) block.

    Defaults follow the paper's training setup (HeunEuler, ACA,
    rtol=atol=1e-2).  ``regime`` picks dynamic adaptive stepping vs the
    static fixed grid used at pod scale; ``use_pallas`` enables the
    fused flat-state solver kernels; ``batch_axis`` turns on per-sample
    batched solving; ``checkpoint_segments`` bounds the ACA trajectory-
    checkpoint memory to K state snapshots per solve (see ``odeint``).

    ``grad_method="mali"`` switches the block to the reversible
    asynchronous-leapfrog integrator (O(1)-state-memory exact-reverse
    gradients — ``solver`` is then forced to ``"alf"``, the only legal
    pairing); it supports only the ``adaptive`` regime (the reversible
    pair stepper has no fixed-grid mode) and no ``checkpoint_segments``
    (there is nothing to segment).  See ``docs/method-selection.md``.
    """
    enabled: bool = False
    solver: str = "heun_euler"      # the paper trains with HeunEuler
    grad_method: str = "aca"
    rtol: float = 1e-2              # paper Appendix D: rtol=atol=1e-2
    atol: float = 1e-2
    max_steps: int = 32
    steps_per_interval: int = 4     # fixed-grid regime
    regime: str = "adaptive"        # adaptive | fixed
    # integration window [t0, t1]; t0 > t1 runs the block in REVERSE
    # time (odeint's descending-ts path) — e.g. inverting a flow or
    # stacking forward/backward blocks
    t0: float = 0.0
    t1: float = 1.0
    use_pallas: bool = False        # fused flat-state solver kernels
    # per-sample batched solving: axis of z0 carrying the batch (None =
    # lockstep).  With a batch axis every sample in the block's input
    # integrates on its own adaptive grid — see odeint(batch_axis=...).
    batch_axis: Optional[int] = None
    # segmented O(K)-state ACA checkpointing (adaptive regime, ACA
    # only): int K, "auto" (= ceil(sqrt(max_steps))) or None for the
    # classic full buffer.  Gradients are bit-identical either way —
    # this is purely a memory/recompute trade — see odeint()
    checkpoint_segments: Optional[Any] = None
    # solve-health policy: "status" (default, report via stats.status),
    # "warn" (jax.debug.print on failure) or "raise" (checkify check —
    # functionalize jitted callers with checkify.checkify); see
    # docs/robustness.md
    on_failure: str = "status"
    # jax.sharding.Mesh to shard the batch over (requires batch_axis):
    # the block's solve runs shard_map-ed over the mesh's data axes —
    # per-device adaptive trip counts, shard-local backward sweeps, one
    # psum on the shared-params cotangent.  See docs/distributed.md.
    mesh: Optional[Any] = None
    # AxisRules override for the mesh's batch-partition axes (None =
    # DEFAULT_TRAIN_RULES: "batch" -> ("pod", "data"))
    shard_rules: Optional[Any] = None


def node_block_apply(
    block_fn: Callable[[PyTree, PyTree, jnp.ndarray], PyTree],
    params: PyTree,
    z0: PyTree,
    cfg: NodeConfig,
) -> Tuple[PyTree, SolveStats]:
    """z(t1) = z(0) + ∫ f(z, t; θ) dt with ACA/adjoint/naive gradients.

    ``block_fn(params, z, t) -> dz/dt`` must preserve the shape/dtype of z.
    Returns ``(z(t1), stats)``: the forward solve's ``SolveStats``
    (accepted steps, trials, field evaluations, status), scalars — or
    per-sample arrays with ``batch_axis`` set.
    """

    def f(t, z, p):
        return block_fn(p, z, t)

    if cfg.grad_method == "mali" and cfg.regime == "fixed":
        raise ValueError(
            "NodeConfig(grad_method='mali', regime='fixed'): the "
            "reversible pair integrator is adaptive-only — use "
            "regime='adaptive', or a fixed RK grid with aca/adjoint/"
            "naive for static pod-scale schedules")

    if cfg.regime == "fixed":
        return odeint_final(
            f, z0, cfg.t0, cfg.t1, (params,),
            solver=_fixed_solver_for(cfg.solver),
            grad_method=cfg.grad_method,
            steps_per_interval=cfg.steps_per_interval,
            use_pallas=cfg.use_pallas,
            batch_axis=cfg.batch_axis,
            # threaded so a segmented config on the fixed regime raises
            # the api's informative error instead of silently ignoring
            checkpoint_segments=cfg.checkpoint_segments,
            on_failure=cfg.on_failure,
            mesh=cfg.mesh, shard_rules=cfg.shard_rules,
        )
    return odeint_final(
        f, z0, cfg.t0, cfg.t1, (params,),
        # mali pairs only with the ALF pair integrator; the RK
        # solver name in the config is a don't-care for that method
        solver="alf" if cfg.grad_method == "mali" else cfg.solver,
        grad_method=cfg.grad_method,
        rtol=cfg.rtol, atol=cfg.atol,
        max_steps=cfg.max_steps,
        use_pallas=cfg.use_pallas,
        batch_axis=cfg.batch_axis,
        checkpoint_segments=cfg.checkpoint_segments,
        on_failure=cfg.on_failure,
        mesh=cfg.mesh, shard_rules=cfg.shard_rules,
    )


def _fixed_solver_for(name: str) -> str:
    """Map an adaptive pair to its advancing fixed-step method."""
    return {
        "heun_euler": "rk2",
        "heuneuler": "rk2",
        "bosh3": "rk2",
        "rk23": "rk2",
        "dopri5": "rk4",
        "rk45": "rk4",
    }.get(name.lower().replace("-", "_"), name)
