"""Mesh-sharded batched solve: host-device scaling of odeint(mesh=...).

The per-sample batched engine is embarrassingly parallel over the batch
— but on ONE device it still runs *lockstep in time*: every while_loop
iteration advances all B controller lanes, so the whole batch pays the
global straggler's iteration count.  Sharding the batch over a mesh
gives every shard its own trip count; with a heavy-tailed stiffness
batch (most elements easy, one very stiff) the per-shard work collapses
from ``B × max_b(trials)`` to ``Σ_s B_s × max_{b∈s}(trials)``, which is
why this benchmark speeds up even on a single CPU core running the
shards serially — it measures eliminated lockstep waste, not core
count, so it is stable in CI.  Every rung runs on the CPU backend
(``JAX_PLATFORMS=cpu``), also on a machine with a chip.

Protocol: the SAME B=64 dopri5/ACA solve (d=256 state, stiffness
``logk = 0.5 + 6.6·frac⁵`` — top element ≈40× more trials than the
median) is timed in a fresh subprocess per device count n ∈ {1,2,4,8}
(``--xla_force_host_platform_device_count`` is locked at jax init, so
each rung needs its own process), with per-device trial counts read
back from ``SolveStats``.  Headline gates (full and quick):

  * per-element trial counts identical on every rung (the sharded
    solve IS the unsharded solve, shard-locally);
  * throughput at 8 devices ≥ 3× the 1-device rung.

Emits BENCH_sharded_solve.json (speedups, scaling efficiency, straggler
trial spread) into the artifact trajectory.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from .common import emit, emit_json

DEVICE_LADDER = (1, 2, 4, 8)
B = 64
DIM = 256
MIN_SPEEDUP_8 = 3.0

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(n_dev: int, n_iter: int) -> None:
    """One rung: time the sharded solve on ``n_dev`` forced host devices
    (XLA_FLAGS comes from the parent's env) and print a JSON line."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import odeint
    from repro.distributed import shard_mesh

    assert jax.device_count() == n_dev, (jax.device_count(), n_dev)

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w = (jax.random.normal(k1, (DIM, DIM))
         * (0.3 / DIM ** 0.5)).astype(jnp.float32)
    x0 = (jax.random.normal(k2, (B, DIM - 1)) * 0.5).astype(jnp.float32)
    # heavy-tailed stiffness: most elements easy, the top shard stiff
    frac = jnp.arange(B) / (B - 1.0)
    logk = (0.5 + 6.6 * frac ** 5).astype(jnp.float32)
    z0 = jnp.concatenate([x0, logk[:, None]], axis=1)
    ts = jnp.array([0.0, 1.0], jnp.float32)

    def f(t, z, w):
        x, logk = z[:-1], z[-1]
        dx = -jnp.exp(logk) * x + 0.5 * jnp.tanh(x @ w[:-1, :-1])
        return jnp.concatenate([dx, jnp.zeros((1,), z.dtype)])

    mesh = shard_mesh()
    run = jax.jit(lambda z0, w: odeint(
        f, z0, ts, (w,), solver="dopri5", rtol=1e-7, atol=1e-7,
        max_steps=1024, grad_method="aca", batch_axis=0, mesh=mesh))

    ys, st = jax.block_until_ready(run(z0, w))
    t0 = time.monotonic()
    for _ in range(n_iter):
        jax.block_until_ready(run(z0, w))
    dt = (time.monotonic() - t0) / n_iter

    trials = np.asarray(st.n_trials)
    per_dev = trials.reshape(n_dev, -1).max(axis=1)
    print(json.dumps({
        "n_dev": n_dev,
        "t_s": dt,
        "throughput_el_s": B / dt,
        "trials_min": int(trials.min()),
        "trials_max": int(trials.max()),
        "trials_sum": int(trials.sum()),
        "dev_straggler_trials": per_dev.tolist(),
        "ys_sum": float(jnp.sum(ys)),
    }), flush=True)


def _run_rung(n_dev: int, n_iter: int) -> dict:
    # the rungs are forced CPU host devices: a child must never ask for
    # the chip, which the parent process may already hold (the on-chip
    # sharded solve is ``chip_smoke.py --four-chips``)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(_REPO, "src"),
                    env.get("PYTHONPATH", "")] if p)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded_solve",
         "--child", str(n_dev), "--iters", str(n_iter)],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded-solve rung n_dev={n_dev} failed:\n{proc.stdout}\n"
            f"{proc.stderr}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("{")][-1]
    return json.loads(line)


def run(quick: bool = True) -> None:
    n_iter = 3 if quick else 10
    rungs = {}
    for n_dev in DEVICE_LADDER:
        rungs[n_dev] = r = _run_rung(n_dev, n_iter)
        emit(f"sharded_solve/t_ms/{n_dev}dev", f"{r['t_s'] * 1e3:.1f}")
        emit(f"sharded_solve/throughput_el_s/{n_dev}dev",
             f"{r['throughput_el_s']:.1f}")
        emit(f"sharded_solve/straggler_trials/{n_dev}dev",
             f"{max(r['dev_straggler_trials'])}")

    base = rungs[DEVICE_LADDER[0]]
    # the sharded solve must BE the unsharded solve: identical
    # per-element trial counts (and forward sums) on every rung
    for n_dev, r in rungs.items():
        same = (r["trials_min"] == base["trials_min"]
                and r["trials_max"] == base["trials_max"]
                and r["trials_sum"] == base["trials_sum"])
        if not same:
            raise AssertionError(
                f"per-element trial counts changed under sharding at "
                f"n_dev={n_dev}: {r} vs 1-device {base}")

    speedups = {n: base["t_s"] / rungs[n]["t_s"] for n in DEVICE_LADDER}
    for n_dev in DEVICE_LADDER[1:]:
        emit(f"sharded_solve/speedup/{n_dev}dev", f"{speedups[n_dev]:.2f}")
        emit(f"sharded_solve/scaling_eff/{n_dev}dev",
             f"{speedups[n_dev] / n_dev:.2f}")

    s8 = speedups[8]
    ok = s8 >= MIN_SPEEDUP_8
    emit("sharded_solve/speedup_8dev_ge_3x", f"{int(ok)}",
         f"measured {s8:.2f}x")
    emit_json("sharded_solve", {
        "batch": B,
        "dim": DIM,
        "t_ms_1dev": base["t_s"] * 1e3,
        "t_ms_8dev": rungs[8]["t_s"] * 1e3,
        "speedup_2dev": speedups[2],
        "speedup_4dev": speedups[4],
        "speedup_8dev": s8,
        "scaling_eff_8dev": s8 / 8.0,
        "throughput_el_s_8dev": rungs[8]["throughput_el_s"],
        "straggler_trials": base["trials_max"],
        "median_shard_trials_8dev": sorted(
            rungs[8]["dev_straggler_trials"])[4],
        "gate_speedup_8dev_ge_3x": int(ok),
    })
    if not ok:
        raise AssertionError(
            f"sharded solve speedup at 8 devices is {s8:.2f}x < "
            f"{MIN_SPEEDUP_8}x — lockstep waste is not being eliminated "
            "(per-shard trip counts should collapse to shard-local "
            "stragglers)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--child", type=int, default=None,
                    help="internal: run one rung at this device count")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()
    if args.child is not None:
        _child(args.child, args.iters)
    else:
        run(quick=args.quick)


if __name__ == "__main__":
    main()
