#!/usr/bin/env python3
"""Smoke run of the NODE solver stack on a TPU, through its entry points.

    python3 chip_smoke.py               # one chip: solve, train, serve
    python3 chip_smoke.py --four-chips  # four chips: sharded solve only

One process drives the chip (a chip belongs to one process).  Every
phase raises on failure; nothing is caught and continued, and there is
no CPU fallback: without a TPU the script stops with ``NoTPUError``
before printing any result.

Phases (one chip):

* **solve** — ``odeint`` forward + ``jax.grad`` for aca, adjoint, naive
  and mali, solo (the whole batch as one lockstep state) and
  ``batch_axis=0``, each with ``use_pallas=True`` and ``False``, on the
  heavy-tailed-stiffness field of ``benchmarks/bench_sharded_solve.py``
  at B=64, D=256, plus an ACA solve at B=8, D=4096.  Every status must
  be OK, and the Pallas path must match the pytree path within
  ``PARITY_YS`` / ``PARITY_GRAD`` times the solve's rtol (Mosaic reduces
  in another order than XLA, so parity is not bitwise here).  The linear
  field dz/dt = k·z is checked against its closed form for every method.
* **train** — ``node18_cifar.CONFIG`` at full width in bf16 with
  ``NODE_TRAIN`` (HeunEuler, ACA, fused kernels, segmented checkpoints),
  built as ``examples/train_node_lm.py`` builds it (seq 128, batch 8),
  3 steps after a warm-up step.  Losses finite, no step skipped, step-0
  loss within ``TRAIN_LOSS_REL`` of a ``use_pallas=False`` build, the
  compiled step holds ``tpu_custom_call`` (the kernels really run), every
  NODE block of every step reports status OK with ``n_steps < max_steps``
  (the step's per-layer ``node_stats``), and the layer-0 NODE block
  solved alone matches its ``use_pallas=False`` solve within
  ``BLOCK_REL``.
* **serve** — ``NodeServeEngine`` on the row-tolerance kernel (8 slots,
  dim 256) answers 16 seeded requests at mixed tolerances, each within
  the chunked-parity bound of ``docs/serving.md`` of a one-shot
  ``odeint``.

``--four-chips`` runs only the B=64 ACA solve + grad sharded over
``shard_mesh()`` on four chips against the unsharded batched solve on
device 0: identical per-element trial counts, ``ys`` and z0-grads
bit-equal (``docs/distributed.md``), and a straggler count per shard.

Earlier lines report the device and what each phase checked; times are
the benchmark's (``chipbench/``).  The last line is the JSON verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no libtpu logs in /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.abspath(__file__))

METHODS = ("aca", "adjoint", "naive", "mali")
TS = (0.0, 1.0)
# heavy-tailed field: element b has stiffness exp(0.5 + span·(b/(B-1))⁵)
SOLVE_B, SOLVE_D, WIDE_B, WIDE_D = 64, 256, 8, 4096
RK_TOL, RK_STEPS, NAIVE_BUDGET, RK_SPAN = 1e-5, 512, 1024, 6.6
# a capped tail for two methods: MALI's explicit leapfrog pair is unstable
# on it (elements past exp(1.6) exhaust any step budget), and the
# adjoint's reverse-time solve of it amplifies rounding until its
# gradients differ by ~1e-2 from one path to the other
MALI_TOL, MALI_STEPS, MILD_SPAN = 1e-4, 512, 1.0
PARITY_YS, PARITY_GRAD = 10.0, 100.0    # × rtol, relative to max |ref|
LINEAR_REL = 100.0                      # × rtol, vs the closed form
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 128, 8, 3
TRAIN_LOSS_REL = 1e-2                   # bf16 step-0 loss, pallas vs pytree
BLOCK_REL = 5e-2                        # bf16 layer-0 block, pallas vs pytree
SERVE_SLOTS, SERVE_DIM, SERVE_REQUESTS = 8, 256, 16
SHARDED_TOL, SHARDED_STEPS, SHARDED_CHIPS = 1e-7, 1024, 4


class NoTPUError(RuntimeError):
    """JAX found no TPU (or too few chips) — this script never falls back."""


class SmokeFailure(AssertionError):
    """A phase produced a result outside its stated bound."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(count: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoTPUError(
            f"no TPU: jax.devices()[0].platform is "
            f"{devices[0].platform!r}; chip_smoke.py runs only on a TPU")
    if len(devices) < count:
        raise NoTPUError(f"needs {count} TPU chips, found {len(devices)}")
    return devices


def rel_err(a, ref) -> float:
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(a - ref).max() / max(np.abs(ref).max(), 1e-30))


# ------------------------------------------------------------------- solve

def heavy_tailed_problem(b: int, d: int, span: float, seed: int = 0):
    """``bench_sharded_solve``'s batch: d-1 coupled states plus a constant
    log-stiffness slot; w is the (d-1, d-1) coupling."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(k1, (d - 1, d - 1)) * (0.3 / d ** 0.5)
    x0 = jax.random.normal(k2, (b, d - 1)) * 0.5
    frac = jnp.arange(b) / max(b - 1.0, 1.0)
    logk = 0.5 + span * frac ** 5
    return jnp.concatenate([x0, logk[:, None]], axis=1), w


def heavy_tailed_field(t, z, w):
    x, logk = z[:-1], z[-1]
    # f32 at full precision: the TPU's default one-pass bf16 matmul would
    # put rounding noise far above the solve tolerance
    xw = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    dx = -jnp.exp(logk) * x + 0.5 * jnp.tanh(xw)
    return jnp.concatenate([dx, jnp.zeros((1,), z.dtype)])


def lockstep_field(t, z, w):
    return jax.vmap(heavy_tailed_field, (None, 0, None))(t, z, w)


def method_kw(method: str) -> dict:
    if method == "mali":
        return dict(grad_method="mali", rtol=MALI_TOL, atol=MALI_TOL,
                    max_steps=MALI_STEPS)
    kw = dict(grad_method=method, solver="dopri5", rtol=RK_TOL,
              atol=RK_TOL, max_steps=RK_STEPS)
    if method == "naive":
        kw["trial_budget"] = NAIVE_BUDGET
    return kw


def solve_and_grad(f, z0, args, kw):
    """One jitted forward + grad; returns (ys, stats, grads)."""
    from repro.core import odeint

    def loss(z0, args):
        ys, st = odeint(f, z0, jnp.asarray(TS), args, **kw)
        return jnp.sum(ys[-1] ** 2), (ys, st)

    (_, (ys, st)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(z0, args)
    return jax.device_get((ys, st, grads))


def check_status(name, st):
    status = np.asarray(st.status)
    check(np.all(status == 0),
          f"{name}: solve status {np.unique(status).tolist()} (0 = OK)")


def heavy_tailed_case(method, b, d, span):
    z0, w = heavy_tailed_problem(b, d, span)
    kw = method_kw(method)
    out = {}
    for batched in (False, True):
        f = heavy_tailed_field if batched else lockstep_field
        mode = "batched" if batched else "solo"
        for pallas in (True, False):
            name = f"{method}/{mode}/{'pallas' if pallas else 'pytree'}/" \
                   f"{b}x{d}"
            ys, st, grads = solve_and_grad(
                f, z0, (w,), dict(kw, use_pallas=pallas,
                                  batch_axis=0 if batched else None))
            check_status(name, st)
            check(all(np.isfinite(g).all() for g in jax.tree.leaves(grads)),
                  f"{name}: non-finite gradient")
            print(f"  {name}: trials max {int(np.max(st.n_trials))}, "
                  f"steps max {int(np.max(st.n_steps))}", flush=True)
            out[pallas] = (ys, grads)
        (ys1, g1), (ys0, g0) = out[True], out[False]
        e_ys = rel_err(ys1[-1], ys0[-1])
        e_g = max(rel_err(a, b_) for a, b_ in zip(jax.tree.leaves(g1),
                                                   jax.tree.leaves(g0)))
        print(f"  {method}/{mode}/{b}x{d}: pallas vs pytree rel err ys "
              f"{e_ys:.3e}, grads {e_g:.3e}", flush=True)
        check(e_ys <= PARITY_YS * kw["rtol"],
              f"{method}/{mode}: pallas ys off by {e_ys:.3e} rel")
        check(e_g <= PARITY_GRAD * kw["rtol"],
              f"{method}/{mode}: pallas grads off by {e_g:.3e} rel")


def linear_case(method, batched):
    """dz/dt = k·z from z0 over [0, 1] has z(1) = z0·eᵏ: every method's
    solution and gradients must land on the closed form."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    shape = (4, SOLVE_D) if batched else (SOLVE_D,)
    z0 = jax.random.normal(k1, shape)
    k = jax.random.uniform(k2, shape[-1:], minval=-1.0, maxval=0.5)
    kw = dict(method_kw(method), use_pallas=True,
              batch_axis=0 if batched else None)
    if method != "mali":
        kw.update(rtol=1e-6, atol=1e-6)
    ys, st, (gz, gk) = solve_and_grad(lambda t, z, k: k * z, z0, k, kw)
    name = f"linear/{method}/{'batched' if batched else 'solo'}"
    check_status(name, st)
    ek = np.exp(np.asarray(k))
    exact = np.asarray(z0) * ek
    # loss Σ z(1)²: ∂/∂z0 = 2·z(1)·eᵏ, ∂/∂k = Σ_batch 2·z(1)²
    for what, got, want in (
            ("z(1)", ys[-1], exact), ("dz0", gz, 2 * exact * ek),
            ("dk", gk, (2 * exact ** 2).reshape(-1, SOLVE_D).sum(0))):
        err = rel_err(got, want)
        check(err <= LINEAR_REL * kw["rtol"],
              f"{name}: {what} off the closed form by {err:.3e} rel")
    print(f"  {name}: closed form ok, trials "
          f"{int(np.max(st.n_trials))}", flush=True)


def solve_phase():
    for method in METHODS:
        span = MILD_SPAN if method in ("adjoint", "mali") else RK_SPAN
        heavy_tailed_case(method, SOLVE_B, SOLVE_D, span)
    heavy_tailed_case("aca", WIDE_B, WIDE_D, RK_SPAN)
    for method in METHODS:
        for batched in (False, True):
            linear_case(method, batched)


# ------------------------------------------------------------------- train

def train_phase():
    from repro.configs.node18_cifar import CONFIG, NODE_TRAIN
    from repro.core import SolveStatus, odeint_final
    from repro.data import TokenPipeline
    from repro.models import RunConfig, build_model
    from repro.models.lm import _embed
    from repro.models.transformer import block_apply
    from repro.optim import adamw, cosine_warmup
    from repro.train import TrainLoop, TrainLoopConfig, make_train_state

    def run_config(node):
        return RunConfig(compute_dtype=jnp.bfloat16, node=node, remat="none")

    model = build_model(CONFIG, run_config(NODE_TRAIN))
    ref_model = build_model(CONFIG, run_config(
        dataclasses.replace(NODE_TRAIN, use_pallas=False)))
    print(f"  model {CONFIG.name}: {model.n_params() / 1e6:.1f}M params, "
          f"{CONFIG.n_layers}x{CONFIG.d_model}, vocab {CONFIG.vocab}, "
          f"seq {TRAIN_SEQ} batch {TRAIN_BATCH}, bf16", flush=True)
    pipe = TokenPipeline(vocab=CONFIG.vocab, seq_len=TRAIN_SEQ,
                         global_batch=TRAIN_BATCH, seed=0)
    opt = adamw(cosine_warmup(3e-4, 20, 300), weight_decay=0.1)
    lcfg = TrainLoopConfig(microbatches=1, clip_norm=1.0, ckpt_dir=None,
                           log_every=1)
    state = make_train_state(model, opt, jax.random.PRNGKey(0))
    batch0 = pipe.batch(0)

    # everything that reads the initial params runs before the loop
    # donates them
    ref_loss = float(jax.jit(ref_model.loss_fn)(state.params, batch0)[0])

    # layer 0's NODE block, solved as node_block_apply solves it
    node = NODE_TRAIN
    p0 = jax.tree.map(lambda v: v[0], state.params["stack"]["u0_attn"])
    x0 = _embed(state.params, batch0, CONFIG, model.rcfg)

    def block(t, z, p):
        return block_apply(p, z, CONFIG, model.rcfg, "attn")[0] - z

    block_out = {}
    for pallas in (True, False):
        z1, st = jax.jit(lambda x, p: odeint_final(
            block, x, node.t0, node.t1, (p,), solver=node.solver,
            grad_method=node.grad_method, rtol=node.rtol, atol=node.atol,
            max_steps=node.max_steps, use_pallas=pallas,
            checkpoint_segments=node.checkpoint_segments))(x0, p0)
        print(f"  layer-0 block ({'pallas' if pallas else 'pytree'}): "
              f"status {SolveStatus.describe(int(st.status))}, n_steps "
              f"{int(st.n_steps)}/{node.max_steps}, trials "
              f"{int(st.n_trials)}", flush=True)
        block_out[pallas] = z1
    e_block = rel_err(block_out[True], block_out[False])
    print(f"  layer-0 block pallas vs pytree rel err {e_block:.3e}",
          flush=True)
    check(e_block <= BLOCK_REL, f"layer-0 block off by {e_block:.3e} rel")

    loop = TrainLoop(model, opt, lcfg, state)
    # the private jitted step is the one the loop runs: its compiled text
    # shows whether the kernels survived into the program
    hlo = loop._step_fn.lower(loop.state, batch0,
                              loop.comp_state).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    print(f"  train step: tpu_custom_call x{n_kernels}", flush=True)
    check(n_kernels > 0, "compiled train step holds no tpu_custom_call")

    metrics = []
    loop.run(pipe.batch, 1 + TRAIN_STEPS,
             log_cb=lambda s, m: metrics.append(m))
    losses = [m["loss"] for m in metrics]
    nfe = [m["node_stats"].nfe for m in metrics]
    print(f"  losses {['%.4f' % v for v in losses]}, step-0 pytree loss "
          f"{ref_loss:.4f}; field evaluations per block {nfe}", flush=True)
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    check(loop.skipped_steps == 0 and not any(m["skipped"] for m in metrics),
          f"{loop.skipped_steps} step(s) skipped")
    check(abs(losses[0] - ref_loss) <= TRAIN_LOSS_REL * abs(ref_loss),
          f"step-0 loss {losses[0]:.5f} vs pytree build {ref_loss:.5f}")
    for m in metrics:
        st = m["node_stats"]
        check(all(s == SolveStatus.OK for s in st.status)
              and max(st.n_steps) < node.max_steps,
              f"NODE blocks: status {st.status}, n_steps {st.n_steps}")


# ------------------------------------------------------------------- serve

def serve_phase():
    from repro.core import odeint
    from repro.serve import NodeEngineConfig, NodeRequest, NodeServeEngine

    w = jax.random.normal(jax.random.PRNGKey(3), (SERVE_DIM, SERVE_DIM)) \
        * (1.0 / SERVE_DIM ** 0.5)

    def field(t, z, w):
        zw = jnp.dot(z, w, precision=jax.lax.Precision.HIGHEST)
        return jnp.tanh(zw) - 0.1 * z * jnp.sin(t)

    rng = np.random.default_rng(0)
    traffic, t = [], 0.0
    for _ in range(SERVE_REQUESTS):
        t += float(rng.exponential(4.0))
        rtol = float(rng.choice((1e-3, 1e-4, 1e-5)))
        traffic.append((t, NodeRequest(
            z0=rng.normal(size=(SERVE_DIM,)).astype(np.float32), t0=0.0,
            t1=float(rng.choice((0.5, 1.0, 4.0))), rtol=rtol,
            atol=rtol * 1e-2)))
    eng = NodeServeEngine(field, SERVE_DIM, (w,), NodeEngineConfig(
        slots=SERVE_SLOTS, chunk_dt=0.5, use_pallas=True))
    for arrival, req in traffic:
        eng.submit(req, arrival=arrival)
    results = {r.req_id: r for r in eng.run()}
    print(f"  served {len(results)} requests in {eng.round} rounds",
          flush=True)
    check(len(results) == SERVE_REQUESTS and all(
        r.ok for r in results.values()),
        f"served statuses {[r.status for r in results.values()]}")

    ref = jax.jit(lambda z0, ts, rtol, atol: odeint(
        field, z0, ts, (w,), rtol=rtol, atol=atol)[0][-1],
        static_argnums=(2, 3))
    worst = 0.0
    for rid, (_, req) in enumerate(traffic):
        want = np.asarray(ref(jnp.asarray(req.z0),
                              jnp.asarray([req.t0, req.t1], jnp.float32),
                              req.rtol, req.atol))
        r = results[rid]
        bound = (r.n_chunks + 1) * (
            req.atol + req.rtol * max(1.0, float(np.abs(want).max())))
        worst = max(worst, float(np.abs(r.z_final - want).max()) / bound)
    print(f"  worst served-vs-one-shot error / bound {worst:.4f}",
          flush=True)
    check(worst < 1.0, f"served answer off the chunked-parity bound "
          f"({worst:.3f} of it)")


# ------------------------------------------------------------ four chips

def sharded_phase(devices):
    from repro.core import odeint
    from repro.distributed import shard_mesh

    z0, w = heavy_tailed_problem(SOLVE_B, SOLVE_D, RK_SPAN)
    kw = dict(solver="dopri5", grad_method="aca", rtol=SHARDED_TOL,
              atol=SHARDED_TOL, max_steps=SHARDED_STEPS, use_pallas=True,
              batch_axis=0)

    def loss(z0, w, mesh):
        ys, st = odeint(heavy_tailed_field, z0, jnp.asarray(TS), (w,),
                        mesh=mesh, **kw)
        return jnp.sum(ys[-1] ** 2), (ys, st)

    grad = functools.partial(jax.value_and_grad, argnums=0, has_aux=True)
    mesh = shard_mesh(devices[:SHARDED_CHIPS])
    sharded = jax.device_get(
        jax.jit(grad(functools.partial(loss, mesh=mesh)))(z0, w))
    one = jax.device_put((z0, w), devices[0])
    unsharded = jax.device_get(
        jax.jit(grad(functools.partial(loss, mesh=None)))(*one))
    (_, (ys_s, st_s)), g_s = sharded
    (_, (ys_u, st_u)), g_u = unsharded
    check_status("sharded", st_s)
    check_status("unsharded", st_u)
    trials = np.asarray(st_s.n_trials)
    per_shard = trials.reshape(SHARDED_CHIPS, -1).max(axis=1)
    print(f"  per-shard straggler trials {per_shard.tolist()} (global "
          f"{int(trials.max())}, median element "
          f"{int(np.median(trials))})", flush=True)
    check(np.array_equal(trials, np.asarray(st_u.n_trials)),
          "per-element trial counts differ sharded vs unsharded")
    check(len(set(per_shard.tolist())) > 1,
          f"every shard ran the global straggler's count {per_shard}")
    for what, a, b in (("ys", ys_s, ys_u), ("z0 grad", g_s, g_u)):
        err = rel_err(a, b)
        print(f"  {what}: sharded vs unsharded rel err {err:.3e} "
              f"(bit-equal: {np.array_equal(a, b)})", flush=True)
        check(np.array_equal(a, b), f"sharded {what} not bit-equal "
              f"(rel err {err:.3e})")


# -------------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded solve over four chips")
    args = ap.parse_args()
    n_chips = SHARDED_CHIPS if args.four_chips else 1
    devices = require_tpu(n_chips)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)
    phases = ([("sharded", functools.partial(sharded_phase, devices))]
              if args.four_chips else
              [("solve", solve_phase), ("train", train_phase),
               ("serve", serve_phase)])
    for name, phase in phases:
        phase()
        print(f"phase {name}: ok", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
