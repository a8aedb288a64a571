"""Batched adaptive solves with gradients, called back to back.

Each call is ``jax.value_and_grad`` of ``sum(z(t1)^2)`` over
``odeint(..., batch_axis=0)`` w.r.t. z0 and the field's coupling, on one
of ``batches`` batches of ``rows`` rows made on the device from the
seed (calls alternate over them).  The window keeps ``IN_FLIGHT`` calls
queued behind the one it waits for.

On several chips the solve runs on their mesh (``odeint(mesh=...)``
over ``repro.distributed.shard_mesh``): each batch is made on the
device already split by rows over the mesh's ``"data"`` axis, with the
coupling replicated, so that a call moves no input between chips.

Correct: after the window, the last answer of every batch (every row's
final state and z0-gradient, and the coupling's gradient) is compared
with the plain reference (``harness/ref_ode.py``) solving the same rows
from the same seed, in blocks of ``ref_block_rows`` rows spread over
the cell's chips in turn; the control is that reference computed at the
next lower matmul precision.
"""

from __future__ import annotations

import collections
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from harness import counts, ref_ode, seeds
from harness.runner import BENCH_DIR, Check, load_module, span

CONTROL_PRECISION = {"highest": "high"}
# calls queued behind the one the window waits for: the device keeps
# working through a short pause of the host
IN_FLIGHT = 3


def _tableau(name):
    if name != "dopri5":
        raise ValueError(f"the solve driver's reference knows dopri5, "
                         f"not {name!r}")
    return ref_ode.Tableau(counts.DOPRI5_A, counts.DOPRI5_B,
                           counts.DOPRI5_E, counts.DOPRI5_C, 5, True)


class Driver:
    def __init__(self, config, traffic, seed, devices):
        self.cfg, self.tr, self.seed = config, traffic, seed
        self.devices = devices
        self.model = load_module(os.path.join(
            BENCH_DIR, "configs", config["name"] + ".py"))
        self.rows = int(traffic["rows"])
        self.n_batches = int(traffic.get("batches", 1))
        self.mesh = None
        if len(devices) > 1:
            from repro.distributed import shard_mesh
            self.mesh = shard_mesh(devices)

    # ---------------------------------------------------------- program
    def _problem(self):
        cfg, tr, model = self.cfg, self.tr, self.model

        def make(key):
            kw, *kb = jax.random.split(key, 1 + self.n_batches)
            zs = [model.rows(k, self.rows, cfg, tr["logk_lo"],
                             tr["logk_span"], tr["logk_power"]) for k in kb]
            return jnp.stack(zs), model.coupling(kw, cfg)

        if self.mesh is None:
            return jax.jit(make)(seeds.key(self.seed))
        # rows split over the mesh, the coupling on every chip
        by_rows = NamedSharding(self.mesh, PartitionSpec(None, "data"))
        everywhere = NamedSharding(self.mesh, PartitionSpec())
        return jax.jit(make, out_shardings=(by_rows, everywhere))(
            seeds.key(self.seed))

    def setup(self):
        from repro.core import odeint

        cfg = self.cfg
        field = self.model.row_field(cfg["matmul_precision"])
        ts = jnp.asarray([cfg["t0"], cfg["t1"]], jnp.float32)
        kw = dict(solver=cfg["solver"], grad_method=cfg["grad_method"],
                  rtol=cfg["rtol"], atol=cfg["atol"],
                  max_steps=cfg["max_steps"], use_pallas=cfg["use_pallas"],
                  batch_axis=0)
        if self.mesh is not None:
            kw["mesh"] = self.mesh

        def loss(z0, w):
            ys, st = odeint(field, z0, ts, (w,), **kw)
            return jnp.sum(ys[-1] ** 2), (ys[-1], st.n_trials, st.status)

        z0s, self.w = self._problem()
        self.z0s = [z0s[i] for i in range(self.n_batches)]
        if self.mesh is not None:
            rows = NamedSharding(self.mesh, PartitionSpec("data"))
            self.z0s = [jax.device_put(z, rows) for z in self.z0s]
        self.fn = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)).lower(
                self.z0s[0], self.w).compile()
        self.last = [jax.block_until_ready(self.fn(z0, self.w))
                     for z0 in self.z0s]

    def window(self, seconds):
        calls = [0] * self.n_batches
        inflight = collections.deque()
        t0 = time.perf_counter()
        i = 0
        while True:
            b = i % self.n_batches
            with span("dispatch"):
                inflight.append((b, self.fn(self.z0s[b], self.w)))
            i += 1
            if len(inflight) > IN_FLIGHT:
                self._retire(inflight.popleft(), calls)
            if time.perf_counter() - t0 >= seconds:
                break
        while inflight:
            self._retire(inflight.popleft(), calls)
        elapsed = time.perf_counter() - t0

        trials = [np.asarray(o[0][1][1]) for o in self.last]
        status = [np.asarray(o[0][1][2]) for o in self.last]
        n_calls = sum(calls)
        attempted = n_calls * self.rows
        failed = sum(c * int(np.sum(s != 0)) for c, s in zip(calls, status))
        row_trials = sum(c * int(t.sum()) for c, t in zip(calls, trials))
        return dict(
            window_s=elapsed, attempted=attempted, failed=failed,
            metrics={"solve_traj_per_s": attempted / elapsed},
            counters=dict(
                calls=n_calls, row_trials=row_trials,
                trials=[t.tolist() for t in trials], width=self.cfg["dim"],
                itemsize=4, tableau=self.cfg["solver"]))

    def _retire(self, item, calls):
        b, out = item
        with span("block"):
            jax.block_until_ready(out)
        self.last[b] = out
        calls[b] += 1

    def programs(self):
        return [self.fn]

    def free(self):
        # keep the answers on the host, drop every device buffer
        self.answers = jax.device_get(self.last)
        del self.last, self.fn, self.z0s, self.w
        jax.clear_caches()

    # -------------------------------------------------------- reference
    def _reference(self, precision):
        cfg, tab = self.cfg, _tableau(self.cfg["solver"])
        z0s, w = jax.device_get(self._problem())
        block = int(self.tr.get("ref_block_rows", self.rows))
        devs = self.devices
        w_on = [jax.device_put(w, d) for d in devs]

        @jax.jit
        def run_block(z0, w):
            f = self.model.batch_field(precision, w)
            sol = ref_ode.solve(f, z0, cfg["t0"], cfg["t1"], tab,
                                cfg["rtol"], cfg["atol"], cfg["max_steps"])

            def loss(z0, w):
                z1 = ref_ode.replay(self.model.batch_field(precision, w),
                                    z0, cfg["t0"], sol.hs, tab)
                return jnp.sum(z1 ** 2)

            gz, gw = jax.grad(loss, argnums=(0, 1))(z0, w)
            return sol.z1, gz, gw, sol.ok

        # block j of every batch runs on chip j mod chips: the chips
        # work through their blocks side by side
        outs = []
        for b in range(self.n_batches):
            parts = []
            for j, i in enumerate(range(0, self.rows, block)):
                d = j % len(devs)
                parts.append(run_block(
                    jax.device_put(z0s[b, i:i + block], devs[d]), w_on[d]))
            z1 = np.concatenate([np.asarray(p[0]) for p in parts])
            gz = np.concatenate([np.asarray(p[1]) for p in parts])
            gw = sum(np.asarray(p[2], np.float64) for p in parts)
            ok = np.concatenate([np.asarray(p[3]) for p in parts])
            outs.append((z1, gz, gw, ok))
        return outs

    def checks(self, control=False):
        cfg = self.cfg
        prec = cfg["matmul_precision"]
        ref = self._reference(prec)
        if control:
            got = [(z1, gz, gw) for z1, gz, gw, _ in
                   self._reference(CONTROL_PRECISION[prec])]
        else:
            got = [(a[0][1][0], a[1][0], a[1][1]) for a in self.answers]
        state, grad_z0, grad_w = [], [], 0.0
        ref_bad = 0
        for (z1, gz, gw), (rz1, rgz, rgw, ok) in zip(got, ref):
            z1, gz, gw = (np.asarray(x, np.float64) for x in (z1, gz, gw))
            scale = cfg["atol"] + cfg["rtol"] * np.abs(rz1)
            state.append(np.max(np.abs(z1 - rz1) / scale, axis=1))
            gscale = np.maximum(np.max(np.abs(rgz), axis=1, keepdims=True),
                                1e-30)
            grad_z0.append(np.max(np.abs(gz - rgz) / gscale, axis=1))
            grad_w = max(grad_w, float(np.max(np.abs(gw - rgw))
                                       / np.max(np.abs(rgw))))
            ref_bad += int(np.sum(~ok))
        state = np.concatenate(state)
        grad_z0 = np.concatenate(grad_z0)
        self.readings = dict(
            state_gap_max=float(state.max()),
            state_gap_p50=float(np.median(state)),
            state_gap_p99=float(np.quantile(state, 0.99)),
            grad_z0_gap_max=float(grad_z0.max()),
            grad_z0_gap_p50=float(np.median(grad_z0)),
            grad_w_gap=grad_w, reference_unfinished=ref_bad)
        return [Check(k, float(self.readings[k]), float(v))
                for k, v in self.tr["limits"].items()]
