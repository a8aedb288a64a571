"""Closed-loop training steps through the program's ``TrainLoop``.

Set-up makes the parameters on the device from the seed (one jitted
call), builds the loop with the program's AdamW and cosine warm-up, and
drives it through its first ``check_steps`` steps on
``TokenPipeline(seed)`` batches: those steps compile and warm up the
step, and are the ones the correctness check compares.  The window then
runs the same loop object step after step until the time is up,
putting back a state every ``replay_steps`` steps: every stretch of the
window replays the same steps on the same batches, so each step does
the same work however many fit in the window (the blocks' step counts,
and with them a step's time, grow as training goes on).  The traffic's
``replay_from`` says which state: ``"copy"`` (the default), the state
of the end of set-up, from a copy kept on the device; ``"seed"``, the
state set-up started from, made again from the seed at the window's
start and at every replay, so that no second copy of the state takes
device memory.

Correct: each of the first steps' losses, the first step's clipped
gradient norm per leaf (read from AdamW's first moment after step 1)
and the parameters' change per leaf after the first steps, against the
plain float32 reference (``configs/node18_cifar.py``) trained from the
same seed on the same tokens.  The control is that reference with every
matrix product's operands rounded to float8.
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import seeds
from harness.runner import BENCH_DIR, Check, load_module, span

GRAD_FLOOR = 1e-3   # leaves under this share of the median gradient norm
REPLAY_FROM = ("copy", "seed")


def path_of(kp) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def _gap(a: dict, b: dict, keys) -> float:
    """Worst leaf's |a - b| over max(b_leaf, median of b)."""
    med = float(np.median([b[k] for k in keys]))
    return max(abs(a[k] - b[k]) / max(b[k], med) for k in keys)


class Driver:
    def __init__(self, config, traffic, seed, devices):
        self.cfg, self.tr, self.seed = config, traffic, seed
        self.devices = devices
        self.ref = load_module(os.path.join(
            BENCH_DIR, "configs", config["name"] + ".py"))
        self.tokens_per_step = traffic["seq"] * traffic["batch"]
        self.replay_from = traffic.get("replay_from", "copy")
        if self.replay_from not in REPLAY_FROM:
            raise ValueError(f"replay_from {self.replay_from!r} is not one "
                             f"of {REPLAY_FROM}")

    def _params_fn(self, abstract):
        """Jitted: the seed's key -> its parameters in the program's tree,
        made on the device in one call."""
        names = jax.tree_util.tree_map_with_path(
            lambda kp, _: path_of(kp), abstract)

        def make(key):
            flat = self.ref.init_params(key, self.cfg)
            return jax.tree.map(lambda p: flat[p], names)

        return jax.jit(make)

    def _initial_state(self):
        """The seed's state at step 0: its parameters and fresh AdamW."""
        from repro.train.state import TrainState

        params = self._make(seeds.key(self.seed))
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=self.opt.init(params))

    def setup(self):
        from repro.core.node_block import NodeConfig
        from repro.data import TokenPipeline
        from repro.models import RunConfig, build_model
        from repro.models.config import ModelConfig
        from repro.optim import adamw, cosine_warmup
        from repro.train import TrainLoop, TrainLoopConfig

        cfg, tr, o = self.cfg, self.tr, self.cfg["optimizer"]
        model = build_model(ModelConfig(**cfg["model"]), RunConfig(
            compute_dtype=jnp.dtype(cfg["compute_dtype"]),
            param_dtype=jnp.dtype(cfg["param_dtype"]),
            node=NodeConfig(**cfg["node"]), remat="none"))
        self.opt = adamw(cosine_warmup(o["peak_lr"], o["warmup_steps"],
                                       o["total_steps"], o["final_frac"]),
                         b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"])
        self.pipe = TokenPipeline(vocab=cfg["model"]["vocab"],
                                  seq_len=tr["seq"], global_batch=tr["batch"],
                                  seed=self.seed, zipf_a=tr["zipf_a"])
        self._make = self._params_fn(model.abstract())
        self.loop = TrainLoop(model, self.opt, TrainLoopConfig(
            microbatches=1, clip_norm=o["clip_norm"], ckpt_dir=None,
            log_every=1), self._initial_state())

        losses = []
        log = lambda s, m: losses.append(m["loss"])  # noqa: E731
        n_check = int(tr["check_steps"])
        self.loop.run(self.pipe.batch, 1, log_cb=log)
        mu = self.loop.state.opt_state.mu
        norms = jax.tree_util.tree_map_with_path(
            lambda kp, m: (path_of(kp), jnp.sqrt(jnp.sum(m * m))
                           / (1.0 - o["b1"])), mu)
        self.grad_norms = {p: float(v) for p, v in jax.tree.leaves(
            norms, is_leaf=lambda x: isinstance(x, tuple))}
        self.loop.run(self.pipe.batch, n_check, log_cb=log)
        self.losses = losses
        self.state0 = None
        if self.replay_from == "copy":
            self._copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
            self.state0 = self._copy(self.loop.state)
            jax.block_until_ready(self.state0)
        params0 = self._make(seeds.key(self.seed))
        change = jax.tree_util.tree_map_with_path(
            lambda kp, a, b: (path_of(kp), jnp.sqrt(jnp.sum((a - b) ** 2))),
            self.loop.state.params, params0)
        self.changes = {p: float(v) for p, v in jax.tree.leaves(
            change, is_leaf=lambda x: isinstance(x, tuple))}
        del params0

    def window(self, seconds):
        loop = self.loop

        def batch(step):
            with span("batch"):
                return self.pipe.batch(step)

        first = int(self.state0.step) if self.replay_from == "copy" else 0
        end = first + int(self.tr["replay_steps"])
        # from the seed, the window starts by putting back step 0
        pending = self.replay_from == "seed"
        skipped0, step_s = loop.skipped_steps, []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            t = time.perf_counter()
            if pending or loop.step >= end:
                with span("restore"):
                    loop.state = self.replay_start()
                pending = False
            with span("step"):
                loop.run(batch, loop.step + 1)
            step_s.append(time.perf_counter() - t)
        elapsed = time.perf_counter() - t0
        steps = len(step_s)
        print("step seconds: " + " ".join(f"{x:.3f}" for x in step_s),
              file=sys.stderr, flush=True)
        tokens = steps * self.tokens_per_step
        return dict(
            window_s=elapsed, attempted=steps,
            failed=loop.skipped_steps - skipped0,
            metrics={"train_tokens_per_s": tokens / elapsed},
            counters=dict(steps=steps, tokens=tokens,
                          tokens_per_step=self.tokens_per_step))

    def replay_start(self):
        """A fresh device copy of the state each stretch of the window
        starts from."""
        if self.replay_from == "copy":
            return self._copy(self.state0)
        return self._initial_state()

    def programs(self):
        loop = self.loop
        return [loop._step_fn.lower(
            loop.state, self.pipe.batch(loop.step),
            loop.comp_state).compile()]

    def free(self):
        del self.loop, self.state0
        jax.clear_caches()

    def checks(self, control=False):
        cfg, tr = self.cfg, self.tr
        n = int(tr["check_steps"])
        batches = [self.ref.tokens(self.seed, s, tr["batch"], tr["seq"],
                                   cfg["model"]["vocab"], tr["zipf_a"])
                   for s in range(n)]
        params0 = self.ref.init_params(seeds.key(self.seed), cfg)
        ref_p0 = {k: np.asarray(v) for k, v in params0.items()}
        losses, gnorms, params, unfinished = self.ref.train(
            params0, batches, cfg)
        changes = {k: float(np.linalg.norm(np.asarray(params[k]) - ref_p0[k]))
                   for k in params}
        if control:
            c_losses, c_gnorms, c_params, _ = self.ref.train(
                self.ref.init_params(seeds.key(self.seed), cfg), batches,
                cfg, quant="fp8")
            got_losses, got_g = c_losses, c_gnorms
            got_change = {k: float(np.linalg.norm(
                np.asarray(c_params[k]) - ref_p0[k])) for k in c_params}
        else:
            got_losses, got_g = self.losses, self.grad_norms
            got_change = self.changes
        med_g = float(np.median(list(gnorms.values())))
        moving = [k for k in gnorms if gnorms[k] >= GRAD_FLOOR * med_g]
        self.readings = dict(
            loss_gap=max(abs(a - b) / abs(b)
                         for a, b in zip(got_losses, losses)),
            grad_norm_gap=_gap(got_g, gnorms, list(gnorms)),
            change_gap=_gap(got_change, changes, moving),
            reference_unfinished=unfinished,
            losses=list(got_losses), ref_losses=losses,
            excluded_leaves=sorted(set(gnorms) - set(moving)))
        self.readings["worst_grad_leaf"] = max(
            gnorms, key=lambda k: abs(got_g[k] - gnorms[k])
            / max(gnorms[k], med_g))
        return [Check(k, float(self.readings[k]), float(v))
                for k, v in tr["limits"].items()]
