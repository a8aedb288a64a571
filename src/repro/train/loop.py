"""Fault-tolerant training loop.

Production posture:

* one fully-jitted ``train_step`` with **microbatch gradient
  accumulation** (``lax.scan`` over microbatches inside the step: the
  data-parallel gradient reduce-scatter of microbatch *i* is exposed to
  XLA's latency-hiding scheduler against the compute of *i+1*);
* gradient clipping + optional int8/top-k **gradient compression**
  (error feedback carried in the loop state) ahead of the cross-pod
  all-reduce;
* **checkpoint/restart**: atomic CheckpointManager saves every
  ``ckpt_every`` steps; on construction the loop auto-resumes from the
  latest valid checkpoint; the step-indexed data pipeline makes resume
  exact without data-state snapshots;
* **straggler detection**: per-step wall-time EMA; steps slower than
  ``straggler_factor``× the EMA trip a callback (on a real cluster this
  feeds the controller that evicts/restarts the slow host — here it is
  surfaced in metrics and the hook is testable);
* **donated** state buffers (in-place update under jit);
* **phase spans**: each step's host phases — ``train/batch`` (the batch
  function), ``train/dispatch`` (the jitted step call), ``train/wait``
  (blocking on the loss and reading the skip flag back) and
  ``train/ckpt_save`` — are ``jax.profiler`` host annotations, on the
  device trace's clock when a profile is taken, and their seconds on
  the loop's clock stay in ``last_phases`` for the last step run.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import Model
from repro.optim.adamw import Optimizer, apply_updates
from repro.optim.grad_utils import (CompressionState, clip_by_global_norm,
                                    init_compression_state,
                                    int8_compress_decompress, topk_sparsify)
from .state import TrainState

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainLoopConfig:
    microbatches: int = 1
    clip_norm: float = 1.0
    compression: str = "none"      # none | int8 | topk
    topk_frac: float = 0.01
    ckpt_every: int = 100
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    straggler_factor: float = 3.0
    log_every: int = 10
    # skip-step guard: when the loss or raw gradient norm is non-finite
    # (e.g. a poisoned NODE solve, fp overflow), hold params/opt state
    # and count the skip in metrics instead of applying a NaN update
    skip_nonfinite: bool = True


def _split_microbatches(batch: Dict[str, jnp.ndarray], m: int):
    def split(x):
        b = x.shape[0]
        if b % m != 0:
            raise ValueError(
                f"batch size {b} not divisible by {m} microbatches")
        return x.reshape((m, b // m) + x.shape[1:])
    return jax.tree.map(split, batch)


def build_train_step(model: Model, opt: Optimizer,
                     cfg: TrainLoopConfig) -> Callable:
    """Returns train_step(state, batch, comp_state) ->
    (state, comp_state, metrics) — pure, jittable, donate-able."""

    def grads_of(params, mb):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, mb)
        return loss, metrics, grads

    def step(state: TrainState, batch, comp_state: CompressionState):
        comp_in = comp_state
        if cfg.microbatches > 1:
            mbs = _split_microbatches(batch, cfg.microbatches)

            def acc_fn(carry, mb):
                gacc, lacc = carry
                loss, _, grads = grads_of(state.params, mb)
                gacc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gacc, grads)
                return (gacc, lacc + loss), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (gsum, lsum), _ = jax.lax.scan(acc_fn, (g0, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / cfg.microbatches, gsum)
            loss = lsum / cfg.microbatches
            metrics = {"ce_loss": loss}
        else:
            loss, metrics, grads = grads_of(state.params, batch)

        grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
        if cfg.compression == "int8":
            grads, comp_state = int8_compress_decompress(grads, comp_state)
        elif cfg.compression == "topk":
            grads, comp_state = topk_sparsify(grads, cfg.topk_frac,
                                              comp_state)

        updates, opt_state = opt.update(grads, state.opt_state,
                                        state.params)
        params = apply_updates(state.params, updates)
        metrics = dict(metrics)
        if cfg.skip_nonfinite:
            # skip-step guard: a non-finite loss or raw grad norm means
            # this update is garbage — hold params/opt/compression state
            # (the step counter still advances so training can't spin on
            # one poisoned batch) and surface the skip in metrics.
            # clip_by_global_norm already zeroed the grads on a bad
            # norm, so `updates` is finite either way; the selects below
            # are what make the skip exact.
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            sel = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new, old)
            params = sel(params, state.params)
            opt_state = sel(opt_state, state.opt_state)
            comp_state = sel(comp_state, comp_in)
            metrics["skipped"] = (~ok).astype(jnp.int32)
        new_state = TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state)
        metrics["loss"] = loss
        metrics["grad_norm"] = gnorm
        return new_state, comp_state, metrics

    return step


class TrainLoop:
    """Drives ``train_step`` with checkpoint/restart + straggler watch."""

    def __init__(self, model: Model, opt: Optimizer, cfg: TrainLoopConfig,
                 state: TrainState,
                 straggler_cb: Optional[Callable[[int, float], None]] = None,
                 jit: bool = True,
                 clock: Callable[[], float] = time.monotonic):
        self.model, self.opt, self.cfg = model, opt, cfg
        self.state = state
        self._clock = clock
        self.comp_state = init_compression_state(state.params) \
            if cfg.compression != "none" else CompressionState(error=())
        self._step_fn = build_train_step(model, opt, cfg)
        if jit:
            self._step_fn = jax.jit(self._step_fn, donate_argnums=(0,))
        self.straggler_cb = straggler_cb
        self.skipped_steps = 0      # total non-finite updates skipped
        # host seconds of each phase of the last step run, on ``clock``
        self.last_phases: Dict[str, float] = {}
        self._ema_dt: Optional[float] = None
        self.manager = None
        if cfg.ckpt_dir:
            from repro.ckpt import CheckpointManager
            self.manager = CheckpointManager(cfg.ckpt_dir, cfg.keep_ckpts)
            restored = self.manager.restore(self.state)
            if restored is not None:
                _, self.state = restored

    @property
    def step(self) -> int:
        return int(self.state.step)

    @contextlib.contextmanager
    def _phase(self, name: str):
        """Host span ``train/<name>`` in a profile, and its seconds on
        the loop's clock in ``last_phases``."""
        t0 = self._clock()
        with jax.profiler.TraceAnnotation("train/" + name):
            yield
        self.last_phases[name] = self._clock() - t0

    def run(self, batch_fn: Callable[[int], Dict[str, jnp.ndarray]],
            n_steps: int,
            log_cb: Optional[Callable[[int, Dict], None]] = None):
        """Run until global step reaches ``n_steps`` (resume-aware)."""
        metrics = {}
        while self.step < n_steps:
            s = self.step
            self.last_phases = {}
            with self._phase("batch"):
                batch = batch_fn(s)
            with self._phase("dispatch"):
                self.state, self.comp_state, metrics = self._step_fn(
                    self.state, batch, self.comp_state)
            with self._phase("wait"):
                jax.block_until_ready(metrics["loss"])
                if "skipped" in metrics:
                    self.skipped_steps += int(metrics["skipped"])
            dt = self.last_phases["dispatch"] + self.last_phases["wait"]

            # straggler watch: EMA of step time, flag outliers
            if self._ema_dt is None:
                self._ema_dt = dt
            else:
                if dt > self.cfg.straggler_factor * self._ema_dt \
                        and self.straggler_cb is not None:
                    self.straggler_cb(s, dt / self._ema_dt)
                self._ema_dt = 0.9 * self._ema_dt + 0.1 * dt

            if self.manager and (s + 1) % self.cfg.ckpt_every == 0:
                with self._phase("ckpt_save"):
                    self.manager.save(s + 1, self.state)

            if log_cb and (s + 1) % self.cfg.log_every == 0:
                log_cb(s + 1, jax.tree.map(_to_host, metrics))
        return metrics


def _to_host(v) -> Any:
    """A metric for ``log_cb``: a float for a scalar, nested lists for
    an array (the NODE blocks' per-layer ``node_stats``)."""
    return float(v) if np.ndim(v) == 0 else np.asarray(v).tolist()
