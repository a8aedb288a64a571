"""Training launcher: ``--arch <id>`` selects any registry config.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_72b \
        --smoke --steps 50 [--node] [--grad-method aca]

``--smoke`` uses the reduced same-family config (CPU-feasible); without
it the full config is built — on real hardware the mesh comes from
``make_elastic_mesh`` over the live device list, checkpoints are
written/resumed via the atomic CheckpointManager, and the step-indexed
pipeline makes restarts exact.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.core.node_block import NodeConfig
from repro.data import TokenPipeline
from repro.launch.mesh import make_elastic_mesh
from repro.models import RunConfig, build_model
from repro.models.frontends import frontend_batch_synthetic
from repro.optim import adamw, cosine_warmup
from repro.train import TrainLoop, TrainLoopConfig, make_train_state


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--node", action="store_true")
    ap.add_argument("--grad-method", default="aca",
                    choices=["aca", "adjoint", "naive"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", action="store_true",
                    help="build an elastic mesh over live devices")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else \
        get_config(args.arch)
    mesh = make_elastic_mesh(model_parallel=1) if args.mesh else None
    node = NodeConfig(enabled=args.node, regime="fixed", solver="rk2",
                      grad_method=args.grad_method, steps_per_interval=2)
    rcfg = RunConfig(mesh=mesh,
                     compute_dtype=jnp.float32 if args.smoke
                     else jnp.bfloat16, node=node)
    model = build_model(cfg, rcfg)
    print(f"arch={cfg.name} params={model.n_params()/1e6:.1f}M "
          f"node={args.node}")

    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch)

    def batch_fn(step):
        if cfg.frontend != "none":
            return frontend_batch_synthetic(
                cfg, args.batch, args.seq, jax.random.PRNGKey(step),
                compute_dtype=rcfg.compute_dtype)
        return pipe.batch(step)

    opt = adamw(cosine_warmup(3e-4, 20, max(args.steps, 100)),
                weight_decay=0.1)
    lcfg = TrainLoopConfig(microbatches=args.microbatches,
                           compression=args.compression,
                           ckpt_dir=args.ckpt_dir, ckpt_every=100,
                           log_every=10)
    state = make_train_state(model, opt, jax.random.PRNGKey(0))
    loop = TrainLoop(model, opt, lcfg, state)
    loop.run(batch_fn, args.steps,
             log_cb=lambda s, m: print(
                 f"step {s:5d} loss {m['loss']:.4f} "
                 f"gnorm {m['grad_norm']:.2f}"))


if __name__ == "__main__":
    main()
