"""End-to-end driver: train a continuous-depth (NODE) language model
with ACA gradients — the paper's ResNet→NODE transformation applied to
a transformer stack, through the full production substrate (config
registry, data pipeline, AdamW + cosine schedule, gradient clipping,
atomic checkpointing with auto-resume, straggler watch).

Default: the ~100M-param node18_cifar config at a CPU-feasible
(seq 128, batch 8) shape for a few hundred steps.  ``--smoke`` shrinks
the model for a fast demonstration; ``--discrete`` trains the same
stack without NODE mode for comparison; ``--grad-method`` switches
aca/adjoint/naive.

    PYTHONPATH=src python examples/train_node_lm.py --steps 300
    PYTHONPATH=src python examples/train_node_lm.py --smoke --steps 50
    PYTHONPATH=src python examples/train_node_lm.py --smoke --adaptive

``--adaptive`` trains with the paper-matching ``NODE_TRAIN`` config
(adaptive HeunEuler, rtol=atol=1e-2, ACA, fused Pallas solver path)
instead of the CPU-friendly fixed grid.
"""

import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.configs.node18_cifar import NODE_TRAIN
from repro.core import NodeConfig
from repro.data import TokenPipeline
from repro.models import RunConfig, build_model
from repro.optim import adamw, cosine_warmup
from repro.train import TrainLoop, TrainLoopConfig, make_train_state


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--discrete", action="store_true")
    ap.add_argument("--grad-method", default="aca",
                    choices=["aca", "adjoint", "naive", "mali"])
    ap.add_argument("--adaptive", action="store_true",
                    help="paper-matching adaptive NODE_TRAIN config "
                         "(HeunEuler 1e-2, fused Pallas solver)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_node_lm")
    args = ap.parse_args()

    cfg = get_smoke_config("node18_cifar") if args.smoke \
        else get_config("node18_cifar")
    if args.adaptive:
        node = dataclasses.replace(
            NODE_TRAIN, enabled=not args.discrete,
            grad_method=args.grad_method,
            # segmented checkpointing is an ACA-only memory bound — drop
            # it when the CLI switches to adjoint/naive
            checkpoint_segments=(NODE_TRAIN.checkpoint_segments
                                 if args.grad_method == "aca" else None))
    else:
        node = NodeConfig(enabled=not args.discrete, regime="fixed",
                          solver="rk2", grad_method=args.grad_method,
                          steps_per_interval=2)
    rcfg = RunConfig(compute_dtype=jnp.float32 if args.smoke
                     else jnp.bfloat16, node=node, remat="none")
    model = build_model(cfg, rcfg)
    print(f"model: {cfg.name}  params={model.n_params()/1e6:.1f}M  "
          f"mode={'discrete' if args.discrete else 'NODE/' + args.grad_method}")

    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch, seed=0)
    opt = adamw(cosine_warmup(3e-4, 20, args.steps), weight_decay=0.1)
    lcfg = TrainLoopConfig(
        microbatches=1, clip_norm=1.0,
        ckpt_dir=args.ckpt_dir, ckpt_every=100, log_every=10,
    )
    state = make_train_state(model, opt, jax.random.PRNGKey(0))
    loop = TrainLoop(model, opt, lcfg, state,
                     straggler_cb=lambda s, r: print(
                         f"  [straggler] step {s} {r:.1f}x slower"))
    if loop.step:
        print(f"resumed from checkpoint at step {loop.step}")

    loop.run(lambda s: pipe.batch(s), args.steps,
             log_cb=lambda s, m: print(
                 f"step {s:5d}  loss {m['loss']:.4f}  "
                 f"gnorm {m['grad_norm']:.2f}"))
    print(f"done at step {loop.step}; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
