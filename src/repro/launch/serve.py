"""Serving launcher: batched prefill + decode for any registry arch.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2_2_7b \
        --smoke --prompt-len 32 --new-tokens 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.models import RunConfig, build_model
from repro.serve import ServeConfig, ServeEngine


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else \
        get_config(args.arch)
    rcfg = RunConfig(compute_dtype=jnp.float32 if args.smoke
                     else jnp.bfloat16,
                     param_dtype=jnp.float32 if args.smoke
                     else jnp.bfloat16,
                     max_seq=args.prompt_len + args.new_tokens + 8)
    model = build_model(cfg, rcfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params,
                         ServeConfig(max_new_tokens=args.new_tokens,
                                     temperature=args.temperature))

    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (args.batch, args.prompt_len), 0,
                              cfg.vocab, jnp.int32)
    t0 = time.monotonic()
    out = engine.generate(toks)
    dt = time.monotonic() - t0
    n_new = out["tokens"].shape[1] - args.prompt_len
    print(f"arch={cfg.name} generated {n_new} tokens x {args.batch} seqs "
          f"in {dt:.2f}s ({args.batch * n_new / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
