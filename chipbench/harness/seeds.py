"""PRNG keys from the run's ``--seed``, any whole number below 2**64."""

from __future__ import annotations


def key(seed: int, stream: int = 0):
    import jax

    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    k = jax.random.PRNGKey(seed % 2 ** 32)
    k = jax.random.fold_in(k, seed // 2 ** 32)
    return jax.random.fold_in(k, stream)
