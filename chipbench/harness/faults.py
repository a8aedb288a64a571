"""Faults planted underneath a run's timed path, to show that the
correctness check catches them (used by the tests under ``tests/`` and
by ``calibrate.py --fault``).  ``plant(name)`` returns an undo
function."""

from __future__ import annotations

from typing import Callable


def _patch(obj, attr, value) -> Callable[[], None]:
    old = getattr(obj, attr)
    setattr(obj, attr, value)
    return lambda: setattr(obj, attr, old)


def _first_shard_only(axis: str):
    """Identity whose cotangent is kept on the mesh's first shard alone
    and zeroed on the others: under ``shard_map`` the sum over the
    shards then returns the first chip's partial sum, as if the chips
    had not exchanged theirs."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def ident(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        first = jax.lax.axis_index(axis) == 0
        return (jax.tree.map(lambda c: jnp.where(first, c, 0), g),)

    ident.defvjp(fwd, bwd)
    return ident


def solve_fault(kind: str):
    """``odeint`` that leaves the state unchanged, solves half of the
    batch (its answers stand in for the other half), alters one element
    of one answer, or (on a mesh) leaves out the exchange that sums the
    coupling's gradient over the chips."""
    import jax.numpy as jnp

    import repro.core

    real = repro.core.odeint

    def broken(f, z0, ts, args, **kw):
        if kind == "no_exchange":
            keep = _first_shard_only(kw["mesh"].axis_names[0])
            return real(lambda t, z, *a: f(t, z, *keep(a)), z0, ts, args,
                        **kw)
        if kind == "half_batch":
            h = z0.shape[0] // 2
            ys, st = real(f, z0[:h], ts, args, **kw)
            return (jnp.concatenate([ys, ys], axis=1),
                    type(st)(*(jnp.concatenate([x, x]) for x in st)))
        ys, st = real(f, z0, ts, args, **kw)
        if kind == "unchanged":
            return jnp.stack([ys[0]] * ys.shape[0]), st
        if kind == "altered":
            return ys.at[-1, 0, 0].add(1e-3), st
        raise ValueError(kind)

    return _patch(repro.core, "odeint", broken)


def train_fault(kind: str):
    """Train step that returns its state unchanged, or trains on half of
    the batch (the mean taken over that half)."""
    import repro.train.loop as loop_mod

    real = loop_mod.build_train_step

    def build(model, opt, cfg):
        step = real(model, opt, cfg)

        def broken(state, batch, comp_state):
            if kind == "half_batch":
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return step(state, half, comp_state)
            new, comp, metrics = step(state, batch, comp_state)
            if kind == "unchanged":
                return state._replace(step=new.step), comp_state, metrics
            raise ValueError(kind)

        return broken

    return _patch(loop_mod, "build_train_step", build)


FAULTS = {"solve": solve_fault, "train": train_fault}


def plant(driver: str, kind: str) -> Callable[[], None]:
    return FAULTS[driver](kind)
