"""jit'd dispatch layer over the Pallas kernels.

On a TPU backend the compiled kernels run natively, always; elsewhere
``interpret=True`` executes the kernel body as plain XLA ops on the CPU
— the mode the test suite validates against the ``ref.py`` oracles.
Off the chip, ``set_interpret`` (or the REPRO_PALLAS_INTERPRET env var)
overrides the choice; on the chip both are ignored, so a kernel run
there can never be the interpreter in disguise.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_pallas
from .rg_lru import rg_lru_pallas
from .rk_stage import (
    _BLOCK,
    combine_err_batched_jnp,
    combine_err_jnp,
    combine_jnp,
    increment_batched_jnp,
    increment_jnp,
    rk_stage_combine_err_batched_pallas,
    rk_stage_combine_err_batched_rowtol_pallas,
    rk_stage_combine_err_pallas,
    rk_stage_combine_pallas,
    rk_stage_increment_batched_pallas,
    rk_stage_increment_pallas,
)
from .rmsnorm import rmsnorm_pallas
from .ssd_scan import ssd_scan_pallas

_FORCE_INTERPRET: Optional[bool] = None

_FALSY = ("0", "false", "no", "off", "")


def set_interpret(value: Optional[bool]) -> None:
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = value


def _interpret() -> bool:
    # on the chip the kernels always compile: an override there would
    # silently time the interpreter instead of the kernels
    if jax.default_backend() == "tpu":
        return False
    if _FORCE_INTERPRET is not None:
        return _FORCE_INTERPRET
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None and env.strip().lower() not in _FALSY:
        return True
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------- rk kernels
# The RK kernels sit on every gradient method's differentiation path (the
# naive method differentiates straight through the solver; ACA replays
# local steps under jax.vjp), and pallas_call has no transpose rule —
# each op is therefore a custom_vjp whose forward runs the kernel and
# whose backward is jax.vjp of the bit-matching pure-jnp twin from
# ``rk_stage.py``.  Weights/tolerances are static (baked into the kernel).

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _rk_combine(z, k, h, b, e, block, interpret):
    return rk_stage_combine_pallas(z, k, h, b, e, block=block,
                                   interpret=interpret)


def _rk_combine_fwd(z, k, h, b, e, block, interpret):
    return _rk_combine(z, k, h, b, e, block, interpret), (z, k, h)


def _rk_combine_bwd(b, e, block, interpret, res, g):
    z, k, h = res
    _, vjp = jax.vjp(lambda z_, k_, h_: combine_jnp(z_, k_, h_, b, e),
                     z, k, h)
    return vjp(g)


_rk_combine.defvjp(_rk_combine_fwd, _rk_combine_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rk_increment(z, k, h, a, block, interpret):
    return rk_stage_increment_pallas(z, k, h, a, block=block,
                                     interpret=interpret)


def _rk_increment_fwd(z, k, h, a, block, interpret):
    return _rk_increment(z, k, h, a, block, interpret), (z, k, h)


def _rk_increment_bwd(a, block, interpret, res, g):
    z, k, h = res
    _, vjp = jax.vjp(lambda z_, k_, h_: increment_jnp(z_, k_, h_, a),
                     z, k, h)
    return vjp(g)


_rk_increment.defvjp(_rk_increment_fwd, _rk_increment_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _rk_combine_err(z, k, h, b, e, rtol, atol, with_err, block, interpret):
    zn, err, partials = rk_stage_combine_err_pallas(
        z, k, h, b, e, rtol, atol, with_err=with_err, block=block,
        interpret=interpret)
    sq = partials.sum()
    return (zn, err, sq) if with_err else (zn, sq)


def _rk_combine_err_fwd(z, k, h, b, e, rtol, atol, with_err, block,
                        interpret):
    return (_rk_combine_err(z, k, h, b, e, rtol, atol, with_err, block,
                            interpret), (z, k, h))


def _rk_combine_err_bwd(b, e, rtol, atol, with_err, block, interpret,
                        res, g):
    z, k, h = res
    _, vjp = jax.vjp(
        lambda z_, k_, h_: combine_err_jnp(z_, k_, h_, b, e, rtol, atol,
                                           with_err), z, k, h)
    return vjp(g)


_rk_combine_err.defvjp(_rk_combine_err_fwd, _rk_combine_err_bwd)


def rk_stage_combine(z, k, h, b, e=None, *, block=None):
    """Fused (z + h·Σ b_i k_i, h·Σ e_i k_i); differentiable."""
    e_t = tuple(float(x) for x in e) if e is not None else None
    return _rk_combine(z, k, h, tuple(float(x) for x in b), e_t,
                       _BLOCK if block is None else int(block),
                       _interpret())


def rk_stage_increment(z, k, h, a, *, block=None):
    """Fused stage argument z + h·Σ_j a_j k_j; differentiable."""
    return _rk_increment(z, k, h, tuple(float(x) for x in a),
                         _BLOCK if block is None else int(block),
                         _interpret())


def rk_stage_combine_err(z, k, h, b, e, rtol, atol, *, with_err=True,
                         block=None):
    """Fused combine + scalar Σ (err/(atol+rtol·max|z|))²; differentiable.

    Returns (z_next, err, sq_sum); sqrt(sq_sum / N) is ``error_ratio``.
    ``with_err=False`` skips the (N,) err store — the solver loop needs
    only z_next and the norm — and returns None in the err slot.
    """
    out = _rk_combine_err(z, k, h, tuple(float(x) for x in b),
                          tuple(float(x) for x in e), float(rtol),
                          float(atol), bool(with_err),
                          _BLOCK if block is None else int(block),
                          _interpret())
    if with_err:
        return out
    zn, sq = out
    return zn, None, sq


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rk_increment_batched(z, k, h, a, block, interpret):
    return rk_stage_increment_batched_pallas(z, k, h, a, block=block,
                                             interpret=interpret)


def _rk_increment_batched_fwd(z, k, h, a, block, interpret):
    return _rk_increment_batched(z, k, h, a, block, interpret), (z, k, h)


def _rk_increment_batched_bwd(a, block, interpret, res, g):
    z, k, h = res
    _, vjp = jax.vjp(
        lambda z_, k_, h_: increment_batched_jnp(z_, k_, h_, a), z, k, h)
    return vjp(g)


_rk_increment_batched.defvjp(_rk_increment_batched_fwd,
                             _rk_increment_batched_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _rk_combine_err_batched(z, k, h, b, e, rtol, atol, block, interpret):
    zn, partials = rk_stage_combine_err_batched_pallas(
        z, k, h, b, e, rtol, atol, block=block, interpret=interpret)
    return zn, partials.sum(axis=-1)


def _rk_combine_err_batched_fwd(z, k, h, b, e, rtol, atol, block,
                                interpret):
    return (_rk_combine_err_batched(z, k, h, b, e, rtol, atol, block,
                                    interpret), (z, k, h))


def _rk_combine_err_batched_bwd(b, e, rtol, atol, block, interpret, res,
                                g):
    z, k, h = res
    _, vjp = jax.vjp(
        lambda z_, k_, h_: combine_err_batched_jnp(z_, k_, h_, b, e, rtol,
                                                   atol), z, k, h)
    return vjp(g)


_rk_combine_err_batched.defvjp(_rk_combine_err_batched_fwd,
                               _rk_combine_err_batched_bwd)


# Per-row-tolerance variant: rtol/atol are *traced* (B,) arrays instead
# of static floats, so they ride the kernel as loaded refs.  They carry
# no cotangent (zeros returned) — the same convention as the static
# path, where tolerances are nondiff: the error norm's dependence on
# the tolerance is control-flow plumbing, not a differentiable quantity.
@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rk_combine_err_batched_rowtol(z, k, h, rtol, atol, b, e, block,
                                   interpret):
    zn, partials = rk_stage_combine_err_batched_rowtol_pallas(
        z, k, h, b, e, rtol, atol, block=block, interpret=interpret)
    return zn, partials.sum(axis=-1)


def _rk_combine_err_batched_rowtol_fwd(z, k, h, rtol, atol, b, e, block,
                                       interpret):
    return (_rk_combine_err_batched_rowtol(z, k, h, rtol, atol, b, e,
                                           block, interpret),
            (z, k, h, rtol, atol))


def _rk_combine_err_batched_rowtol_bwd(b, e, block, interpret, res, g):
    z, k, h, rtol, atol = res
    _, vjp = jax.vjp(
        lambda z_, k_, h_: combine_err_batched_jnp(z_, k_, h_, b, e, rtol,
                                                   atol), z, k, h)
    dz, dk, dh = vjp(g)
    return dz, dk, dh, jnp.zeros_like(rtol), jnp.zeros_like(atol)


_rk_combine_err_batched_rowtol.defvjp(_rk_combine_err_batched_rowtol_fwd,
                                      _rk_combine_err_batched_rowtol_bwd)


def rk_stage_increment_batched(z, k, h, a, *, block=None):
    """Per-row fused stage argument z + h_b·Σ_j a_j k_j over a (B, N)
    batch; differentiable.  Rows with h_b = 0 pass through bit-exactly
    (frozen-element masking of the batched solver)."""
    return _rk_increment_batched(z, k, h, tuple(float(x) for x in a),
                                 _BLOCK if block is None else int(block),
                                 _interpret())


def rk_stage_combine_err_batched(z, k, h, b, e, rtol, atol, *, block=None):
    """Per-row fused combine + per-row Σ (err/(atol+rtol·max|z|))² over a
    (B, N) batch; differentiable.

    Returns (z_next (B, N), sq_sum (B,)); sqrt(sq_sum / N) is each batch
    element's own ``error_ratio`` — the per-sample accept/reject signal.
    The (B, N) err buffer is never materialized.

    ``rtol``/``atol`` are static scalars (baked into the kernel, the
    classic path) or (B,) arrays — then each row is error-controlled
    against its own tolerance (per-request QoS), loaded per grid row
    like ``h``.  Tolerances never carry gradient on either path.
    """
    bw = tuple(float(x) for x in b)
    ew = tuple(float(x) for x in e)
    blk = _BLOCK if block is None else int(block)
    if jnp.ndim(rtol) > 0 or jnp.ndim(atol) > 0:
        bsz = z.shape[0]
        rt = jnp.broadcast_to(jnp.asarray(rtol, jnp.float32), (bsz,))
        at = jnp.broadcast_to(jnp.asarray(atol, jnp.float32), (bsz,))
        return _rk_combine_err_batched_rowtol(z, k, h, rt, at, bw, ew,
                                              blk, _interpret())
    return _rk_combine_err_batched(
        z, k, h, bw, ew, float(rtol), float(atol), blk, _interpret())


def rmsnorm(x, w, eps: float = 1e-6, **kw):
    return rmsnorm_pallas(x, w, eps=eps, interpret=_interpret(), **kw)


def flash_attention(q, k, v, *, window: int = 0, scale=None, **kw):
    return flash_attention_pallas(q, k, v, window=window, scale=scale,
                                  interpret=_interpret(), **kw)


def ssd_scan(x, dt, a, b_mat, c_mat, chunk: int, **kw):
    return ssd_scan_pallas(x, dt, a, b_mat, c_mat, chunk,
                           interpret=_interpret(), **kw)


def rg_lru(log_a, b, **kw):
    return rg_lru_pallas(log_a, b, interpret=_interpret(), **kw)
