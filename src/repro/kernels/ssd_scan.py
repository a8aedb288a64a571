"""Mamba-2 SSD chunk-scan kernel with VMEM state carry.

One grid step processes one (batch, head, chunk) tile:

    Y_diag  = ((C_c B_cᵀ) ⊙ L) · (dt ⊙ X_c)        (MXU, intra-chunk)
    Y_inter = C_c · h_prev ⊙ decay_from_start        (MXU, inter-chunk)
    h_next  = h_prev · exp(Σ dA) + (B_c ⊙ decay)ᵀ X  (state update)

The (P, N) SSM state h lives in VMEM scratch and is carried across the
chunk grid dimension (innermost, sequential on TPU) — the HBM traffic
is exactly X/B/C/dt in + Y out; the O(S/Q) intermediate chunk states
never touch HBM, unlike the XLA fallback which materializes them for
the inter-chunk ``lax.scan``.  This is the paper's checkpoint idea
applied intra-layer: chunk boundaries are the trajectory checkpoints.

Grid: (B, H, nc) — nc innermost carries the recurrence.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _segsum_exp(da: jnp.ndarray, q: int) -> jnp.ndarray:
    """L[i, j] = exp(sum_{k=j+1..i} da_k) for j <= i else 0.  da (Q,)."""
    cs = jnp.cumsum(da)
    diff = cs[:, None] - cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return jnp.where(ii >= jj, jnp.exp(diff), 0.0)


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_scr, *, q):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)         # (Q,)
    a = a_ref[0, 0]                                  # scalar decay rate
    bm = b_ref[0, 0, 0].astype(jnp.float32)          # (Q, N)
    cm = c_ref[0, 0, 0].astype(jnp.float32)          # (Q, N)

    da = dt * a                                      # (Q,)
    da_cum = jnp.cumsum(da)
    da_tot = da_cum[-1]

    # intra-chunk
    l_mat = _segsum_exp(da, q)                       # (Q, Q)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(
        cb * l_mat, x * dt[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Q, P)

    # inter-chunk from carried state h (P, N)
    h = h_scr[...]
    y_inter = jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(da_cum)[:, None]

    y_ref[0, 0, 0] = (y_diag + y_inter).astype(y_ref.dtype)

    # state update: h' = h·exp(da_tot) + Σ_t decay_to_end_t · dt_t x_t B_tᵀ
    decay_to_end = jnp.exp(da_tot - da_cum)          # (Q,)
    xb = jax.lax.dot_general(
        x * (dt * decay_to_end)[:, None], bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (P, N)
    h_scr[...] = h * jnp.exp(da_tot) + xb


def ssd_scan_pallas(
    x: jnp.ndarray,      # (B, S, H, P)
    dt: jnp.ndarray,     # (B, S, H) fp32, post-softplus
    a: jnp.ndarray,      # (H,) fp32, negative decay rates
    b_mat: jnp.ndarray,  # (B, S, G, N) — G must divide H
    c_mat: jnp.ndarray,  # (B, S, G, N)
    chunk: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y (B, S, H, P).  (h_last stays on-chip; the model's prefill
    path uses the jnp reference when it needs the final state.)"""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    if s % chunk != 0:
        raise ValueError(
            f"ssd_scan: sequence length {s} not divisible by chunk {chunk}")
    nc = s // chunk

    # layout: (B, H, nc, Q, ·) tiles
    xt = x.transpose(0, 2, 1, 3).reshape(bsz, h, nc, chunk, p)
    dtt = dt.transpose(0, 2, 1).reshape(bsz, h, nc, chunk)
    a_bh = jnp.broadcast_to(a[None, :], (bsz, h))
    bt = jnp.repeat(b_mat.transpose(0, 2, 1, 3), rep, axis=1) \
        .reshape(bsz, h, nc, chunk, n)
    ct = jnp.repeat(c_mat.transpose(0, 2, 1, 3), rep, axis=1) \
        .reshape(bsz, h, nc, chunk, n)

    grid = (bsz, h, nc)
    scratch = [pltpu.VMEM((p, n), jnp.float32)]

    y = pl.pallas_call(
        functools.partial(_kernel, q=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1), lambda b_, h_, c_: (b_, h_)),
            pl.BlockSpec((1, 1, 1, chunk, n),
                         lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n),
                         lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, p),
                               lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, nc, chunk, p), x.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(xt, dtt, a_bh, bt, ct)

    return y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
