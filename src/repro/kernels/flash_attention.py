"""Causal (optionally sliding-window) GQA flash attention for TPU.

Block-tiled online-softmax attention (Rabe & Staats / FlashAttention)
mapped onto the TPU grid:

  grid = (B, H, nq, nk), kv innermost; running (m, l, acc) live in VMEM
  scratch across the kv sweep of one q tile.

Beyond the XLA fallback (``repro.models.attention.chunked_attention``),
the kernel *skips* fully-masked kv tiles — upper-triangle blocks
(``j > i``) and out-of-window blocks — via ``pl.when``:  ~2× fewer MXU
FLOPs for causal, and O(S·w) instead of O(S²) for windowed attention.
GQA is native (the kv tile index maps ``h -> h // group``), so no
expanded-KV materialization happens on TPU.

Tiles default to (block_q=512, block_k=512) with dh lanes — MXU-aligned
(multiples of 128) and < 4 MB VMEM per operand at dh=128/bf16.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_q, block_k, window, nk):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal tile filter: kv tile j intersects q tile i iff j*bk <= i*bq+bq-1
    live = (j * block_k) <= (i * block_q + block_q - 1)
    if window > 0:
        # out-of-window tiles contribute nothing
        live = live & ((j * block_k + block_k) > (i * block_q - window))

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    # the diagonal tile is always the LAST live tile of the row
    last = jnp.minimum((i * block_q + block_q - 1) // block_k, nk - 1)

    @pl.when(j == last)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,      # (B, H, S, dh)
    k: jnp.ndarray,      # (B, Hkv, S, dh)
    v: jnp.ndarray,      # (B, Hkv, S, dh)
    *,
    window: int = 0,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, s, dh = q.shape
    hkv = k.shape[1]
    group = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q != 0 or s % block_k != 0:
        raise ValueError(
            f"flash_attention: sequence length {s} must divide by "
            f"block_q={block_q} and block_k={block_k}")
    nq, nk = s // block_q, s // block_k

    grid = (b, h, nq, nk)
    kern = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        window=window, nk=nk)

    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, dh), jnp.float32),
    ]

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
