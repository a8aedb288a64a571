"""Fused Runge-Kutta kernels — the ACA inner-loop hot spot.

The per-trial cost of ψ over a flat (N,) state is three memory-bound
passes, each fused into one Pallas kernel here:

  * ``rk_stage_increment_pallas`` — per-stage state  z + h · Σ a_ij k_j
    (the argument of the i-th f evaluation); weights baked per tableau
    row, zero weights skipped at compile time.
  * ``rk_stage_combine_pallas`` — the accepted-solution combine
    z_next = z + h·Σ b_i k_i  and embedded error  err = h·Σ e_i k_i in a
    single pass.  Unfused, XLA materializes s intermediate AXPY results
    in HBM (s = #stages, up to 7 for Dopri5): ~(2s+2)·N bytes moved; the
    fused pass moves (s+3)·N — a ~2× cut of the memory-bound term.
  * ``rk_stage_combine_err_pallas`` — the combine *plus* per-tile
    partial sums of the scaled error norm
    Σ (err / (atol + rtol·max(|z|, |z_next|)))², so the accept/reject
    loop's ``error_ratio`` costs no extra full-array pass at all.

Layout: k is stacked (s, N); the grid tiles N in ``block``-lane tiles
(narrower states use one tile of N rounded up to whole 128-lane vregs).
Weights/tolerances are baked into the kernel as compile-time constants
(they come from the tableau), h arrives as a (1, 1) SMEM scalar.  Each
tile writes its norm partial across one lane-dense 128-lane row of an
(n_tiles, 1, 128) slab — every block then satisfies the TPU tiling
rules — and the wrapper reads lane 0 of each row.  ``*_ref`` companions in
``ref.py`` are the oracles; the differentiable dispatch wrappers live in
``ops.py``.

Batched variants (``*_batched_pallas``) serve the per-sample batched
solver (``odeint(..., batch_axis=0)``): the state is (B, N) with one
stepsize *per row*, k is stacked (s, B, N), and the error norm is
reduced **per row** — every batch element gets its own scaled-error
partial sums, so the accept/reject decision is per-element instead of
one global reduction over the whole batch.  The grid is (row groups ×
tiles): a row group is all of B when its tile fits the VMEM budget,
otherwise a multiple of 8 rows with B padded to whole groups (padded
rows use z=1, k=0, h=0 and are sliced off).  Per-row h arrives as a
(rows, 1) block and the per-row partials leave through a (rows, 128)
lane-dense slab.  Masking of rejected/finished elements is by zeroed
per-row h: a row with h = 0 computes z + 0·Σ… which round-trips
bit-exactly through the f32 accumulator, so frozen elements pass
through unchanged.

The ``*_rowtol`` variant additionally loads **per-row tolerances**: rtol
and atol arrive as (B,) arrays through (rows, 1) blocks — the ``h``
pattern — instead of baked compile-time floats, so every batch element
is error-controlled against its own (rtol, atol).  This is the
per-request tolerance QoS knob of the serving engine; the arithmetic is
unchanged, so equal-tolerance rows stay bitwise identical to the baked
kernel's.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK = 2048  # lanes per tile: multiple of 128 (VPU lane width)
_LANES = 128   # width of one norm-partial row (one vreg of lanes)
# VMEM bytes one batched row group may take for its (s + 3) f32 tiles
# (z, s stages, z_next, partials); double-buffered, that is half of the
# 16 MiB default scoped VMEM limit of a v5e
_ROW_GROUP_BYTES = 4 << 20


# --- pure-jnp twins -------------------------------------------------------
# Pallas calls have no transpose rule, so ``ops.py`` wraps each kernel in a
# custom_vjp whose backward is jax.vjp of these functions.  They must
# compute exactly what the kernel computes (same dtypes, same weight
# handling); the independent oracles used by the tests live in ``ref.py``.

def combine_jnp(z, k, h, b, e):
    kf = k.astype(jnp.float32)
    bw = jnp.asarray(b, jnp.float32)[:, None]
    zn = (z.astype(jnp.float32) + h * (bw * kf).sum(0)).astype(z.dtype)
    if e is None:
        err = jnp.zeros(z.shape, jnp.float32)
    else:
        ew = jnp.asarray(e, jnp.float32)[:, None]
        err = (h * (ew * kf).sum(0)).astype(jnp.float32)
    return zn, err


def increment_jnp(z, k, h, a):
    aw = jnp.asarray(tuple(a)[: k.shape[0]], jnp.float32)[:, None]
    incr = (aw * k.astype(jnp.float32)).sum(0)
    return (z.astype(jnp.float32) + h * incr).astype(z.dtype)


def combine_err_jnp(z, k, h, b, e, rtol, atol, with_err=True):
    zn, err = combine_jnp(z, k, h, b, e)
    scale = atol + rtol * jnp.maximum(
        jnp.abs(z.astype(jnp.float32)), jnp.abs(zn.astype(jnp.float32)))
    r = err / scale
    sq = jnp.sum(r * r)
    return (zn, err, sq) if with_err else (zn, sq)


def increment_batched_jnp(z, k, h, a):
    """(B, N) twin of ``increment_jnp`` with per-row stepsizes h (B,)."""
    aw = jnp.asarray(tuple(a)[: k.shape[0]], jnp.float32)[:, None, None]
    incr = (aw * k.astype(jnp.float32)).sum(0)          # (B, N)
    hv = h.astype(jnp.float32)[:, None]
    return (z.astype(jnp.float32) + hv * incr).astype(z.dtype)


def combine_err_batched_jnp(z, k, h, b, e, rtol, atol):
    """(B, N) twin of ``combine_err_jnp``: per-row combine + per-row
    scaled-error square sums (B,).

    ``rtol``/``atol`` may be scalars or per-row (B,) arrays (the
    per-request tolerance QoS path): a row's tolerance broadcasts down
    its lanes exactly like the baked scalar — same f32 arithmetic, so a
    row solved at tolerance τ is bitwise the all-τ batch's row.
    """
    kf = k.astype(jnp.float32)                          # (s, B, N)
    bw = jnp.asarray(b, jnp.float32)[:, None, None]
    ew = jnp.asarray(e, jnp.float32)[:, None, None]
    hv = h.astype(jnp.float32)[:, None]
    zn = (z.astype(jnp.float32) + hv * (bw * kf).sum(0)).astype(z.dtype)
    err = hv * (ew * kf).sum(0)
    rt = jnp.asarray(rtol, jnp.float32)
    at = jnp.asarray(atol, jnp.float32)
    rt = rt[:, None] if rt.ndim else rt
    at = at[:, None] if at.ndim else at
    scale = at + rt * jnp.maximum(
        jnp.abs(z.astype(jnp.float32)), jnp.abs(zn.astype(jnp.float32)))
    r = err / scale
    return zn, jnp.sum(r * r, axis=-1)


# --- layout helpers -------------------------------------------------------

def _h_spec(interpret: bool):
    if interpret:
        return pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM)


def _tile(n: int, block: int) -> int:
    """Lanes per tile: ``block``, or all of a narrower state rounded up
    to whole 128-lane rows (a 256-wide row is not padded to 2048)."""
    return min(block, -(-n // _LANES) * _LANES)


def _pad_lanes(z, k, block: int, z_fill: float = 0):
    """Pad the last (lane) axis of z and k to whole tiles of ``block``."""
    pad = (-z.shape[-1]) % block
    if pad:
        z = jnp.pad(z, [(0, 0)] * (z.ndim - 1) + [(0, pad)],
                    constant_values=z_fill)
        k = jnp.pad(k, [(0, 0)] * (k.ndim - 1) + [(0, pad)])
    return z, k


def _row_group(bsz: int, block: int, s: int) -> int:
    """Rows per grid step: all of B when the row group's tiles fit
    ``_ROW_GROUP_BYTES``, else the largest multiple of 8 that does."""
    fit = max(8, _ROW_GROUP_BYTES // ((s + 3) * block * 4) // 8 * 8)
    return bsz if bsz <= fit else fit


def _row_column(x, bsz: int, bpad: int, fill: float):
    """A per-row scalar (or (B,) array) as a padded (bpad, 1) f32 column."""
    col = jnp.broadcast_to(jnp.asarray(x, jnp.float32), (bsz,))
    return jnp.pad(col, (0, bpad - bsz), constant_values=fill)[:, None]


def _weighted(k_ref, ws, like):
    """Σ_i ws[i] · k_i in f32, exact-zero weights skipped at trace time."""
    acc = jnp.zeros_like(like)
    for i, w in enumerate(ws):
        if w != 0.0:
            acc = acc + w * k_ref[i, ...].astype(jnp.float32)
    return acc


def _write_partials(nrm_ref, sq):
    """Broadcast a tile's square sum(s) across its lane-dense slab row."""
    nrm_ref[...] = jnp.broadcast_to(sq, nrm_ref.shape)


# --- flat kernels -----------------------------------------------------------

def _kernel(h_ref, z_ref, k_ref, out_ref, err_ref, *, b, e):
    h = h_ref[0, 0]
    z = z_ref[...].astype(jnp.float32)
    out_ref[...] = (z + h * _weighted(k_ref, b, z)).astype(out_ref.dtype)
    err_ref[...] = (h * _weighted(k_ref, e, z)).astype(err_ref.dtype)


def rk_stage_combine_pallas(
    z: jnp.ndarray,          # (N,) flattened state
    k: jnp.ndarray,          # (s, N) stacked stage derivatives
    h: jnp.ndarray,          # scalar stepsize
    b: Sequence[float],      # solution weights
    e: Optional[Sequence[float]],  # embedded-error weights (None -> zeros)
    *,
    block: int = _BLOCK,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (z_next (N,), err (N,))."""
    s, n = k.shape
    assert z.shape == (n,)
    e = tuple(e) if e is not None else tuple(0.0 for _ in b)
    b = tuple(b)

    block = _tile(n, block)
    z, k = _pad_lanes(z, k, block)
    npad = z.shape[0]
    h2d = jnp.asarray(h, jnp.float32).reshape(1, 1)

    out, err = pl.pallas_call(
        functools.partial(_kernel, b=b, e=e),
        grid=(npad // block,),
        in_specs=[
            _h_spec(interpret),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((s, block), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad,), z.dtype),
            jax.ShapeDtypeStruct((npad,), jnp.float32),
        ],
        interpret=interpret,
    )(h2d, z, k)
    return out[:n], err[:n]


def _incr_kernel(h_ref, z_ref, k_ref, out_ref, *, a):
    h = h_ref[0, 0]
    z = z_ref[...].astype(jnp.float32)
    out_ref[...] = (z + h * _weighted(k_ref, a, z)).astype(out_ref.dtype)


def rk_stage_increment_pallas(
    z: jnp.ndarray,          # (N,) flattened state
    k: jnp.ndarray,          # (j, N) stage derivatives computed so far
    h: jnp.ndarray,          # scalar stepsize
    a: Sequence[float],      # tableau row a[i][:j]
    *,
    block: int = _BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns z + h · Σ_j a_j k_j  (the i-th stage argument), shape (N,)."""
    s, n = k.shape
    assert z.shape == (n,)
    a = tuple(a)[:s]

    block = _tile(n, block)
    z, k = _pad_lanes(z, k, block)
    npad = z.shape[0]
    h2d = jnp.asarray(h, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_incr_kernel, a=a),
        grid=(npad // block,),
        in_specs=[
            _h_spec(interpret),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((s, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), z.dtype),
        interpret=interpret,
    )(h2d, z, k)
    return out[:n]


def _combine_err_kernel(h_ref, z_ref, k_ref, out_ref, *out_rest,
                        b, e, rtol, atol, with_err):
    err_ref, nrm_ref = out_rest if with_err else (None, out_rest[0])
    h = h_ref[0, 0]
    z = z_ref[...].astype(jnp.float32)
    zn = z + h * _weighted(k_ref, b, z)
    err = h * _weighted(k_ref, e, z)
    out_ref[...] = zn.astype(out_ref.dtype)
    if with_err:
        err_ref[...] = err
    scale = atol + rtol * jnp.maximum(jnp.abs(z), jnp.abs(zn))
    r = err / scale
    _write_partials(nrm_ref, jnp.sum(r * r))


def rk_stage_combine_err_pallas(
    z: jnp.ndarray,          # (N,) flattened state
    k: jnp.ndarray,          # (s, N) stacked stage derivatives
    h: jnp.ndarray,          # scalar stepsize
    b: Sequence[float],      # solution weights
    e: Sequence[float],      # embedded-error weights
    rtol: float,
    atol: float,
    *,
    with_err: bool = True,
    block: int = _BLOCK,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], jnp.ndarray]:
    """Returns (z_next (N,), err (N,) | None, norm_partials (n_tiles,)).

    ``norm_partials[t]`` is the tile-t partial sum of
    (err / (atol + rtol·max(|z|, |z_next|)))² — summing it and dividing
    by N gives ``error_ratio``² without a second full-array pass.
    Padded lanes are filled with z=1, k=0 so err=0 there and the scale
    stays positive: they contribute exactly 0 to the norm.

    ``with_err=False`` skips the (N,) err store entirely (the adaptive
    solver loop consumes only z_next and the norm) and returns None in
    its slot.
    """
    s, n = k.shape
    assert z.shape == (n,)
    b = tuple(b)
    e = tuple(e)

    block = _tile(n, block)
    z, k = _pad_lanes(z, k, block, z_fill=1)
    npad = z.shape[0]
    n_tiles = npad // block
    h2d = jnp.asarray(h, jnp.float32).reshape(1, 1)

    tile_spec = pl.BlockSpec((block,), lambda i: (i,))
    outs = pl.pallas_call(
        functools.partial(_combine_err_kernel, b=b, e=e,
                          rtol=float(rtol), atol=float(atol),
                          with_err=with_err),
        grid=(n_tiles,),
        in_specs=[
            _h_spec(interpret),
            tile_spec,
            pl.BlockSpec((s, block), lambda i: (0, i)),
        ],
        out_specs=[
            tile_spec,
            *([tile_spec] if with_err else []),
            pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad,), z.dtype),
            *([jax.ShapeDtypeStruct((npad,), jnp.float32)]
              if with_err else []),
            jax.ShapeDtypeStruct((n_tiles, 1, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(h2d, z, k)
    nrm = outs[-1][:, 0, 0]
    err = outs[1][:n] if with_err else None
    return outs[0][:n], err, nrm


# --- batched (per-sample) kernels ----------------------------------------
# The grid is (row groups × lane tiles); h is (B,) — each row advances
# with its own trial stepsize, and the error norm partials are per row so
# the controller can accept/reject elements independently (the whole
# point of batch_axis: no lockstep).

def _batched_layout(z, k, h, block: int, z_fill: float):
    """Pads (B, N) z / (s, B, N) k to whole (rows × block) tiles.

    Returns (z, k, h column (bpad, 1), rows, block, grid); padded rows
    carry h = 0 and padded lanes k = 0, so both contribute exact zeros.
    """
    s, bsz, n = k.shape
    if z.shape != (bsz, n):
        raise ValueError(f"batched state {z.shape} does not match stage "
                         f"derivatives {k.shape}")
    block = _tile(n, block)
    rows = _row_group(bsz, block, s)
    bpad = -(-bsz // rows) * rows
    z, k = _pad_lanes(z, k, block, z_fill)
    if bpad != bsz:
        z = jnp.pad(z, ((0, bpad - bsz), (0, 0)), constant_values=z_fill)
        k = jnp.pad(k, ((0, 0), (0, bpad - bsz), (0, 0)))
    grid = (bpad // rows, z.shape[1] // block)
    return z, k, _row_column(h, bsz, bpad, 0.0), rows, block, grid


def _batched_specs(s: int, rows: int, block: int, n_cols: int):
    """In-specs for ``n_cols`` per-row (rows, 1) columns, z and k."""
    col = pl.BlockSpec((rows, 1), lambda r, i: (r, 0))
    return [col] * n_cols + [
        pl.BlockSpec((rows, block), lambda r, i: (r, i)),
        pl.BlockSpec((s, rows, block), lambda r, i: (0, r, i)),
    ]


def _incr_batched_kernel(h_ref, z_ref, k_ref, out_ref, *, a):
    h = h_ref[...]                                   # (rows, 1)
    z = z_ref[...].astype(jnp.float32)
    out_ref[...] = (z + h * _weighted(k_ref, a, z)).astype(out_ref.dtype)


def rk_stage_increment_batched_pallas(
    z: jnp.ndarray,          # (B, N) flattened per-sample states
    k: jnp.ndarray,          # (s, B, N) stacked stage derivatives
    h: jnp.ndarray,          # (B,) per-row stepsizes
    a: Sequence[float],      # tableau row a[i][:j]
    *,
    block: int = _BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-row z + h_b · Σ_j a_j k_j, shape (B, N).

    A row whose h_b is 0 passes through bit-exactly (the f32 round trip
    of z + 0 is the identity) — the masking contract used by the batched
    solver to freeze rejected/finished elements.
    """
    s, bsz, n = k.shape
    a = tuple(a)[:s]
    zp, kp, hcol, rows, block, grid = _batched_layout(z, k, h, block, 0)

    out = pl.pallas_call(
        functools.partial(_incr_batched_kernel, a=a),
        grid=grid,
        in_specs=_batched_specs(s, rows, block, 1),
        out_specs=pl.BlockSpec((rows, block), lambda r, i: (r, i)),
        out_shape=jax.ShapeDtypeStruct(zp.shape, z.dtype),
        interpret=interpret,
    )(hcol, zp, kp)
    return out[:bsz, :n]


def _combine_err_rows(h, rtol, atol, z_ref, k_ref, out_ref, nrm_ref, b, e):
    """Shared body of the batched combine kernels: ``h``/``rtol``/
    ``atol`` are per-row (rows, 1) columns or baked floats."""
    z = z_ref[...].astype(jnp.float32)
    zn = z + h * _weighted(k_ref, b, z)
    err = h * _weighted(k_ref, e, z)
    out_ref[...] = zn.astype(out_ref.dtype)
    scale = atol + rtol * jnp.maximum(jnp.abs(z), jnp.abs(zn))
    r = err / scale
    _write_partials(nrm_ref, jnp.sum(r * r, axis=-1, keepdims=True))


def _combine_err_batched_kernel(h_ref, z_ref, k_ref, out_ref, nrm_ref, *,
                                b, e, rtol, atol):
    _combine_err_rows(h_ref[...], rtol, atol, z_ref, k_ref, out_ref,
                      nrm_ref, b, e)


def _combine_err_batched_rowtol_kernel(h_ref, rtol_ref, atol_ref, z_ref,
                                       k_ref, out_ref, nrm_ref, *, b, e):
    _combine_err_rows(h_ref[...], rtol_ref[...], atol_ref[...], z_ref,
                      k_ref, out_ref, nrm_ref, b, e)


def _combine_err_batched_call(kernel, z, k, h, cols, block, interpret):
    """pallas_call of a batched combine kernel with ``cols`` extra
    per-row (B,) inputs after h; returns (z_next (B, N), partials
    (B, n_tiles))."""
    s, bsz, n = k.shape
    zp, kp, hcol, rows, block, grid = _batched_layout(z, k, h, block, 1)
    bpad = zp.shape[0]
    cols = [_row_column(c, bsz, bpad, 1.0) for c in cols]
    out, nrm = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=_batched_specs(s, rows, block, 1 + len(cols)),
        out_specs=[
            pl.BlockSpec((rows, block), lambda r, i: (r, i)),
            pl.BlockSpec((rows, _LANES), lambda r, i: (r, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(zp.shape, z.dtype),
            jax.ShapeDtypeStruct((bpad, grid[1] * _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(hcol, *cols, zp, kp)
    nrm = nrm.reshape(bpad, grid[1], _LANES)[:bsz, :, 0]
    return out[:bsz, :n], nrm


def rk_stage_combine_err_batched_pallas(
    z: jnp.ndarray,          # (B, N) flattened per-sample states
    k: jnp.ndarray,          # (s, B, N) stacked stage derivatives
    h: jnp.ndarray,          # (B,) per-row stepsizes
    b: Sequence[float],      # solution weights
    e: Sequence[float],      # embedded-error weights
    rtol: float,
    atol: float,
    *,
    block: int = _BLOCK,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (z_next (B, N), norm_partials (B, n_tiles)).

    ``norm_partials[b, t]`` is element b's tile-t partial sum of
    (err / (atol + rtol·max(|z|, |z_next|)))² — a **per-row** reduction:
    summing axis -1 and dividing by N gives each element's own
    ``error_ratio``², the quantity that makes per-sample accept/reject
    possible.  Padded lanes use z=1, k=0 so they contribute exactly 0.
    The err buffer is never materialized (the batched solver loop reads
    only z_next and the norms); rows with h_b = 0 return z unchanged and
    a zero norm (frozen-element masking).
    """
    kernel = functools.partial(_combine_err_batched_kernel, b=tuple(b),
                               e=tuple(e), rtol=float(rtol),
                               atol=float(atol))
    return _combine_err_batched_call(kernel, z, k, h, (), block, interpret)


def rk_stage_combine_err_batched_rowtol_pallas(
    z: jnp.ndarray,          # (B, N) flattened per-sample states
    k: jnp.ndarray,          # (s, B, N) stacked stage derivatives
    h: jnp.ndarray,          # (B,) per-row stepsizes
    b: Sequence[float],      # solution weights
    e: Sequence[float],      # embedded-error weights
    rtol: jnp.ndarray,       # (B,) per-row relative tolerances
    atol: jnp.ndarray,       # (B,) per-row absolute tolerances
    *,
    block: int = _BLOCK,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row-tolerance twin of ``rk_stage_combine_err_batched_pallas``.

    Identical combine arithmetic, but ``rtol``/``atol`` arrive as (B,)
    arrays loaded per row group through (rows, 1) blocks — the same
    pattern as the per-row stepsize ``h`` — instead of being baked into
    the kernel as compile-time constants.  A row whose loaded tolerance
    equals a baked scalar computes bit-identical f32 values (same ops,
    same tile partial-sum order), which is what lets tight- and
    loose-tolerance batch elements share one solve while each matches
    its own solo trajectory bitwise (the serving QoS contract).
    """
    kernel = functools.partial(_combine_err_batched_rowtol_kernel,
                               b=tuple(b), e=tuple(e))
    return _combine_err_batched_call(kernel, z, k, h, (rtol, atol), block,
                                     interpret)
