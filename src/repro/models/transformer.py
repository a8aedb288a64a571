"""Transformer stack: block composition, scan-over-layers, NODE mode.

A *block* is (norm → mixer → residual, norm → ffn/moe → residual), or the
parallel variant (Cohere Command-R style: attn and ffn both read one
norm).  The mixer is attention, an RG-LRU recurrent block, or a Mamba-2
SSM block depending on ``cfg.family`` / ``cfg.pattern``.

The stack runs as ``lax.scan`` over stacked per-layer parameters — HLO
size O(1) in depth, mandatory for 64–94-layer configs to compile on 512
devices.  Hybrid (RecurrentGemma) stacks scan over repeating *groups*
(("rec","rec","attn")); trailing remainder layers apply unscanned.

NODE mode — the paper's contribution as a first-class feature: each
block's residual branch becomes the dynamics of an ODE block
``z(1) = z(0) + ∫₀¹ f(z) dt`` (Eq. 30 → 31), solved with the configured
solver and differentiated with ACA (or adjoint/naive for the paper's
comparisons).  The ``fixed`` regime (static step count) is used for
multi-pod lowering; ``adaptive`` matches the paper's training setup.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.node_block import NodeConfig, node_block_apply
from .attention import attention_apply, attn_defs
from .common import ParamDef, apply_norm, norm_defs
from .config import ModelConfig, RunConfig
from .ffn import ffn_apply, ffn_defs
from .mamba2 import mamba2_block_apply, mamba2_cache_defs, mamba2_defs
from .moe import moe_apply, moe_defs
from .rglru import rglru_block_apply, rglru_cache_defs, rglru_defs

PyTree = Any


# ----------------------------------------------------------------------------
# Per-layer definitions
# ----------------------------------------------------------------------------

def layer_kinds(cfg: ModelConfig) -> List[str]:
    """Block kind per layer: 'attn' | 'moe_attn' | 'rec' | 'ssm'."""
    if cfg.family == "ssm":
        return ["ssm"] * cfg.n_layers
    if cfg.family == "hybrid":
        pat = cfg.pattern or ("rec", "rec", "attn")
        return [pat[i % len(pat)] for i in range(cfg.n_layers)]
    if cfg.family == "moe":
        return ["moe_attn"] * cfg.n_layers
    return ["attn"] * cfg.n_layers


def block_defs(cfg: ModelConfig, kind: str, param_dtype) -> PyTree:
    d = {"norm1": norm_defs(cfg.norm, cfg.d_model, param_dtype)}
    if kind == "ssm":
        d["mixer"] = mamba2_defs(cfg, param_dtype)
        return d  # mamba2 blocks are single-residual (no separate ffn)
    if kind == "rec":
        d["mixer"] = rglru_defs(cfg, param_dtype)
    else:
        d["mixer"] = attn_defs(cfg, param_dtype)
    if not cfg.parallel_block:
        d["norm2"] = norm_defs(cfg.norm, cfg.d_model, param_dtype)
    if kind == "moe_attn":
        d["moe"] = moe_defs(cfg, param_dtype)
    else:
        d["ffn"] = ffn_defs(cfg, param_dtype, gated=(cfg.act == "silu"))
    return d


def block_cache_defs(cfg: ModelConfig, kind: str, batch: int,
                     max_seq: int, cache_dtype) -> Optional[PyTree]:
    hk, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    if kind == "ssm":
        return mamba2_cache_defs(cfg, batch)
    if kind == "rec":
        return rglru_cache_defs(cfg, batch, cache_dtype)
    # attention KV cache; window-limited archs only need the window
    slots = max_seq if cfg.window == 0 else min(max_seq, cfg.window)
    return {
        "k": ParamDef((batch, slots, hk, dh), cache_dtype,
                      ("batch", "kv_seq", None, None), init="zeros"),
        "v": ParamDef((batch, slots, hk, dh), cache_dtype,
                      ("batch", "kv_seq", None, None), init="zeros"),
        "len": ParamDef((), jnp.int32, (), init="zeros"),
    }


# ----------------------------------------------------------------------------
# Block application
# ----------------------------------------------------------------------------

def _mixer_apply(kind: str, p, x, cfg, rcfg, *, mode, positions, cache):
    if kind == "ssm":
        return mamba2_block_apply(p, x, cfg, rcfg, mode=mode, cache=cache)
    if kind == "rec":
        return rglru_block_apply(p, x, cfg, rcfg, mode=mode, cache=cache)
    return attention_apply(p, x, cfg, rcfg, mode=mode, positions=positions,
                           cache=cache)


def block_apply(
    p: PyTree,
    x: jnp.ndarray,
    cfg: ModelConfig,
    rcfg: RunConfig,
    kind: str,
    *,
    mode: str = "train",
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[PyTree] = None,
) -> Tuple[jnp.ndarray, Optional[PyTree], jnp.ndarray]:
    """One block with residuals.  Returns (y, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(cfg.norm, x, p["norm1"], cfg.norm_eps)
    mix, new_cache = _mixer_apply(kind, p["mixer"], h, cfg, rcfg,
                                  mode=mode, positions=positions,
                                  cache=cache)
    if kind == "ssm":
        return x + mix, new_cache, aux

    if cfg.parallel_block:
        # Command-R: y = x + attn(n(x)) + ffn(n(x))
        if kind == "moe_attn":
            f, aux = moe_apply(p["moe"], h, cfg, rcfg)
        else:
            f = ffn_apply(p["ffn"], h, cfg, rcfg)
        return x + mix + f, new_cache, aux

    y = x + mix
    h2 = apply_norm(cfg.norm, y, p["norm2"], cfg.norm_eps)
    if kind == "moe_attn":
        f, aux = moe_apply(p["moe"], h2, cfg, rcfg)
    else:
        f = ffn_apply(p["ffn"], h2, cfg, rcfg)
    return y + f, new_cache, aux


def _branch_fn(p, x, cfg, rcfg, kind, positions):
    """The residual *branch* (dy = block(x) - x) — NODE dynamics f."""
    y, _, _ = block_apply(p, x, cfg, rcfg, kind, mode="train",
                          positions=positions, cache=None)
    return y - x


# ----------------------------------------------------------------------------
# Stack
# ----------------------------------------------------------------------------

def _stack_defs(defs: PyTree, n: int) -> PyTree:
    """Prepend a stacked-layers dim to every ParamDef leaf."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, d.dtype,
                           ("layers",) + d.logical, init=d.init,
                           scale=d.scale),
        defs, is_leaf=lambda d: isinstance(d, ParamDef))


def stack_plan(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int, List[str]]:
    """(repeating unit kinds, n_groups, tail kinds)."""
    kinds = layer_kinds(cfg)
    if cfg.family == "hybrid":
        pat = cfg.pattern or ("rec", "rec", "attn")
        n_groups = cfg.n_layers // len(pat)
        tail = kinds[n_groups * len(pat):]
        return tuple(pat), n_groups, tail
    return (kinds[0],), cfg.n_layers, []


def stack_defs(cfg: ModelConfig, param_dtype) -> PyTree:
    unit, n_groups, tail = stack_plan(cfg)
    d: Dict[str, PyTree] = {}
    for j, kind in enumerate(unit):
        d[f"u{j}_{kind}"] = _stack_defs(
            block_defs(cfg, kind, param_dtype), n_groups)
    for j, kind in enumerate(tail):
        d[f"tail{j}_{kind}"] = block_defs(cfg, kind, param_dtype)
    return d


def stack_cache_defs(cfg: ModelConfig, batch: int, max_seq: int,
                     cache_dtype) -> PyTree:
    unit, n_groups, tail = stack_plan(cfg)
    d: Dict[str, PyTree] = {}
    for j, kind in enumerate(unit):
        cd = block_cache_defs(cfg, kind, batch, max_seq, cache_dtype)
        d[f"u{j}_{kind}"] = _stack_defs(cd, n_groups)
    for j, kind in enumerate(tail):
        d[f"tail{j}_{kind}"] = block_cache_defs(cfg, kind, batch, max_seq,
                                                cache_dtype)
    return d


def _apply_one(p, x, cfg, rcfg, kind, mode, positions, cache):
    """One layer.  Returns (y, new_cache, aux_loss, node_stats), the
    last the NODE block's ``SolveStats`` (None for a discrete block)."""
    if rcfg.node.enabled and mode == "train":
        # the paper: residual block -> ODE block, ACA gradients.
        # RunConfig.use_pallas turns on the fused flat-state solver path
        # for every NODE block, matching the kernels used elsewhere.
        ncfg = rcfg.node
        if rcfg.use_pallas and not ncfg.use_pallas:
            ncfg = dataclasses.replace(ncfg, use_pallas=True)
        zT, stats = node_block_apply(
            lambda pp, z, t: _branch_fn(pp, z, cfg, rcfg, kind, positions),
            p, x, ncfg)
        return zT, None, jnp.zeros((), jnp.float32), stats
    y, new_cache, aux = block_apply(p, x, cfg, rcfg, kind, mode=mode,
                                    positions=positions, cache=cache)
    return y, new_cache, aux, None


def _stack_stats(stats: List[Optional[PyTree]]) -> Optional[PyTree]:
    """Layer-stacked NODE stats (each leaf gains a leading layer axis),
    or None where the layers are discrete blocks."""
    if not stats or stats[0] is None:
        return None
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stats)


def stack_apply(
    params: PyTree,
    x: jnp.ndarray,
    cfg: ModelConfig,
    rcfg: RunConfig,
    *,
    mode: str = "train",
    positions: Optional[jnp.ndarray] = None,
    caches: Optional[PyTree] = None,
) -> Tuple[jnp.ndarray, Optional[PyTree], jnp.ndarray, Optional[PyTree]]:
    """Apply the full stack.  Returns (y, new_caches, aux_loss_sum,
    node_stats): ``node_stats`` is the NODE blocks' ``SolveStats`` with
    a leading (n_layers,) axis in layer order, None when the stack runs
    no NODE block (inference modes, discrete blocks)."""
    unit, n_groups, tail = stack_plan(cfg)
    need_cache = mode in ("prefill", "decode")
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, PyTree] = {}

    def group_body(x, layer_in):
        gp, gc = layer_in
        aux_g = jnp.zeros((), jnp.float32)
        outs = {}
        stats = []
        for j, kind in enumerate(unit):
            key = f"u{j}_{kind}"
            c = gc.get(key) if gc is not None else None
            x, nc, aux, st = _apply_one(gp[key], x, cfg, rcfg, kind, mode,
                                        positions, c)
            if need_cache:
                outs[key] = nc
            aux_g = aux_g + aux
            stats.append(st)
        return x, (outs if need_cache else None, aux_g, _stack_stats(stats))

    group_params = {k: v for k, v in params.items() if k.startswith("u")}
    group_caches = None
    if caches is not None:
        group_caches = {k: v for k, v in caches.items()
                        if k.startswith("u")}

    if rcfg.scan_layers and n_groups > 1:
        body = group_body
        if rcfg.remat == "block":
            body = jax.checkpoint(group_body)
        x, (cache_out, aux_stack, group_stats) = jax.lax.scan(
            body, x, (group_params,
                      group_caches if group_caches is not None
                      else _none_tree(group_params, n_groups)))
        aux_total = aux_total + aux_stack.sum()
        if need_cache:
            new_caches.update(cache_out)
    else:
        per_group = []
        for i in range(n_groups):
            gp = jax.tree.map(lambda v: v[i], group_params)
            gc = jax.tree.map(lambda v: v[i], group_caches) \
                if group_caches is not None else None
            x, (outs, aux_g, st) = group_body(x, (gp, gc))
            aux_total = aux_total + aux_g
            per_group.append(st)
            if need_cache:
                for k, v in outs.items():
                    new_caches.setdefault(k, []).append(v)
        group_stats = _stack_stats(per_group)
        if need_cache and new_caches:
            new_caches = {
                k: jax.tree.map(lambda *ls: jnp.stack(ls), *v)
                for k, v in new_caches.items()}

    # (n_groups, len(unit)) -> layer order, then the tail's layers
    layer_stats = [] if group_stats is None else [jax.tree.map(
        lambda v: v.reshape((-1,) + v.shape[2:]), group_stats)]
    for j, kind in enumerate(tail):
        key = f"tail{j}_{kind}"
        c = caches.get(key) if caches is not None else None
        x, nc, aux, st = _apply_one(params[key], x, cfg, rcfg, kind, mode,
                                    positions, c)
        aux_total = aux_total + aux
        if need_cache:
            new_caches[key] = nc
        if st is not None:
            layer_stats.append(jax.tree.map(lambda v: v[None], st))
    node_stats = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                              *layer_stats) if layer_stats else None

    return x, (new_caches if need_cache else None), aux_total, node_stats


def _none_tree(group_params: PyTree, n: int):
    """Placeholder cache xs for scan when no cache is threaded."""
    return {k: None for k in group_params}
