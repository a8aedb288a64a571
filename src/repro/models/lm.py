"""Language-model assembly: embeddings, stack, head, loss, decode.

``build_model(cfg, rcfg)`` returns a ``Model`` facade with:

  * ``defs`` / ``init`` / ``abstract`` / ``specs`` — parameter tree,
  * ``loss_fn(params, batch)``        — train-mode forward + CE loss
    (its metrics hold ``node_stats``, the NODE blocks' per-layer
    ``SolveStats``, when the stack runs NODE blocks),
  * ``prefill(params, batch)``        — forward returning per-layer caches,
  * ``decode_step(params, batch, caches)`` — one-token serve step,
  * ``cache_defs(batch, max_seq)``    — KV/state cache ParamDefs.

Batches: ``{"tokens": (B,S) i32, "labels": (B,S) i32, "mask": (B,S)}``;
frontend-stub archs (VLM / audio) replace ``tokens`` with precomputed
``embeds`` (B,S,D) per the assignment (backbone-only).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from .common import (ParamDef, abstract_params, apply_norm, init_params,
                     norm_defs, param_count, param_specs)
from .config import ModelConfig, RunConfig
from .transformer import stack_apply, stack_cache_defs, stack_defs

PyTree = Any


def model_defs(cfg: ModelConfig, param_dtype) -> PyTree:
    d: Dict[str, PyTree] = {}
    if cfg.frontend == "none":
        d["embed"] = ParamDef((cfg.vocab, cfg.d_model), param_dtype,
                              ("vocab", "embed"), init="embed")
    d["stack"] = stack_defs(cfg, param_dtype)
    d["final_norm"] = norm_defs(cfg.norm, cfg.d_model, param_dtype)
    if not cfg.tie_embeddings or cfg.frontend != "none":
        d["lm_head"] = ParamDef((cfg.d_model, cfg.vocab), param_dtype,
                                ("embed", "vocab"), init="embed")
    return d


def _embed(params: PyTree, batch: Dict[str, jnp.ndarray],
           cfg: ModelConfig, rcfg: RunConfig) -> jnp.ndarray:
    if cfg.frontend != "none":
        x = batch["embeds"].astype(rcfg.compute_dtype)
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0) \
            .astype(rcfg.compute_dtype)
        if cfg.tie_embeddings:
            x = x * jnp.sqrt(jnp.asarray(cfg.d_model, rcfg.compute_dtype))
    return shard(x, ("batch", "res_seq", "embed_act"), rcfg.rules,
                 rcfg.mesh)


def _head(params: PyTree, x: jnp.ndarray, cfg: ModelConfig,
          rcfg: RunConfig) -> jnp.ndarray:
    x = apply_norm(cfg.norm, x, params["final_norm"], cfg.norm_eps)
    if "lm_head" in params:
        w = params["lm_head"].astype(rcfg.compute_dtype)
        logits = jnp.einsum("bsd,dv->bsv", x, w)
    else:
        w = params["embed"].astype(rcfg.compute_dtype)
        logits = jnp.einsum("bsd,vd->bsv", x, w)
    return shard(logits, ("batch", "seq", "vocab_act"), rcfg.rules,
                 rcfg.mesh)


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray],
                 label_smoothing: float = 0.0) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """Mean CE over masked tokens, fp32.  Returns (loss, n_tokens)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if label_smoothing > 0.0:
        smooth = -lf.mean(axis=-1) + lse
        nll = (1 - label_smoothing) * nll + label_smoothing * smooth
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    mask = mask.astype(jnp.float32)
    n = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / n, n


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    rcfg: RunConfig
    defs: PyTree

    # -- parameters ------------------------------------------------------
    def init(self, key) -> PyTree:
        return init_params(self.defs, key)

    def abstract(self) -> PyTree:
        return abstract_params(self.defs)

    def specs(self, mesh=None) -> PyTree:
        return param_specs(self.defs, self.rcfg.rules,
                           mesh if mesh is not None else self.rcfg.mesh)

    def n_params(self) -> int:
        return param_count(self.defs)

    # -- forward ---------------------------------------------------------
    def forward(self, params: PyTree, batch: Dict[str, jnp.ndarray],
                *, mode: str = "train",
                caches: Optional[PyTree] = None,
                positions: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Optional[PyTree], jnp.ndarray]:
        return self._forward(params, batch, mode, caches, positions)[:3]

    def _forward(self, params, batch, mode, caches=None, positions=None):
        """``forward`` plus the stack's NODE ``node_stats`` (or None)."""
        x = _embed(params, batch, self.cfg, self.rcfg)
        y, new_caches, aux, node_stats = stack_apply(
            params["stack"], x, self.cfg, self.rcfg, mode=mode,
            positions=positions, caches=caches)
        logits = _head(params, y, self.cfg, self.rcfg)
        return logits, new_caches, aux, node_stats

    def loss_fn(self, params: PyTree, batch: Dict[str, jnp.ndarray]
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        logits, _, aux, node_stats = self._forward(params, batch, "train")
        loss, n = softmax_xent(logits, batch["labels"],
                               batch.get("mask"),
                               self.rcfg.label_smoothing)
        total = loss + self.cfg.router_aux_coef * aux
        metrics = {"ce_loss": loss, "aux_loss": aux, "tokens": n}
        if node_stats is not None:
            metrics["node_stats"] = node_stats
        return total, metrics

    # -- serving ---------------------------------------------------------
    def prefill(self, params: PyTree, batch: Dict[str, jnp.ndarray]
                ) -> Tuple[jnp.ndarray, PyTree]:
        logits, caches, _ = self.forward(params, batch, mode="prefill")
        return logits[:, -1], caches

    def decode_step(self, params: PyTree, batch: Dict[str, jnp.ndarray],
                    caches: PyTree, position: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, PyTree]:
        """One new token.  batch['tokens'] (B,1) (or 'embeds' (B,1,D) for
        frontend-stub archs); position scalar i32."""
        ref = batch["tokens"] if "tokens" in batch else batch["embeds"]
        pos = jnp.broadcast_to(position, (ref.shape[0], 1))
        logits, caches, _ = self.forward(
            batch=batch, params=params, mode="decode", caches=caches,
            positions=pos)
        return logits[:, -1], caches

    def cache_defs(self, batch: int, max_seq: int,
                   cache_dtype=jnp.bfloat16) -> PyTree:
        return stack_cache_defs(self.cfg, batch, max_seq, cache_dtype)

    def abstract_caches(self, batch: int, max_seq: int,
                        cache_dtype=jnp.bfloat16) -> PyTree:
        return abstract_params(self.cache_defs(batch, max_seq, cache_dtype))

    def cache_specs(self, batch: int, max_seq: int,
                    cache_dtype=jnp.bfloat16, mesh=None) -> PyTree:
        return param_specs(self.cache_defs(batch, max_seq, cache_dtype),
                           self.rcfg.rules,
                           mesh if mesh is not None else self.rcfg.mesh)


def build_model(cfg: ModelConfig, rcfg: Optional[RunConfig] = None) -> Model:
    rcfg = rcfg or RunConfig()
    return Model(cfg=cfg, rcfg=rcfg,
                 defs=model_defs(cfg, rcfg.param_dtype))
