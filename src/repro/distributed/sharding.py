"""Logical-axis sharding rules → ``PartitionSpec`` / ``NamedSharding``.

Tensors throughout the model code are annotated with *logical* axis names
("batch", "embed", "mlp", "heads", ...).  A rule table maps each logical
name to zero or more physical mesh axes.  This indirection is what lets the
same model definition run on

  * no mesh at all (CPU smoke tests — every rule resolves to ``None``),
  * the single-pod mesh  (data=16, model=16),
  * the multi-pod mesh   (pod=2, data=16, model=16),

and lets the perf loop change a sharding decision in exactly one place.

Two rule sets ship by default:

``DEFAULT_TRAIN_RULES``
    2-D weight sharding (FSDP x TP): weight ``embed``/``ffn-in`` dims shard
    over the data axis, head/mlp/vocab/expert dims over the model axis.
    XLA's SPMD partitioner materializes the FSDP all-gathers / reduce-
    scatters around each matmul — ZeRO-3-style memory scaling with
    overlap left to the XLA latency-hiding scheduler.

``DEFAULT_SERVE_RULES``
    Same 2-D weight layout (weight-gathered serving; large models do not
    fit TP-only on 16 chips) with the KV cache sequence dim sharded over
    the model axis for flash-decode.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# A rule value is a physical mesh axis name, a tuple of them, or None.
RuleValue = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Immutable logical→physical axis mapping."""

    rules: Tuple[Tuple[str, RuleValue], ...]

    def get(self, logical: Optional[str]) -> RuleValue:
        if logical is None:
            return None
        for k, v in self.rules:
            if k == logical:
                return v
        raise KeyError(f"no sharding rule for logical axis {logical!r}")

    def override(self, **kw: RuleValue) -> "AxisRules":
        """New rule set with some logical axes remapped (perf-loop hook)."""
        d = dict(self.rules)
        d.update(kw)
        return AxisRules(tuple(d.items()))


# "batch" resolves to every data-parallel axis present in the mesh; the
# helper below intersects rule values with the mesh's actual axis names so
# one table serves both single-pod and multi-pod meshes.
_COMMON: Dict[str, RuleValue] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,               # sequence dim of activations (unsharded)
    # residual-stream sequence dim: None = classic TP (activations
    # replicated over `model` between blocks); "model" = Megatron-style
    # sequence parallelism (norms/residual adds shard 16× further and
    # the TP all-reduce pair becomes all-gather + reduce-scatter)
    "res_seq": None,
    "embed_act": None,         # d_model dim of activations
    "heads_act": "model",      # per-head activation dim
    "kv_heads_act": None,      # kv heads are few; replicate (GQA-local attn)
    "mlp_act": "model",
    "vocab_act": "model",
    "kv_seq": "model",         # decode-time KV cache sequence dim (flash-decode)
    "expert_act": "model",
    # weights
    "embed": "data",           # d_model dim of weights  (FSDP axis)
    "heads": "model",          # q-head dim of weights   (TP axis)
    "kv_heads": None,
    "mlp": "model",            # d_ff dim of weights     (TP axis)
    "vocab": "model",          # vocab dim of embedding  (TP axis)
    "expert": "model",         # expert dim of MoE weights (EP axis)
    "layers": None,            # stacked-layer dim: replicated
    "conv": None,
    "stack": None,
}

DEFAULT_TRAIN_RULES = AxisRules(tuple(_COMMON.items()))

_SERVE = dict(_COMMON)
DEFAULT_SERVE_RULES = AxisRules(tuple(_SERVE.items()))


def _filter_axes(value: RuleValue, mesh: Optional[Mesh]) -> RuleValue:
    """Drop physical axes that are not present in the mesh."""
    if value is None or mesh is None:
        return None if mesh is None else value
    names = set(mesh.axis_names)
    if isinstance(value, str):
        return value if value in names else None
    kept = tuple(a for a in value if a in names)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: AxisRules,
    mesh: Optional[Mesh] = None,
) -> P:
    """PartitionSpec for a tensor annotated with logical axis names."""
    parts = []
    for ax in logical_axes:
        v = rules.get(ax)
        if mesh is not None:
            v = _filter_axes(v, mesh)
        parts.append(v)
    # trailing Nones can be dropped but keeping them is harmless/explicit
    return P(*parts)


def shard(
    x: PyTree,
    logical_axes: Sequence[Optional[str]],
    rules: AxisRules,
    mesh: Optional[Mesh],
) -> PyTree:
    """``with_sharding_constraint`` if a mesh is active, else identity.

    Models call this at layer boundaries; on a mesh-less CPU run it
    disappears entirely.
    """
    if mesh is None or mesh.empty:
        return x
    spec = logical_to_spec(logical_axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def make_named_sharding(
    logical_axes: Sequence[Optional[str]],
    rules: AxisRules,
    mesh: Mesh,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules, mesh))


def spec_tree_for(defs: PyTree, rules: AxisRules,
                  mesh: Optional[Mesh]) -> PyTree:
    """Map a tree of ParamDef (anything with .logical) to PartitionSpecs."""
    return jax.tree.map(
        lambda d: logical_to_spec(d.logical, rules, mesh),
        defs,
        is_leaf=lambda d: hasattr(d, "logical"),
    )


def fit_spec_to_shape(shape: Tuple[int, ...], spec: P,
                      mesh: Mesh) -> P:
    """Drop mesh axes from dims they don't divide (jit ``in_shardings``
    demands exact divisibility; GSPMD-internal constraints don't).

    E.g. vocab=50280 over model=16 -> replicated; batch=1 over
    (pod,data) -> replicated.  Axes are dropped right-to-left so the
    leading (usually larger) axis survives when a partial product fits.
    """
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, val in zip(shape, parts):
        if val is None:
            out.append(None)
            continue
        axes = list(val) if isinstance(val, tuple) else [val]
        while axes:
            prod = 1
            for a in axes:
                prod *= mesh.shape[a]
            if dim % prod == 0:
                break
            axes.pop()          # drop the rightmost axis
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    return P(*out)


def fit_specs(abstract_tree: PyTree, spec_tree: PyTree,
              mesh: Mesh) -> PyTree:
    """Apply ``fit_spec_to_shape`` leafwise over matching trees."""
    return jax.tree.map(
        lambda a, s: fit_spec_to_shape(tuple(a.shape), s, mesh),
        abstract_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def data_axis_names(mesh: Optional[Mesh]) -> Tuple[str, ...]:
    """The mesh axes that carry data parallelism."""
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_partition_axes(mesh: Optional[Mesh],
                         rules: Optional[AxisRules] = None
                         ) -> Tuple[str, ...]:
    """Physical mesh axes the logical ``"batch"`` axis shards over.

    The rule table's ``"batch"`` entry (``("pod", "data")`` by default)
    intersected with the mesh's actual axis names — empty when the mesh
    carries no data-parallel axis at all (e.g. a pure-TP mesh).
    """
    rules = DEFAULT_TRAIN_RULES if rules is None else rules
    v = _filter_axes(rules.get("batch"), mesh)
    if v is None:
        return ()
    return (v,) if isinstance(v, str) else tuple(v)


def batch_shard_count(mesh: Optional[Mesh],
                      rules: Optional[AxisRules] = None) -> int:
    """Number of batch shards ``odeint(..., mesh=...)`` splits into
    (the product of the mesh's batch-partition axis sizes; 1 when the
    mesh has no data axis or is None)."""
    n = 1
    for a in batch_partition_axes(mesh, rules):
        n *= mesh.shape[a]
    return n


def shard_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """Flat 1-D ``("data",)`` mesh over all (or the given) devices.

    The simplest mesh ``odeint(..., mesh=...)`` accepts: every device is
    a batch shard, no model parallelism.  A function (never a constant)
    so importing this module touches no jax device state.
    """
    devices = list(devices if devices is not None else jax.devices())
    return auto_mesh((len(devices),), ("data",), devices)


def auto_mesh(shape: Tuple[int, ...], axis_names: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the repo's sharding
    goes through ``with_sharding_constraint`` and GSPMD propagation,
    which ``Explicit`` axes (the ``make_mesh`` default) reject."""
    return jax.make_mesh(
        shape, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def shard_map_compat(fn, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled — the one
    shard_map call site of the repo: the solver bodies run custom_vjp
    interiors the checker cannot see through, and the model shard_fns
    psum manually."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def model_axis_size(mesh: Optional[Mesh]) -> int:
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]
