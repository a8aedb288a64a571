"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant):
importing this module must not touch jax device state, because the
dry-run sets ``xla_force_host_platform_device_count`` before any jax
initialization and smoke tests must keep seeing 1 device.

Mesh layout:
  single-pod  (data=16, model=16)            — 256 chips (one v5e pod)
  multi-pod   (pod=2, data=16, model=16)     — 512 chips

The ``model`` axis carries TP / EP / decode sequence-parallelism; the
``data`` axis carries FSDP + batch DP; the ``pod`` axis carries pure DP
(parameters replicated across pods, one gradient all-reduce per step
over DCN — the only cross-pod traffic).  Elasticity: the mesh is a
function of the live device list, and every sharding is derived from
the mesh shape, so relaunching on (1|2|4, 16, 16) re-derives parameter
shardings and reuses checkpoints unchanged.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax

from repro.distributed.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def elastic_mesh_shape(n_devices: int,
                       model_parallel: int = 16) -> Tuple[int, int, int]:
    """(pods, data, model) for ``n_devices`` live devices.

    Pure shape derivation (no jax device state) so it can be unit-tested
    at any device count.  The data-parallel product dp = n/model is
    split into pods×data targeting ~16 data shards per pod: pods is the
    largest divisor of dp not exceeding max(dp // 16, 1) (pods=1 in the
    worst case, data then absorbing all of dp), so pods·data·model ==
    n_devices holds exactly for every divisible count — the old
    derivation rounded twice and dropped devices (dp=33 gave 2×16=32).

    Raises ``ValueError`` (not an assert — asserts vanish under
    ``python -O``) when ``model_parallel`` does not divide the device
    count: an elastic relaunch must shrink the data axes, never the TP
    axis, because parameter shardings are derived from the model axis.
    """
    if n_devices <= 0:
        raise ValueError(
            f"elastic mesh needs at least one device (got {n_devices})")
    if n_devices % model_parallel:
        raise ValueError(
            f"elastic mesh: device count {n_devices} is not a multiple "
            f"of model_parallel={model_parallel} — the TP axis is fixed "
            "across relaunches (parameter shardings derive from it); "
            "adjust model_parallel or the device reservation")
    dp = n_devices // model_parallel
    pods = max(dp // 16, 1)
    while dp % pods:            # keep pods a divisor: pods*data == dp
        pods -= 1
    return pods, dp // pods, model_parallel


def make_elastic_mesh(devices: Optional[Sequence] = None,
                      model_parallel: int = 16):
    """Mesh over whatever devices are alive: (pod, data, model) with the
    pod×data product derived from the device count (elastic re-launch).
    Raises ``ValueError`` when model_parallel does not divide the device
    count — see ``elastic_mesh_shape``."""
    devices = list(devices if devices is not None else jax.devices())
    shape = elastic_mesh_shape(len(devices), model_parallel)
    return auto_mesh(shape, ("pod", "data", "model"), devices)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh for CPU tests (requires >= n_data*n_model devices)."""
    return auto_mesh((n_data, n_model), ("data", "model"))
