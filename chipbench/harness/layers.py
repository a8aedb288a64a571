"""Shared arithmetic of the per-layer readers in ``metrics/``."""

from __future__ import annotations

from typing import Optional

from . import kernels

# the solver's fused stage-combination kernels: repro/kernels/rk_stage.py
RK_STAGE_MODULE = "rk_stage"


def idle_share(ctx) -> Optional[float]:
    s = ctx["summary"]
    if s.window_s <= 0 or not s.busy_s:
        return None
    return 100.0 * (1.0 - s.busy_mean_s / s.window_s)


def rk_stage_seconds(ctx) -> float:
    return kernels.kernel_seconds(ctx["summary"].op_s, ctx["kernels"],
                                  RK_STAGE_MODULE)


def busy_total_s(ctx) -> float:
    return sum(ctx["summary"].busy_s.values())
