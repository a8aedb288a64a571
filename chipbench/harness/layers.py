"""Shared arithmetic of the per-layer readers in ``metrics/``."""

from __future__ import annotations

import re
from typing import Optional

from . import kernels

# the solver's fused stage-combination kernels: repro/kernels/rk_stage.py
RK_STAGE_MODULE = "rk_stage"

# a traced op is a collective by its HLO instruction's name, which XLA
# takes from the opcode: all-reduce.128, all-gather-start.3, ...-done.3
_COLLECTIVE = re.compile(
    r"^(?:all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start|-done)?(?:\.\d+)?$")


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


def n_chips(ctx) -> int:
    """Chips the cell was given."""
    return len(ctx["devices"])


def is_collective(op: str) -> bool:
    return _COLLECTIVE.match(op) is not None


def collective_share(ctx) -> Optional[float]:
    """Self time of the collective ops summed over the chips, over their
    summed busy time (%)."""
    busy = busy_total_s(ctx)
    if busy <= 0:
        return None
    coll = sum(secs for op, secs in ctx["summary"].op_s.items()
               if is_collective(op))
    return 100.0 * coll / busy


def straggler_idle(ctx) -> Optional[float]:
    """Mean over the chips of (the busiest chip's busy time - the chip's
    busy time) over the window (%): the time a chip waits, idle, for the
    slowest shard.  A chip of the cell that ran nothing counts as busy
    for 0 s."""
    s = ctx["summary"]
    if s.window_s <= 0 or not s.busy_s:
        return None
    busy = list(s.busy_s.values())
    busy += [0.0] * (n_chips(ctx) - len(busy))
    top = max(busy)
    return 100.0 * sum(top - b for b in busy) / (len(busy) * s.window_s)
