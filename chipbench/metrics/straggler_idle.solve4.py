"""Idle time the chips spend waiting for the slowest one: the mean over
the chips of (the busiest chip's busy time - the chip's own), over the
traced window (%).  0 on one chip."""
from harness.layers import straggler_idle as read  # noqa: F401
