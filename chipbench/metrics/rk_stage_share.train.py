"""Device time of the rk_stage kernels over the device's busy time in
the traced window (%)."""

from harness.layers import busy_total_s, rk_stage_seconds


def read(ctx):
    busy = busy_total_s(ctx)
    return 100.0 * rk_stage_seconds(ctx) / busy if busy > 0 else None
