"""Share of the HBM roofline reached by the rk_stage kernels (%).

Bytes: what the stage combinations of every forward row trial in the
window must move, counted from the tableau, the state width and the
sum of row trials (``harness.counts.rk_bytes``); time: the device time
of the rk_stage kernels in the traced window, summed over the chips, so
that on several chips the bytes are held against all their peaks.
Trials of finished rows that ride along in the lockstep loop, and the
backward sweep's replay, add time but no bytes, so the share reads low
where they are large.
"""

from harness import counts
from harness.layers import rk_stage_seconds

TABLEAUS = {"dopri5": (counts.DOPRI5_A, counts.DOPRI5_B, counts.DOPRI5_E)}


def read(ctx):
    c = ctx["counters"]
    secs = rk_stage_seconds(ctx)
    if secs <= 0 or not c.get("row_trials"):
        return None
    a, b, e = TABLEAUS[c["tableau"]]
    moved = counts.rk_bytes(c["row_trials"], c["width"], c["itemsize"],
                            a, b, e)
    return 100.0 * moved / (ctx["peaks"]["hbm_bytes_per_s"] * secs)
