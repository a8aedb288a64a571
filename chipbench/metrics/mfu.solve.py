"""Whole-call share of the chips' bf16 peak (%): the field's matmul
FLOPs of every forward row trial in the traced window (6 new stage
evaluations per dopri5 trial, 2 * 255 * 255 FLOPs each), over the
window's time and the peak of all the cell's chips.  The backward
sweep's FLOPs are not counted, so the share is a lower bound."""

from harness import counts
from harness.layers import n_chips


def read(ctx):
    c = ctx["counters"]
    s = ctx["summary"]
    if not c.get("row_trials") or s.window_s <= 0 or not s.busy_s:
        return None
    d = c["width"] - 1
    new_evals = len(counts.DOPRI5_C) - 1
    flops = c["row_trials"] * new_evals * 2.0 * d * d
    return 100.0 * flops / (s.window_s * n_chips(ctx)
                            * ctx["peaks"]["bf16_flops_per_s"])
