"""Whole training step's share of the chip's bf16 peak (%).

FLOPs: the same network run as a discrete residual stack, forward and
backward (``harness.counts.dense_lm_train_flops``), per token, times the
tokens trained in the traced window; over the window's time and the
peak.  A NODE step evaluates each block at least twice forward, so the
count is below the work done and the share cannot exceed the true
utilization."""

from harness import counts


def read(ctx):
    c, s = ctx["counters"], ctx["summary"]
    if not c.get("tokens") or s.window_s <= 0 or not s.busy_s:
        return None
    m = ctx["config"]["model"]
    per_token = counts.dense_lm_train_flops(
        m["n_layers"], m["d_model"], m["d_ff"], m["n_heads"],
        m["head_dim"], m["n_kv_heads"], m["vocab"], ctx["traffic"]["seq"])
    return 100.0 * per_token * c["tokens"] / (
        s.window_s * ctx["peaks"]["bf16_flops_per_s"])
