"""Device self time of the ops under the program's ``ode_ckpt_write``
scope (the batched adaptive loop's writes into its per-row trajectory
checkpoint) over the device's busy time in the traced window (%).

A fusion is attributed by its root's op_name (``harness/scopes.py``);
shares of different scopes may overlap."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, scopes.CKPT_WRITE)
