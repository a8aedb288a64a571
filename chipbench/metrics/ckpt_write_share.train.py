"""Device self time of the ops under the program's ``ode_ckpt_write``
scope (the adaptive loop's trajectory-checkpoint snapshot writes, and
the segmented ACA backward's replay-buffer writes) over the device's
busy time in the traced window (%).

Shares overlap: the replay-buffer writes also count in
``aca_backward_share.train``.  A fusion is attributed by its root's
op_name (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, scopes.CKPT_WRITE)
