"""Device self time of the ops under the program's ``ode_aca_backward``
scope (the whole ACA backward sweep of the NODE blocks: segment
re-integration, reverse replay with its rk_stage kernels, buffer reads)
over the device's busy time in the traced window (%).

Shares overlap: the sweep's field evaluations also count in
``field_share.train`` and its buffer writes in
``ckpt_write_share.train``.  A fusion is attributed by its root's
op_name (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, scopes.ACA_BACKWARD)
