"""Device self time of the ops under the program's ``ode_aca_backward``
scope (the batched ACA backward sweep: per-row reverse replay with its
rk_stage kernels and checkpoint reads) over the device's busy time in
the traced window (%).

Shares overlap: the sweep's field evaluations also count in
``field_share.solve``.  A fusion is attributed by its root's op_name
(``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, scopes.ACA_BACKWARD)
