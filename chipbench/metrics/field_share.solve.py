"""Device self time of the ops under the program's ``ode_field`` scope
(the batched solve's field evaluations, forward and their VJPs in the
ACA backward replay) over the device's busy time in the traced window
(%).

Shares overlap: a field VJP of the backward replay also counts in
``aca_backward_share.solve``.  A fusion is attributed by its root's
op_name (``harness/scopes.py``)."""

from harness import scopes


def read(ctx):
    return scopes.share(ctx, scopes.FIELD)
