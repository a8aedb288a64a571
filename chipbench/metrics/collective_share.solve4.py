"""Self time of the collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, with their async start
and done halves) summed over the chips, over their summed busy time in
the traced window (%).  An op is a collective by its HLO instruction's
name (``harness.layers.is_collective``).  A chip that reaches a
blocking collective before the others waits inside it, so the share
holds that wait as well as the exchange."""
from harness.layers import collective_share as read  # noqa: F401
