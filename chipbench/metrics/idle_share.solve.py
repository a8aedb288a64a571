"""Device idle share of the traced window, averaged over the chips (%)."""
from harness.layers import idle_share as read  # noqa: F401
