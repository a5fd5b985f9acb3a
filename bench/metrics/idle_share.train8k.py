"""Share of the traced window with no operation on the device (percent)."""
from bench.metrics.common import idle_share_pct as read  # noqa: F401
