"""device.idle_share.agent: 1 - device busy time over the traced window."""
from bench.readers import idle_share as read  # noqa: F401
