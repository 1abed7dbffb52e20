"""1 - union of device-busy intervals over the traced window."""
from benchmark.readers._common import idle_pct as read  # noqa: F401
