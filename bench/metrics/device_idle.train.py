"""Share (%) of the traced window in which no operation ran on the
device: 1 - the union of device-op intervals over the window."""
from bench.readers import idle_pct as read  # noqa: F401
