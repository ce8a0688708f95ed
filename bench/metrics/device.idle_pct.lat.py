"""Share of the traced window in which no operation ran on the device, in a cell judged on latency."""

from bench.readings import idle_pct as read  # noqa: F401
