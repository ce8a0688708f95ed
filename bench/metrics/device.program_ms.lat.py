"""Device time of the serving programs per batch, from the trace, in a cell judged on latency."""

from bench.readings import program_ms as read  # noqa: F401
