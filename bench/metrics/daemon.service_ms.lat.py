"""Mean batch service time (launch to completion), in a cell judged on latency."""

from bench.readings import service_ms as read  # noqa: F401
