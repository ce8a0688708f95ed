"""Mean wait from a request's due time to the launch of its batch, in a cell judged on latency."""

from bench.readings import queue_wait_ms as read  # noqa: F401
