"""Posting bytes at HBM bandwidth over the serving programs' device time, in a cell judged on latency."""

from bench.readings import roofline_pct as read  # noqa: F401
