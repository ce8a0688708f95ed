"""Serving programs compiled or loaded during the window, in a cell judged on latency."""

from bench.readings import compiles_in_window as read  # noqa: F401
