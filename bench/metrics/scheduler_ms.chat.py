"""Median serve/schedule time per turnaround, less the dispatches and pool copies nested in it, traced chat window (ms)."""
from benchlib.spans import scheduler_ms as read  # noqa: F401
