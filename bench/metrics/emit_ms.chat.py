"""Median serve/emit time (token events and retirement) per turnaround, traced chat window (ms)."""
from benchlib.spans import emit_ms as read  # noqa: F401
