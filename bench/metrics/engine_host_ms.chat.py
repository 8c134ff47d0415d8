"""Median host time of the engine loop per turnaround (a harvest's end to the next dispatch's end, less the harness's spans), traced chat window (ms)."""
from benchlib.spans import engine_host_ms as read  # noqa: F401
