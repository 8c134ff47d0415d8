"""Mean device time of a segment program in the traced chat window (ms)."""
from benchlib.readers import segment_ms as read  # noqa: F401
