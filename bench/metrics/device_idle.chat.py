"""Share of the traced chat window with no device operation (per cent)."""
from benchlib.readers import device_idle as read  # noqa: F401
