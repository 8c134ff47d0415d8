"""Model operations of the chat window over its seconds times the int8 peak (per cent)."""
from benchlib.readers import mfu as read  # noqa: F401
