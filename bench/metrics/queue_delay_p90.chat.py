"""p90 of the steps from arrival to admission, chat window (decode steps)."""
from benchlib.readers import queue_delay_p90 as read  # noqa: F401
