"""Programs compiled or fetched from the cache inside the chat window (count)."""
from benchlib.readers import compiles_in_window as read  # noqa: F401
