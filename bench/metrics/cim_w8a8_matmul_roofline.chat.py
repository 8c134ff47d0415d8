"""cim_w8a8_matmul's least time from its operand shapes over its device time, chat window (per cent)."""
from benchlib.readers import w8a8_roofline as read  # noqa: F401
