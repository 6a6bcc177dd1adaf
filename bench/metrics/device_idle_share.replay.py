"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, in %."""

from benchlib.trace_reduce import idle_share_percent as read  # noqa: F401
