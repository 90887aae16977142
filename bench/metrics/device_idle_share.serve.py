"""Share of the window in which no program ran on the device, in
percent: 1 - (union of device program intervals) / window."""

from lib.measure import idle_share as read  # noqa: F401
