"""Device time of the decode programs per decode step in the window."""

from lib.measure import decode_step_ms as read  # noqa: F401
