"""Device-idle time inside the engine's ticks: idle ns overlapping the
``serve.step`` host spans of the window, per ``serve.step`` span that
starts in it, in ms."""

from lib.enginespans import host_idle_ms as read  # noqa: F401
