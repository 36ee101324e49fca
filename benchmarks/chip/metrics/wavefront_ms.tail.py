"""Wavefront kernel device time per decision at the tail bucket, in ms
(device trace)."""

from layers import wavefront_ms as read  # noqa: F401
