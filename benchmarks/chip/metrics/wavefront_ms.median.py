"""Wavefront kernel device time per decision, in ms (device trace)."""

from layers import wavefront_ms as read  # noqa: F401
