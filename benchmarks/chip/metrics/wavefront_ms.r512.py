"""Wavefront kernel device time per decision at the bucket (512, 8192), in
ms (device trace)."""

from layers import wavefront_ms as read  # noqa: F401
