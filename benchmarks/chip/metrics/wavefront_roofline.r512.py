"""Share of the wavefront's device time at the bucket (512, 8192) that its
recurrence's bytes need at the chip's HBM peak, in % (device trace,
peaks.json)."""

from layers import wavefront_roofline as read  # noqa: F401
