"""Device busy time per sweep: the union of the intervals in which an
operation ran on the chip (profiler trace), in ms."""

from chipbench.readers import device_busy_ms_per_step as read  # noqa: F401
