"""Mean boundary.drain per epoch boundary closed in the window: the one device_get that waits for every queued step."""
from benchmarks.harness.program_spans import boundary_drain_ms as read  # noqa: F401
