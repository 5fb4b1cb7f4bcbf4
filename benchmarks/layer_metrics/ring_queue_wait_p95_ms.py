"""95th percentile of offer -> drawable (ingest.append's wait_ms, stamped in DeviceReplay.offer) over the window's appends: the ring's own part of episode_to_ring_p95_ms."""
from benchmarks.harness.program_spans import ring_queue_wait_p95_ms as read  # noqa: F401
