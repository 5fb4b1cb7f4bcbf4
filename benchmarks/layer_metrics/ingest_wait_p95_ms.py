"""95th percentile of due-to-drawable over the window's episodes: the steadier twin of episode_to_ring_p95_ms where that is not end to end."""
from benchmarks.harness.layers import ingest_wait_p95_ms as read  # noqa: F401
