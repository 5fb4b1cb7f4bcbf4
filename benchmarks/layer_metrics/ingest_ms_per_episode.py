"""Host wall of DeviceReplay.ingest on the trainer thread per episode appended in the window."""
from benchmarks.harness.layers import ingest_ms_per_episode as read  # noqa: F401
