"""Share of the window the trainer thread spent in DeviceReplay.ingest calls that appended something."""
from benchmarks.harness.layers import ingest_thread_share as read  # noqa: F401
