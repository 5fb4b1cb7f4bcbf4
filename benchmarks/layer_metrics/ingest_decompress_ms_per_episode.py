"""Trainer-thread time inside the program's own ingest.decompress spans (unzip, unpickle, columnar build) per episode appended in the window."""
from benchmarks.harness.program_spans import ingest_decompress_ms_per_episode as read  # noqa: F401
