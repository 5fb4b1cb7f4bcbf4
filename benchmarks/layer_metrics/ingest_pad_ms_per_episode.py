"""Trainer-thread time inside the program's own ingest.pad spans (_pad_episode, the concatenations, the index build) per episode appended in the window."""
from benchmarks.harness.program_spans import ingest_pad_ms_per_episode as read  # noqa: F401
