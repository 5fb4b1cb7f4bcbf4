"""Trainer-thread time inside the program's own ingest.append spans (the scatter's dispatch and the upload of the run) per episode appended in the window."""
from benchmarks.harness.program_spans import ingest_append_ms_per_episode as read  # noqa: F401
