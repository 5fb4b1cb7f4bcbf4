"""Share of the window the trainer thread stood in the runtime's queue: over the window's trainer.update and ingest.append spans that entered over half the deepest queue the log saw, each one's duration beyond the median of its kind's unheld calls (entered at half the deepest or under)."""
from benchmarks.harness.inflight import queue_wait_thread_share as read  # noqa: F401
