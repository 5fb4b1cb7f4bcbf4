"""The part of window_starved_share that the trainer thread spent inside trainer.ingest (device.starved split by overlap with the thread's top-level spans), in points of the window."""
from benchmarks.harness.inflight import starved_in_ingest_share as read  # noqa: F401
