"""The part of window_starved_share that the trainer thread spent inside trainer.boundary or trainer.handoff, in points of the window."""
from benchmarks.harness.inflight import starved_in_boundary_share as read  # noqa: F401
