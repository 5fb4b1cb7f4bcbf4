"""Share of the window the trainer thread spent in Trainer.train's tail (metrics fetch, snapshot, train-state checkpoint): harness span."""
from benchmarks.harness.layers import boundary_thread_share as read  # noqa: F401
