"""10th percentile of the depth attr of the trainer.update spans closed in the window: steps in flight when a dispatch began, at the moments the queue came nearest to running dry."""
from benchmarks.harness.inflight import run_ahead_steps_p10 as read  # noqa: F401
