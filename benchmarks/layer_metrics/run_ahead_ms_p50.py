"""Median depth of the window's trainer.update spans times the window's seconds per finished step: the device work a boundary's drain will wait out, and the age of the parameters a snapshot taken now would have."""
from benchmarks.harness.inflight import run_ahead_ms_p50 as read  # noqa: F401
