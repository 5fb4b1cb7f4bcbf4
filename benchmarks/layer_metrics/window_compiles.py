"""Programs lowered inside the window (jax.monitoring events); a run with any fails instead of reporting."""
from benchmarks.harness.layers import window_compiles as read  # noqa: F401
