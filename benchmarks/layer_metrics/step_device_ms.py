"""Device time of one fused step: durations of the step program's XLA Modules events in the trace over their count."""
from benchmarks.harness.layers import step_device_ms as read  # noqa: F401
