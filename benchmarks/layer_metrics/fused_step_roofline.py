"""Least time the chip could take for one fused step (operations and bytes counted from shapes, harness/roofline.py) over step_device_ms."""
from benchmarks.harness.layers import fused_step_roofline as read  # noqa: F401
