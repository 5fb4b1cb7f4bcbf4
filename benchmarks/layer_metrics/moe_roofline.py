"""Least time the chip could take for the expert layers' part of one fused step (``parts.moe`` of the configuration's cost module: router, shared expert, the held picks by expectation) over step_moe_ms."""
from benchmarks.harness.sequence_parts import moe_roofline as read  # noqa: F401
