"""Least time the chip could take for the delta-rule mixers of one fused step (the configuration's cost module, ``parts.delta`` + ``parts.delta_scan``: the seven projections, the three convolutions and the recurrence as the algorithm needs it) over step_delta_ms."""
from benchmarks.harness.delta_parts import delta_roofline as read  # noqa: F401
