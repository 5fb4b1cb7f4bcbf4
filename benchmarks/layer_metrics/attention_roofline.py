"""Least time the chip could take for the attention part of one fused step (its operations and bytes from the configuration's cost module, ``parts.attention``, through harness/roofline.py's peaks) over step_attention_ms."""
from benchmarks.harness.sequence_parts import attention_roofline as read  # noqa: F401
