"""Device time a fused step spends in the net's attention scopes (net.attention.window + net.attention.full: projections, scores, gate; forward, recomputation and transpose together), from Trainer.step_profile()'s ``scopes``: 16 steps after the window, as the six step_*_ms."""
from benchmarks.harness.sequence_parts import step_attention_ms as read  # noqa: F401
