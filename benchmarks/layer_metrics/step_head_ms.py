"""Device time a fused step spends under net.head (final norm, value head, the policy head's logits a chunk of positions at a time with log-probability and entropy; forward, recomputation and transpose together), from Trainer.step_profile()'s ``scopes``."""
from benchmarks.harness.sequence_parts import step_head_ms as read  # noqa: F401
