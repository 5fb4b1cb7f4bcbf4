"""Device time a fused step spends in the expert layers' scopes (net.moe.route + net.moe.experts + net.moe.shared; forward, recomputation and transpose together), from Trainer.step_profile()'s ``scopes``."""
from benchmarks.harness.sequence_parts import step_moe_ms as read  # noqa: F401
