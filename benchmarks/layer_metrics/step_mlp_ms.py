"""Device time a fused step spends under net.mlp (the dense layers' SwiGLU: three products and the gate between them, every dense layer held; forward, recomputation and transpose together), from Trainer.step_profile()'s ``scopes``: 16 steps after the window, as the six step_*_ms."""
from benchmarks.harness.delta_parts import step_mlp_ms as read  # noqa: F401
