"""Device time of the optimizer scope (Adam, apply, grad norm, target refresh) per fused step (Trainer.step_profile). Read AFTER the timed window, with the trainer thread ended and no ingest running: 16 more steps on the live params and ring under a private trace, not the window's own steps."""
from benchmarks.harness.program_spans import step_optimizer_ms as read  # noqa: F401
