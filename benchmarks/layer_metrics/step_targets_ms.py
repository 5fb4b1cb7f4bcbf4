"""Device time of loss.targets + loss.terms, forward direction, per fused step (Trainer.step_profile). Read AFTER the timed window, with the trainer thread ended and no ingest running: 16 more steps on the live params and ring under a private trace, not the window's own steps."""
from benchmarks.harness.program_spans import step_targets_ms as read  # noqa: F401
