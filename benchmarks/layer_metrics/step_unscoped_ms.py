"""What no scope claims of a fused step's device time: unnamed ops and the device's gaps between ops (Trainer.step_profile). Read AFTER the timed window, with the trainer thread ended and no ingest running: 16 more steps on the live params and ring under a private trace, not the window's own steps."""
from benchmarks.harness.program_spans import step_unscoped_ms as read  # noqa: F401
