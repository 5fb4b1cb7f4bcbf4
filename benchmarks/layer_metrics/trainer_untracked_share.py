"""100 minus the window's shares of trainer.ingest, trainer.boundary, trainer.handoff and trainer.update on the trainer thread: what its own spans do not account for."""
from benchmarks.harness.program_spans import trainer_untracked_share as read  # noqa: F401
