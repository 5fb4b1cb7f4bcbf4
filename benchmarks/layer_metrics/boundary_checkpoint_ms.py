"""Mean boundary.checkpoint per epoch boundary closed in the window: save_train_state."""
from benchmarks.harness.program_spans import boundary_checkpoint_ms as read  # noqa: F401
