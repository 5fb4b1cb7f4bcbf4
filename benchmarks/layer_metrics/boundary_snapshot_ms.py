"""Mean boundary.snapshot per epoch boundary closed in the window: the params' copy to the host."""
from benchmarks.harness.program_spans import boundary_snapshot_ms as read  # noqa: F401
