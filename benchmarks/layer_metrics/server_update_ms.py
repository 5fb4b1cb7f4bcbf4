"""Mean learner.update per epoch boundary closed in the window: how long the server thread takes no episode in."""
from benchmarks.harness.program_spans import server_update_ms as read  # noqa: F401
