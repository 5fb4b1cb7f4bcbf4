"""95th percentile of due-on-the-control-plane to drawable over the window's episodes (~756 a window in geister.fed): freshness, end to end until PR 45, where its spread (6-9%) could not be told from a change under any bound the contract admits; the pairing is ingest_wait_p95_ms's."""
from benchmarks.harness.layers import ingest_wait_p95_ms as read  # noqa: F401
