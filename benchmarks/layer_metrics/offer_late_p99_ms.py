"""How late the fed generator ran: 99th percentile of (sent - due) over the window's offers, from the generator's own clock."""
from benchmarks.harness.layers import offer_late_p99_ms as read  # noqa: F401
