"""Share of the window in which the device had no step in flight: seconds of the program's device.starved spans (the trainer thread's in-flight ledger) clipped to the window, over the window. A lower bound: the note window_starved_share_upper is the same with each span widened by its since_ms."""
from benchmarks.harness.inflight import window_starved_share as read  # noqa: F401
