"""Median duration of the log's trainer.update spans (set-up's too) that the runtime's queue cannot have held and that did not find it nearly empty: entered at a depth between an eighth and a half of the deepest queue the log saw. None under 20 such spans; the notes carry the medians of the other two classes."""
from benchmarks.harness.inflight import dispatch_ms_per_step as read  # noqa: F401
