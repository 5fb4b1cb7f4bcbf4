"""1 - union of device-operation intervals over the traced window, averaged over the chips."""
from benchmarks.harness.layers import device_idle_share as read  # noqa: F401
