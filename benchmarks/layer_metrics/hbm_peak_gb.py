"""memory_stats()['peak_bytes_in_use'] of the fullest chip at the window's close."""
from benchmarks.harness.layers import hbm_peak_gb as read  # noqa: F401
