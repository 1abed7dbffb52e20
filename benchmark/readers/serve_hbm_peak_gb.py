"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, before the reference runs."""
from benchmark.readers._common import hbm_peak_gb as read  # noqa: F401
