"""Mean, per read_pages span, of the span's time during which the
device is idle: the tier manager's host work per read."""
from bench.lib.readers import host_ms_per_read as read  # noqa: F401
