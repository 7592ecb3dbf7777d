"""The RALT update kernel's share of its roofline: the least time (the
larger of its operations over the bf16 peak and its bytes over the HBM
peak, counted from the tracker's shapes) over its device time."""
from bench.lib.readers import ralt_roofline_pct as read  # noqa: F401
