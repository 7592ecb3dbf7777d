"""Whole-read share of the chip's peak on the tier path: the least time
each read's work needs (its pages read once from HBM, and the RALT update
over every tracked unit; operations at the bf16 peak or bytes at the HBM
peak, whichever takes longer), over the traced stretch."""
from bench.lib.readers import kv_mfu_pct as read  # noqa: F401
