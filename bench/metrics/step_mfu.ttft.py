"""Whole-step share of the chip's bf16 peak: the operations the model
needs for the tokens the traced stretch's waves fed, over the
stretch."""
from bench.lib.readers import serving_mfu_pct as read  # noqa: F401
