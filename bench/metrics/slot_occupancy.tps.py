"""Useful token-steps (a live slot fed a prompt or output token) over
engine steps times the batch: what lockstep padding leaves of the batch."""
from bench.lib.readers import slot_occupancy_pct as read  # noqa: F401
