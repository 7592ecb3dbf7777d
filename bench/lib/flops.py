"""Operations and bytes that the work needs, from shapes alone.

These count what the model or kernel requires, not what the current code
reads or pads: a later change to the code cannot move them.  A model's
counts belong to its family (bench/families/<family>.py); what is here
belongs to none.
"""
from __future__ import annotations

# The RALT update (kernels/ralt_score.py) per tracked unit: reads an int32
# tick, an f32 score and an int8 hit; writes the tick, the score and an
# int8 hot flag.  Operations: the tick difference, the decay exponent
# (one multiply and one exp), the decayed score, the hit add and the
# threshold compare.
RALT_BYTES_PER_UNIT = 4 + 4 + 1 + 4 + 4 + 1
RALT_FLOPS_PER_UNIT = 6


def ralt_update_cost(n_units: int):
    """(flops, bytes) of one RALT update over n_units."""
    return RALT_FLOPS_PER_UNIT * n_units, RALT_BYTES_PER_UNIT * n_units


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time on the chip: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
