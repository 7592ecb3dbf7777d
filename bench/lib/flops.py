"""Operations and bytes that the work needs, from shapes alone.

These count what the model or kernel requires, not what the current code
reads or pads: a later change to the code cannot move them.
"""
from __future__ import annotations

from .models import Spec


def matmul_flops_per_token(s: Spec) -> float:
    """Multiply-adds (x2) of every weight matrix one token passes
    through, the output head over the served vocabulary included."""
    d = s.d_model
    if s.family == "transformer":
        attn = d * s.head_dim * (s.n_heads + 2 * s.n_kv_heads) \
            + s.n_heads * s.head_dim * d
        per_layer = attn + 3 * d * s.d_ff
    else:
        di, ns, nh = s.d_inner, s.ssm_state, s.ssm_heads
        per_layer = d * (2 * di + 2 * ns + nh) + di * d
    return 2.0 * (s.n_layers * per_layer + d * s.vocab)


def mixer_flops(s: Spec, pos: int) -> float:
    """Sequence-mixing operations of the token at position `pos`:
    attention over the pos + 1 cached positions (scores and values), or
    the Mamba2 conv and state update and read-out."""
    if s.family == "transformer":
        return s.n_layers * 4.0 * s.n_heads * s.head_dim * (pos + 1)
    conv_dim = s.d_inner + 2 * s.ssm_state
    state = s.ssm_heads * s.ssm_state * s.ssm_head_dim
    # conv: K multiply-adds per channel; state: decay (1), outer product
    # (2), read-out (2) per element
    return s.n_layers * (2.0 * conv_dim * s.ssm_conv + 5.0 * state)


def sequence_flops(s: Spec, n_tokens: int) -> float:
    """Operations for feeding positions 0 .. n_tokens - 1 of one
    sequence."""
    mm = matmul_flops_per_token(s) * n_tokens
    if s.family == "transformer":
        # sum over pos of (pos + 1) = n (n + 1) / 2
        return mm + s.n_layers * 4.0 * s.n_heads * s.head_dim \
            * n_tokens * (n_tokens + 1) / 2
    return mm + mixer_flops(s, 0) * n_tokens


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
