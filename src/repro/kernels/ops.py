"""Public jit'd wrappers for the Pallas kernels.

`interpret_mode` is the one place that decides how a kernel runs: the
real Mosaic kernel on a TPU, the Pallas interpreter on the CPU backend
(correctness checks in tests), and an error on any other backend, so a
kernel never falls back silently.  `ref.py` holds the pure-jnp oracles;
tests sweep shapes/dtypes asserting allclose between the two.
"""
from __future__ import annotations

import functools

import jax

from .decode_attention import decode_attention as _decode_attention
from .flash_attention import flash_attention as _flash_attention
from .ralt_score import ralt_update as _ralt_update
from .ssd_scan import ssd_scan as _ssd_scan


def interpret_mode() -> bool:
    """True on the CPU backend, False on a TPU; other backends raise."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run on a TPU (or interpreted on "
                       f"the CPU backend), not on {backend!r}")


def _on_backend(kernel, *static_argnames):
    """`kernel` jitted with its static arguments, run as `interpret_mode`
    decides."""
    jitted = jax.jit(kernel, static_argnames=(*static_argnames, "interpret"))

    @functools.wraps(kernel)
    def op(*args, **kwargs):
        return jitted(*args, interpret=interpret_mode(), **kwargs)
    return op


flash_attention = _on_backend(_flash_attention, "causal", "window",
                              "block_q", "block_k", "kv_len")
decode_attention = _on_backend(_decode_attention, "block_s")
ralt_update = _on_backend(_ralt_update, "alpha", "block_n")
ssd_scan = _on_backend(_ssd_scan)
