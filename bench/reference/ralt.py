"""Plain reference of the RALT score update (HotRAP section 3.2):

    score' = alpha ** (now - tick) * score + hit,    tick' = now

for every tracked unit, in float64 with numpy.  `dtype` rounds the
scores to a lower precision after each operation (the control)."""
from __future__ import annotations

import numpy as np


def ralt_update(ticks, scores, hits, now, alpha, dtype=np.float64):
    ticks = np.asarray(ticks, np.int64)
    s = np.asarray(scores).astype(dtype)
    decay = np.power(np.float64(alpha),
                     (int(now) - ticks).astype(np.float64)).astype(dtype)
    new = (s * decay).astype(dtype) + np.asarray(hits).astype(dtype)
    return np.full_like(ticks, int(now)), new.astype(dtype)


def score_error(got, want) -> float:
    """Largest gap between two score tables, over the larger of each
    unit's reference magnitude and the median magnitude of the table
    (scores that have decayed to nearly nothing are held to the table's
    scale, not their own)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), max(np.median(np.abs(want)), 1e-30))
    return float(np.max(np.abs(got - want) / scale))
