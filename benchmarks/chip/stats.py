"""Percentiles of latency samples (linear interpolation, as numpy's
``percentile``; an empty sample has none)."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    return float(np.percentile(np.asarray(list(xs)), q)) if len(xs) else None
