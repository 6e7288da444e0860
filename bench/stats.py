"""Statistics over every sample of a window (never medians of chunks)."""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) over all `values`, linear between the
    two nearest ranks; None for no values."""
    a = np.asarray(values, np.float64)
    if a.size == 0:
        return None
    return float(np.percentile(a, q))


def rate(count: float, seconds: float) -> float:
    """`count` over the whole window's `seconds`."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return float(count) / float(seconds)
