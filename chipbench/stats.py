"""Percentiles and spreads, one definition for every reader."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def pct(values: Sequence[float], q: float) -> Optional[float]:
    """``q``-th percentile (linear interpolation); None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
