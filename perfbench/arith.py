"""Window arithmetic that several metric readers share."""
from __future__ import annotations

from typing import List

import numpy as np


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile by linear interpolation between ranks
    (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_steps(obs) -> list:
    """Engine steps that started inside the window."""
    return [s for s in obs.steps if obs.t0 <= s.start < obs.t1]
