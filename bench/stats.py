"""Window statistics that several metric readers share."""
from __future__ import annotations

from typing import Optional

import numpy as np


def tokens_in_window(run) -> int:
    return sum(1 for r in run.records for t in r.times
               if run.t_open <= t <= run.t_close)


def window_s(run) -> float:
    return run.t_close - run.t_open


def percentile(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else None


def window_steps(run):
    return [s for s in run.steps if run.t_open <= s.t_start <= run.t_close]

