"""95th percentile of the gap between consecutive output tokens of a
request, over every gap whose later token reached the host in the window."""

import numpy as np


def read(rec):
    t_open, t_close = rec["t_open"], rec["t_close"]
    gaps = [b - a for s in rec["served"].values()
            for a, b in zip(s.times, s.times[1:]) if t_open <= b < t_close]
    return float(np.percentile(gaps, 95) * 1e3) if gaps else None
