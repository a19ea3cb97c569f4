"""90th percentile of time to first token over every request due in the
window, from its scheduled arrival to its first token on the host.  A
request that never got one counts as the longest wait the run allowed."""

import numpy as np


def read(rec):
    if rec["kind"] != "serve_open":
        return None
    t_open, t_close = rec["t_open"], rec["t_close"]
    waits = [(s.times[0] if s.times else rec["t_end"]) - s.due
             for s in rec["served"].values() if t_open <= s.due < t_close]
    return float(np.percentile(waits, 90)) if waits else None
