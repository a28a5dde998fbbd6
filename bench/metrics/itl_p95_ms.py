"""``itl_p95_ms``: 95th percentile of every gap between consecutive tokens of
every request, whose later token reached the host in the window (wall
time).  Tokens that arrive together count a gap of 0."""

import numpy as np


def read(rec):
    t0, t1 = rec.window
    gaps = [(b - a) * 1e3 for r in rec.requests
            for a, b in zip(r.token_times, r.token_times[1:]) if t0 < b <= t1]
    return float(np.percentile(gaps, 95)) if gaps else None
