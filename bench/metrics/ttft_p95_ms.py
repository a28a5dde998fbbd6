"""``ttft_p95_ms``: 95th percentile, over every request the sessions sent in
the window, of the time from its sending to its first token reaching the
host (wall time; requests sent late in the window are waited for)."""

import numpy as np


def read(rec):
    t0, t1 = rec.window
    waits = [(r.token_times[0] - r.send) * 1e3 for r in rec.requests
             if t0 <= r.send < t1 and r.token_times]
    return float(np.percentile(waits, 95)) if waits else None
