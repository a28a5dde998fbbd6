"""``serve.decode_tick_ms``: median duration of the program's ``serve.tick``
spans (``repro.obs.trace``, host clock, closed after the tick's tokens
reached the host) of the ticks that admitted no request."""

import numpy as np


def read(rec):
    if not rec.spans:
        return None
    ticks = [e["dur"] * 1e-3 for e in rec.spans
             if e["name"] == "serve.tick" and e["args"].get("admitted") == 0]
    return float(np.median(ticks)) if ticks else None
