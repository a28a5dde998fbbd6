"""``serve_tokens_per_s``: output tokens that reached the host in the window,
over the window (host clock)."""


def read(rec):
    t0, t1 = rec.window
    n = sum(1 for r in rec.requests for t in r.token_times if t0 < t <= t1)
    return n / (t1 - t0) if n else None
