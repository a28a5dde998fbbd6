"""``device.idle_share.train``: share of the traced window in which no operation ran on the
device, 1 - (union of device-op intervals / window), averaged over the
chips (profiler trace)."""


def read(rec):
    if rec.trace is None:
        return None
    share = rec.trace.idle_share()
    return None if share is None else 100.0 * share
