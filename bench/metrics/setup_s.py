"""``setup_s``: process start to window open, compiles included (host clock)."""


def read(rec):
    return rec.setup_s
