"""``train_tokens_per_s``: tokens of all steps completed in the window, over
the time from the first step's start to the last step's end (host clock;
every step ends after its metrics reached the host)."""


def read(rec):
    steps = rec.steps_in_window()
    if not steps:
        return None
    return sum(s.tokens for s in steps) / (steps[-1].end - steps[0].start)
