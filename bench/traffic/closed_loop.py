"""Serving traffic: a closed loop of sessions that each wait for their reply.

Each of ``sessions`` callers sends a request, waits until its last token has
reached the host, and sends the next one at once, as agents and reasoning
callers do.

Lengths follow bounded Zipf laws, ``p(k) ∝ (k - lo + 1)^-a`` over ``[lo,
hi]`` (the law of the repository's ``src/repro/serve/workload.py``
``_bounded_zipf``).  Prompt lengths are rounded up to a multiple of
``prompt_round``.  The requests form one fixed cycle of ``pool`` (prompt,
output) pairs: the i-th pair takes the laws' quantiles at the i-th point of
the R2 low-discrepancy sequence, so every stretch of the cycle holds nearly
the same mix of lengths.  Every seed sends the same lengths in the same
order, so the window of every seed holds the same work; ``--seed`` draws the
prompt tokens (uniform over the vocabulary) and the weights.

Parameters: ``sessions``, ``slots``, ``pool``,
``prompt_min``/``prompt_max``/``prompt_zipf_a``/``prompt_round``,
``out_min``/``out_max``/``out_zipf_a``, ``fill_ticks`` (engine ticks the
loop runs after its first burst of admissions, before the window opens) and
``trace_s`` (length of the traced window).  ``reduced`` lists the
parameters cut from the mix the cell stands for, ``reduced_why`` says why.
"""

from __future__ import annotations

import numpy as np

DRIVER = "serve"


def bounded_zipf_quantile(u, a: float, lo: int, hi: int):
    """The bounded Zipf law's quantiles at ``u`` in [0, 1)."""
    support = np.arange(lo, hi + 1)
    p = (support - lo + 1.0) ** -a
    cdf = np.cumsum(p / p.sum())
    return support[np.minimum(np.searchsorted(cdf, u, side="right"), len(support) - 1)]


def r2_points(n: int) -> np.ndarray:
    """``[n, 2]`` points of the R2 sequence (Roberts, 2018), the plastic
    number's powers as the two steps."""
    g = 1.32471795724474602596
    alpha = np.array([1.0 / g, 1.0 / g**2])
    return (0.5 + np.arange(n)[:, None] * alpha) % 1.0


def round_up(n, m: int):
    return -(-np.asarray(n) // m) * m


def prompt_lengths(t: dict) -> list[int]:
    """Every prompt length the mix can send (the shapes set-up warms)."""
    lo = int(round_up(t["prompt_min"], t["prompt_round"]))
    hi = int(round_up(t["prompt_max"], t["prompt_round"]))
    return list(range(lo, hi + 1, t["prompt_round"]))


def max_len(t: dict) -> int:
    return int(round_up(t["prompt_max"], t["prompt_round"])) + int(t["out_max"])


def requests(t: dict, config: dict, seed: int) -> list[tuple[np.ndarray, int]]:
    """The cycle of ``(prompt tokens, output length)`` the sessions send in
    turn; ``seed`` draws the tokens."""
    n = int(t["pool"])
    u = r2_points(n)
    prompts = round_up(
        bounded_zipf_quantile(u[:, 0], t["prompt_zipf_a"], t["prompt_min"], t["prompt_max"]),
        t["prompt_round"],
    )
    outs = bounded_zipf_quantile(u[:, 1], t["out_zipf_a"], t["out_min"], t["out_max"])
    rng = np.random.default_rng(int(seed))
    return [
        (rng.integers(0, config["vocab_size"], size=int(prompts[i]), dtype=np.int32),
         int(outs[i]))
        for i in range(n)
    ]
