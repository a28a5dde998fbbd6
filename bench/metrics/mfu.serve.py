"""``mfu.serve``: the whole window's roofline share.  For each engine tick,
the least time the chip could take, max(FLOPs needed / peak FLOP/s, bytes
needed / peak HBM bytes/s), summed over the ticks in the window and divided
by the window, in percent.

What a tick needs is the architecture's count (``tick_bounds`` of
``bench/models/<model_type>.py``), from the configuration's shapes and the
harness's record of the tick: the tokens decoded, the positions they
attended over, and the prompts admitted.
"""

import pathlib

from bench.lib import arch

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(rec):
    ticks = rec.ticks_in_window()
    tick_bounds = arch.count(rec.config, "tick_bounds", ROOT)
    if not ticks or not rec.peaks or tick_bounds is None:
        return None
    least = 0.0
    for t in ticks:
        flops, nbytes = tick_bounds(rec.config, t.live, t.context_tokens, t.admitted_prompt_tokens)
        least += max(flops / rec.peaks["bf16_flops"], nbytes / rec.peaks["hbm_bytes_per_s"])
    t0, t1 = rec.window
    return 100.0 * least / (rec.chips * (t1 - t0))
