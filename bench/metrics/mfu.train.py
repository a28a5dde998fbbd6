"""``mfu.train``: model FLOPs per token x tokens per second of the traced
window / (chips x bf16 peak), in percent.

Model FLOPs per token are the architecture's count (``flops_per_token`` of
``bench/models/<model_type>.py``), from the configuration's shapes;
recomputation is not counted.
"""

import pathlib

from bench.lib import arch

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(rec):
    steps = rec.steps_in_window()
    flops_per_token = arch.count(rec.config, "flops_per_token", ROOT)
    if not steps or not rec.peaks or flops_per_token is None:
        return None
    rate = sum(s.tokens for s in steps) / (steps[-1].end - steps[0].start)
    fpt = flops_per_token(rec.config, rec.traffic["seq_len"])
    return 100.0 * fpt * rate / (rec.chips * rec.peaks["bf16_flops"])
