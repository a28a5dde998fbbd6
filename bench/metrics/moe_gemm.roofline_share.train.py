"""``moe_gemm.roofline_share.train``: the dropless grouped GEMM's forward
kernel (``kernels/grouped_matmul.py``) against its roofline.

For each call found in the device trace, the least time the chip could
take for what the call needs: FLOPs 2 x (T x k) x D x F on the T x k useful
rows, bytes for each hit expert's weights read once plus the rows read and
written (bf16).  Every call of the train step has the same need (the w_in
and w_gate calls map D to F, the w_out call F to D).  The share is the sum
of those least times over the sum of the calls' device times, in percent.
At Mixtral widths the FLOPs bound applies.  Counted from the configuration's
shapes, never from the kernel's tiles or padding.
"""

import re

# The kernel's operations in a v5e trace: "%grouped_matmul_blocks_pallas.<n> = ..."
KERNEL = re.compile(r"^%grouped_matmul_blocks_pallas\b")


def call_bounds(c: dict, tokens: int) -> tuple[float, float]:
    d, f = c["hidden_size"], c["intermediate_size"]
    rows = tokens * c["num_experts_per_tok"]
    experts = c["num_local_experts"]
    flops = 2.0 * rows * d * f
    nbytes = 2.0 * (experts * d * f + rows * (d + f))
    return flops, nbytes


def read(rec):
    if rec.trace is None or not rec.peaks:
        return None
    calls = [e - s for d in rec.trace.devices for name, s, e in rec.trace.ops[d]
             if KERNEL.search(name)]
    if not calls:
        return None
    t = rec.traffic
    flops, nbytes = call_bounds(rec.config, t["seq_len"] * t["batch"])
    least = max(flops / rec.peaks["bf16_flops"], nbytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(calls) / (sum(calls) * 1e-9)
