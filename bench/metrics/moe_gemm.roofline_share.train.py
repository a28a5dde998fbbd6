"""``moe_gemm.roofline_share.train``: the dropless grouped GEMM's forward
kernel (``kernels/grouped_matmul.py``) against its roofline.

For each call found in the device trace, the least time the chip could
take for what the call needs: the architecture's count (``call_bounds`` of
``bench/models/<model_type>.py``) of FLOPs and bytes, from the
configuration's shapes, never from the kernel's tiles or padding.  The
share is the sum of those least times over the sum of the calls' device
times, in percent.
"""

import pathlib
import re

from bench.lib import arch

ROOT = pathlib.Path(__file__).resolve().parents[2]
# The kernel's operations in a v5e trace: "%grouped_matmul_blocks_pallas.<n> = ..."
KERNEL = re.compile(r"^%grouped_matmul_blocks_pallas\b")


def read(rec):
    call_bounds = arch.count(rec.config, "call_bounds", ROOT)
    if rec.trace is None or not rec.peaks or call_bounds is None:
        return None
    calls = [e - s for d in rec.trace.devices for name, s, e in rec.trace.ops[d]
             if KERNEL.search(name)]
    if not calls:
        return None
    t = rec.traffic
    flops, nbytes = call_bounds(rec.config, t["seq_len"] * t["batch"])
    least = max(flops / rec.peaks["bf16_flops"], nbytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(calls) / (sum(calls) * 1e-9)
