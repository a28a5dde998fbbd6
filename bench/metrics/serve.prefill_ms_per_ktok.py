"""``serve.prefill_ms_per_ktok``: over the ticks that admitted requests, the
sum of (``serve.tick`` span duration - ``serve.decode_tick_ms``) per 1000
prompt tokens admitted (program spans; prompt lengths from the harness's
own record of each tick)."""

import numpy as np


def read(rec):
    if not rec.spans:
        return None
    ticks = [e for e in rec.spans if e["name"] == "serve.tick"]
    plain = [e["dur"] * 1e-3 for e in ticks if e["args"].get("admitted") == 0]
    if not plain:
        return None
    decode_ms = float(np.median(plain))
    prompts = {t.index: sum(t.admitted_prompt_tokens) for t in rec.ticks}
    extra, tokens = 0.0, 0
    for e in ticks:
        n = prompts.get(e["args"].get("tick"), 0)
        if e["args"].get("admitted", 0) > 0 and n:
            extra += e["dur"] * 1e-3 - decode_ms
            tokens += n
    return 1000.0 * extra / tokens if tokens else None
