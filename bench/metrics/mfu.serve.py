"""``mfu.serve``: the whole window's roofline share.  For each engine tick,
the least time the chip could take, max(FLOPs needed / peak FLOP/s, bytes
needed / peak HBM bytes/s), summed over the ticks in the window and divided
by the window, in percent.

FLOPs needed: for each token decoded, 2 x the active matrix parameters
(attention projections, router, top-k experts, output head) plus
4 x heads x head size per attended position per layer; for each prompt
admitted, 2 x the active layer parameters per prompt token, the output head
once, and 2 x heads x head size x p^2 per layer for causal attention.
Bytes needed: every held weight once per tick (with 64 live tokens, top-2
of 8 leaves an expert unused with probability 0.75^64, so every expert is
needed) plus the K/V read by the decoded tokens.  Counted from the
configuration's shapes and the harness's record of each tick.
"""


def _sizes(c):
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    layers, experts, k = c["num_hidden_layers"], c["num_local_experts"], c["num_experts_per_tok"]
    wbytes = 2 if c["torch_dtype"] == "bfloat16" else 4
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    active_layer = attn + d * experts + k * 3 * d * f
    held_bytes = (layers * ((attn + experts * 3 * d * f) * wbytes + d * experts * 4)
                  + d * v * wbytes)
    kv_bytes_per_position = layers * 2 * hkv * dh * wbytes
    return d, v, h * dh, layers, active_layer, held_bytes, kv_bytes_per_position


def tick_bounds(c: dict, live: int, context: int, prompts: list[int]) -> tuple[float, float]:
    """(FLOPs, bytes) one tick needs."""
    d, v, hd, layers, active_layer, held, kvb = _sizes(c)
    flops = live * 2.0 * (layers * active_layer + d * v) + 4.0 * hd * layers * context
    for p in prompts:
        flops += 2.0 * p * layers * active_layer + 2.0 * d * v + 2.0 * hd * layers * p * p
    return flops, float(held + kvb * context)


def read(rec):
    ticks = rec.ticks_in_window()
    if not ticks or not rec.peaks:
        return None
    least = 0.0
    for t in ticks:
        flops, nbytes = tick_bounds(rec.config, t.live, t.context_tokens, t.admitted_prompt_tokens)
        least += max(flops / rec.peaks["bf16_flops"], nbytes / rec.peaks["hbm_bytes_per_s"])
    t0, t1 = rec.window
    return 100.0 * least / (rec.chips * (t1 - t0))
