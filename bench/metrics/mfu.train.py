"""``mfu.train``: model FLOPs per token x tokens per second of the traced
window / (chips x bf16 peak), in percent.

Model FLOPs per token are 6 x the active matrix parameters (attention
projections, router, the top-k experts' three matrices, output head) plus
6 x S x (heads x head size) per layer for causal attention; recomputation
is not counted.  Counted from the configuration's shapes.
"""


def flops_per_token(c: dict, seq_len: int) -> float:
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    layers, experts, k = c["num_hidden_layers"], c["num_local_experts"], c["num_experts_per_tok"]
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    per_layer = attn + d * experts + k * 3 * d * f
    return 6.0 * (layers * per_layer + d * v) + 6.0 * seq_len * h * dh * layers


def read(rec):
    steps = rec.steps_in_window()
    if not steps or not rec.peaks:
        return None
    rate = sum(s.tokens for s in steps) / (steps[-1].end - steps[0].start)
    fpt = flops_per_token(rec.config, rec.traffic["seq_len"])
    return 100.0 * fpt * rate / (rec.chips * rec.peaks["bf16_flops"])
