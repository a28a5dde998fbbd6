"""Mixtral (``model_type`` ``"mixtral"``): a decoder of pre-norm blocks,
grouped-query attention with rotary embeddings, and top-k routed SwiGLU
experts (Jiang et al., 2024, "Mixtral of Experts"; the published
``config.json``).

Weight layout (``L`` layers, ``E`` experts held, leaves of a layer stacked on
a leading layer axis)::

    embed      [V, D]          multiplied by sqrt(D) on lookup
    layers     norm1 [L, D], norm2 [L, D]           gain applied as 1 + w
               attn  wq [L, D, H, dh]  wk, wv [L, D, Hkv, dh]  wo [L, H, dh, D]
               moe   router [L, D, E] (float32)
                     w_in, w_gate [L, E, D, F]  w_out [L, E, F, D]
    final_norm [D]
    lm_head    [D, V]

The program holds the layers as one scanned block kind (``blocks/0_global``).
Its plain reference is ``bench/reference/moe_lm.py``.
"""

from __future__ import annotations

from bench.lib import dims

REFERENCE = "moe_lm"
# Leaves that start at zero (norm gains, applied as 1 + w).
ZEROS = ("norm1", "norm2", "final_norm")
# The program's one scanned block kind, which holds every layer.
_BLOCK = "0_global"


def shapes(c: dict) -> dict:
    """``{leaf path: (shape, dtype name)}`` in the layout above."""
    d, v, f = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    h, hkv, dh = c["num_attention_heads"], c["num_key_value_heads"], dims.head_dim(c)
    n, e = c["num_hidden_layers"], c["num_local_experts"]
    wdt = c["torch_dtype"]
    return {
        "embed": ((v, d), wdt),
        "layers/norm1": ((n, d), wdt),
        "layers/attn/wq": ((n, d, h, dh), wdt),
        "layers/attn/wk": ((n, d, hkv, dh), wdt),
        "layers/attn/wv": ((n, d, hkv, dh), wdt),
        "layers/attn/wo": ((n, h, dh, d), wdt),
        "layers/norm2": ((n, d), wdt),
        "layers/moe/router": ((n, d, e), "float32"),
        "layers/moe/w_in": ((n, e, d, f), wdt),
        "layers/moe/w_gate": ((n, e, d, f), wdt),
        "layers/moe/w_out": ((n, e, f, d), wdt),
        "final_norm": ((d,), wdt),
        "lm_head": ((d, v), wdt),
    }


def reference_view(c: dict) -> dict:
    """The keys the plain reference reads, with derived sizes filled in."""
    return {
        "hidden_size": c["hidden_size"],
        "num_attention_heads": c["num_attention_heads"],
        "num_key_value_heads": c["num_key_value_heads"],
        "head_dim": dims.head_dim(c),
        "num_experts_per_tok": c["num_experts_per_tok"],
        "rope_theta": float(c["rope_theta"]),
        "rms_norm_eps": float(c["rms_norm_eps"]),
    }


def model_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``.

    The router's auxiliary losses stay out of the objective: the source
    config sets ``output_router_logits`` false, so its
    ``router_aux_loss_coef`` is not applied, and Mixtral has no z-loss."""
    from repro.models.config import ModelConfig, MoEConfig

    s = c["system"]
    return ModelConfig(
        name=c["name"],
        family="moe",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        head_dim=dims.head_dim(c),
        moe=MoEConfig(
            num_experts=c["num_local_experts"],
            top_k=c["num_experts_per_tok"],
            d_ff=c["intermediate_size"],
            backend=s["moe_backend"],
            dispatch=s["dispatch"],
            decode_backend=s["decode_backend"],
            balance_loss=c["router_aux_loss_coef"] if c["output_router_logits"] else 0.0,
            router_z_loss=0.0,
        ),
        act=c["hidden_act"],
        dtype=c["torch_dtype"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        remat=s["remat"],
        opt_moment_dtype=c.get("optimizer", {}).get("moment_dtype", "float32"),
    )


def to_program(w: dict) -> dict:
    """The bench's weight tree in the program's parameter layout (the same
    leaves, nested as ``init_model`` nests them: one scanned block kind)."""
    return {
        "embed": w["embed"],
        "blocks": {_BLOCK: w["layers"]},
        "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
    }


def canonical(paths: dict[str, float]) -> dict[str, float]:
    """Program leaf paths in the bench's weight layout."""
    return {p.replace(f"blocks/{_BLOCK}/", "layers/"): v for p, v in paths.items()}


def flops_per_token(c: dict, seq_len: int) -> float:
    """Model FLOPs per trained token: 6 x the active matrix parameters
    (attention projections, router, the top-k experts' three matrices,
    output head) plus 6 x S x (heads x head size) per layer for causal
    attention; recomputation is not counted."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    layers, experts, k = c["num_hidden_layers"], c["num_local_experts"], c["num_experts_per_tok"]
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    per_layer = attn + d * experts + k * 3 * d * f
    return 6.0 * (layers * per_layer + d * v) + 6.0 * seq_len * h * dh * layers


def call_bounds(c: dict, tokens: int) -> tuple[float, float]:
    """(FLOPs, bytes) one call of the dropless grouped GEMM's forward kernel
    needs in a step of ``tokens`` tokens: FLOPs 2 x (T x k) x D x F on the
    T x k useful rows, bytes for each held expert's weights read once plus
    the rows read and written (bf16).  Every call of the train step has the
    same need (the w_in and w_gate calls map D to F, the w_out call F to
    D).  Counted from the configuration's shapes, never from the kernel's
    tiles or padding."""
    d, f = c["hidden_size"], c["intermediate_size"]
    rows = tokens * c["num_experts_per_tok"]
    experts = c["num_local_experts"]
    flops = 2.0 * rows * d * f
    nbytes = 2.0 * (experts * d * f + rows * (d + f))
    return flops, nbytes


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
    """(FLOPs, bytes) one serving tick needs.

    FLOPs: for each token decoded, 2 x the active matrix parameters
    (attention projections, router, top-k experts, output head) plus
    4 x heads x head size per attended position per layer; for each prompt
    admitted, 2 x the active layer parameters per prompt token, the output
    head once, and 2 x heads x head size x p^2 per layer for causal
    attention.  Bytes: every held weight once per tick (with 64 live tokens,
    top-2 of 8 leaves an expert unused with probability 0.75^64, so every
    expert is needed) plus the K/V read by the decoded tokens."""
    d, v, hd, layers, active_layer, held, kvb = _sizes(c)
    flops = live * 2.0 * (layers * active_layer + d * v) + 4.0 * hd * layers * context
    for p in prompts:
        flops += 2.0 * p * layers * active_layer + 2.0 * d * v + 2.0 * hd * layers * p * p
    return flops, float(held + kvb * context)
