"""A dense decoder (``model_type`` ``"tiny_dense"``), the harness's test of
a second architecture: pre-norm blocks of grouped-query attention with
rotary embeddings and a SwiGLU MLP, run through the program's dense path.

The harness's tests copy this file to ``bench/models/tiny_dense.py`` and its
reference to ``bench/reference/tiny_dense_lm.py``.  Weight layout (``L``
layers, leaves of a layer stacked on a leading layer axis)::

    embed      [V, D]
    layers     norm1, norm2 [L, D]
               attn  wq [L, D, H, dh]  wk, wv [L, D, Hkv, dh]  wo [L, H, dh, D]
               mlp   w_in, w_gate [L, D, F]  w_out [L, F, D]
    final_norm [D]
    lm_head    [D, V]
"""

from __future__ import annotations

from bench.lib import dims

REFERENCE = "tiny_dense_lm"
ZEROS = ("norm1", "norm2", "final_norm")


def shapes(c: dict) -> dict:
    d, v, f = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    h, hkv, dh = c["num_attention_heads"], c["num_key_value_heads"], dims.head_dim(c)
    n, wdt = c["num_hidden_layers"], c["torch_dtype"]
    return {
        "embed": ((v, d), wdt),
        "layers/norm1": ((n, d), wdt),
        "layers/attn/wq": ((n, d, h, dh), wdt),
        "layers/attn/wk": ((n, d, hkv, dh), wdt),
        "layers/attn/wv": ((n, d, hkv, dh), wdt),
        "layers/attn/wo": ((n, h, dh, d), wdt),
        "layers/norm2": ((n, d), wdt),
        "layers/mlp/w_in": ((n, d, f), wdt),
        "layers/mlp/w_gate": ((n, d, f), wdt),
        "layers/mlp/w_out": ((n, f, d), wdt),
        "final_norm": ((d,), wdt),
        "lm_head": ((d, v), wdt),
    }


def reference_view(c: dict) -> dict:
    return {
        "hidden_size": c["hidden_size"],
        "num_attention_heads": c["num_attention_heads"],
        "num_key_value_heads": c["num_key_value_heads"],
        "head_dim": dims.head_dim(c),
        "rope_theta": float(c["rope_theta"]),
        "rms_norm_eps": float(c["rms_norm_eps"]),
    }


def model_config(c: dict):
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=dims.head_dim(c), act=c["hidden_act"],
        dtype=c["torch_dtype"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), tie_embeddings=bool(c["tie_word_embeddings"]),
        remat=c["system"]["remat"], opt_moment_dtype=c["optimizer"]["moment_dtype"],
    )


def to_program(w: dict) -> dict:
    return {"embed": w["embed"], "blocks": {"0_global": w["layers"]},
            "final_norm": w["final_norm"], "lm_head": w["lm_head"]}


def canonical(paths: dict) -> dict:
    return {p.replace("blocks/0_global/", "layers/"): v for p, v in paths.items()}


def flops_per_token(c: dict, seq_len: int) -> float:
    """6 x the matrix parameters plus 6 x S x (heads x head size) per layer."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, hkv, dh = c["num_attention_heads"], c["num_key_value_heads"], dims.head_dim(c)
    layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    n = c["num_hidden_layers"]
    return 6.0 * (n * layer + d * v) + 6.0 * seq_len * h * dh * n
