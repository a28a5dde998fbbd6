"""Sizes derived from a configuration file's published keys."""

from __future__ import annotations


def head_dim(c: dict) -> int:
    """Per-head width: the file's ``head_dim`` where it states one, else
    ``hidden_size / num_attention_heads`` as the published Mixtral config
    implies."""
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def reference_view(c: dict) -> dict:
    """The keys the plain reference reads, with derived sizes filled in."""
    return {
        "hidden_size": c["hidden_size"],
        "num_attention_heads": c["num_attention_heads"],
        "num_key_value_heads": c["num_key_value_heads"],
        "head_dim": head_dim(c),
        "num_experts_per_tok": c["num_experts_per_tok"],
        "rope_theta": float(c["rope_theta"]),
        "rms_norm_eps": float(c["rms_norm_eps"]),
    }
