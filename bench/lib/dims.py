"""Sizes derived from a configuration file's published keys."""

from __future__ import annotations


def head_dim(c: dict) -> int:
    """Per-head width: the file's ``head_dim`` where it states one, else
    ``hidden_size / num_attention_heads`` as the published Mixtral config
    implies."""
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])
