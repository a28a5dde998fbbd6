"""Plain float32 reference of the ``tiny_dense`` decoder: the Mixtral
reference's norm, rotary embedding and attention (``bench/reference/moe_lm.py``)
with a SwiGLU MLP ``w_out(silu(x w_gate) * (x w_in))`` in place of the
experts.  ``precision="fp8"`` rounds as that reference does.  Nothing here
imports the system under test."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import moe_lm

logits = moe_lm.logits


def features(params, tokens, cfg, precision="f32"):
    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    keep = moe_lm._fp8 if precision == "fp8" else (lambda v: v)
    x = keep(jnp.take(params["embed"], tokens, axis=0).astype(moe_lm.F32) * math.sqrt(d))

    def block(x, layer):
        x = keep(x + moe_lm.attention(layer["attn"], moe_lm.rms_norm(x, layer["norm1"], eps),
                                      cfg, precision))
        h, p = moe_lm.rms_norm(x, layer["norm2"], eps), layer["mlp"]
        a = jax.nn.silu(moe_lm._mm("bsd,df->bsf", h, p["w_gate"], precision))
        y = moe_lm._mm("bsf,fd->bsd", a * moe_lm._mm("bsd,df->bsf", h, p["w_in"], precision),
                       p["w_out"], precision)
        return keep(x + y), None

    x, _ = jax.lax.scan(block, x, params["layers"])
    return moe_lm.rms_norm(x, params["final_norm"], eps)


def loss(params, tokens, labels, cfg, precision="f32", *, mask=None):
    """Mean next-token cross-entropy over the positions ``mask`` keeps."""
    lg = logits(params, features(params, tokens, cfg, precision), precision)
    if mask is None:
        mask = jnp.ones(labels.shape, moe_lm.F32)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(mask * (jax.nn.logsumexp(lg, axis=-1) - gold)) / jnp.sum(mask)
