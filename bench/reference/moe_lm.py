"""Plain reference of a Mixtral-style sparse-MoE decoder, in float32.

Written from the Mixtral description (Jiang et al., 2024, "Mixtral of
Experts", and the model's published ``config.json``): pre-norm blocks of
RMSNorm -> grouped-query attention with rotary embeddings (half-split
rotation) -> residual, then RMSNorm -> top-k softmax router over the experts,
the k gate values renormalised to sum to 1 -> SwiGLU experts
``w_out(silu(x w_gate) * (x w_in))`` -> residual; a final RMSNorm and an
untied output head.  Two stated parametrisation choices of the configuration
file (``assumed`` there) are followed: the embedding is multiplied by
``sqrt(hidden_size)`` on lookup, and each RMSNorm gain is stored as ``w`` and
applied as ``1 + w``.

Every expert is computed on every token and weighted by its (mostly zero)
gate: the plainest form of the same function.  Matrix products run at
``Precision.HIGHEST``.  Weights are read from the bench's own generator
(``bench/lib/weights.py``) and nothing here imports the system under test.

``precision="fp8"`` is the control, the reference computed one step below
the configuration's bfloat16: every matrix product takes its two operands
and gives its result rounded to float8 (e4m3) with one scale per tensor, and
the residual stream is held in float8, as the program takes, gives and holds
bfloat16 ones.  The rounding is
straight-through, so the backward pass sees the rounded values and exact
cotangents.

Memory: attention runs one key/value head group at a time and the experts
one at a time, each under ``jax.checkpoint``, so a 4096-token sequence at
Mixtral-8x7B widths fits one 16 GB chip next to the weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x):
    """Round ``x`` to float8 e4m3 with one per-tensor scale (straight-through)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, precision):
    a = a.astype(F32)
    b = b.astype(F32)
    if precision == "fp8":
        return _fp8(jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(F32))


def rope(x, positions, theta):
    """Half-split rotary embedding of ``x [B, S, H, dh]``."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = positions[..., None].astype(F32) * inv  # [B, S, dh/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, h, cfg, precision):
    """Causal GQA self-attention of ``h [B, S, D]``."""
    b, s, _ = h.shape
    nh, nkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    group = nh // nkv
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q = rope(_mm("bsd,dhk->bshk", h, p["wq"], precision), pos, cfg["rope_theta"])
    k = rope(_mm("bsd,dhk->bshk", h, p["wk"], precision), pos, cfg["rope_theta"])
    v = _mm("bsd,dhk->bshk", h, p["wv"], precision)
    # [Hkv, B, S, group, dh] query groups against [Hkv, B, S, dh] keys/values.
    qg = q.reshape(b, s, nkv, group, dh).transpose(2, 0, 1, 3, 4)
    kg, vg = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    @jax.checkpoint
    def one_group(args):
        qh, kh, vh = args
        logits = _mm("bqgd,bkd->bgqk", qh, kh, precision) / math.sqrt(dh)
        logits = jnp.where(causal, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return _mm("bgqk,bkd->bqgd", probs, vh, precision)

    out = jax.lax.map(one_group, (qg, kg, vg))  # [Hkv, B, S, group, dh]
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, s, nh, dh)
    return _mm("bshk,hkd->bsd", out, p["wo"], precision)


def moe(p, h, cfg, precision):
    """Top-k routed SwiGLU experts on ``h [T, D]``, every expert dense."""
    k = cfg["num_experts_per_tok"]
    logits = _mm("td,de->te", h, p["router"], precision)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)

    @jax.checkpoint
    def one_expert(acc, xs):
        w_in, w_gate, w_out, g = xs
        a = jax.nn.silu(_mm("td,df->tf", h, w_gate, precision))
        y = _mm("tf,fd->td", a * _mm("td,df->tf", h, w_in, precision), w_out, precision)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros(h.shape, F32),
        (p["w_in"], p["w_gate"], p["w_out"], gates.T),
    )
    return out


def features(params, tokens, cfg, precision="f32"):
    """Final-normed hidden states ``[B, S, D]`` of ``tokens [B, S]``."""
    d = cfg["hidden_size"]
    eps = cfg["rms_norm_eps"]
    # The residual stream is held at the working precision, as the
    # program holds it in bfloat16.
    keep = _fp8 if precision == "fp8" else (lambda v: v)
    x = keep(jnp.take(params["embed"], tokens, axis=0).astype(F32) * math.sqrt(d))
    b, s, _ = x.shape

    def block(x, layer):
        h = rms_norm(x, layer["norm1"], eps)
        x = keep(x + attention(layer["attn"], h, cfg, precision))
        h = rms_norm(x, layer["norm2"], eps)
        y = moe(layer["moe"], h.reshape(b * s, d), cfg, precision).reshape(b, s, d)
        return keep(x + y), None

    # ``layers`` leaves are stacked on a leading layer axis.
    x, _ = jax.lax.scan(block, x, params["layers"])
    return rms_norm(x, params["final_norm"], eps)


def logits(params, feats, precision="f32"):
    return _mm("bsd,dv->bsv", feats, params["lm_head"], precision)


def loss(params, tokens, labels, cfg, precision="f32", *, mask=None, chunk=1024):
    """Mean next-token cross-entropy over the positions ``mask`` keeps (all
    by default), the head applied ``chunk`` positions at a time so
    ``[B, S, V]`` logits never exist at once."""
    feats = features(params, tokens, cfg, precision)
    b, s, d = feats.shape
    if mask is None:
        mask = jnp.ones((b, s), F32)
    chunk = math.gcd(s, chunk)

    def chunks(x):
        return x.reshape(b, s // chunk, chunk, *x.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def body(total, xs):
        f, lab, m = xs
        lg = logits(params, f, precision)
        gold = jnp.take_along_axis(lg, lab[..., None], axis=-1)[..., 0]
        return total + jnp.sum(m * (jax.nn.logsumexp(lg, axis=-1) - gold)), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32), (chunks(feats), chunks(labels), chunks(mask)))
    return total / jnp.sum(mask)
