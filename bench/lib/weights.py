"""Random weights of a configuration, made on the device from ``--seed``.

One jitted call draws every leaf, in the dtype it is stored in, so set-up
pays one program (cached after the first run) and no host copy.  The same
function gives the reference its weights: nothing of the system under test
is involved.

Layout (``L`` layers, ``E`` experts held, leaves of a layer stacked on a
leading layer axis)::

    embed      [V, D]          multiplied by sqrt(D) on lookup
    layers     norm1 [L, D], norm2 [L, D]           gain applied as 1 + w
               attn  wq [L, D, H, dh]  wk, wv [L, D, Hkv, dh]  wo [L, H, dh, D]
               moe   router [L, D, E] (float32)
                     w_in, w_gate [L, E, D, F]  w_out [L, E, F, D]
    final_norm [D]
    lm_head    [D, V]

Matrices, the embedding table as stored among them, are drawn from
N(0, initializer_range), the source configuration's value; norm gains start
at 1 (w = 0).
"""

from __future__ import annotations

import functools

import numpy as np

from bench.lib import dims

NORMS = ("norm1", "norm2", "final_norm")


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


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


@functools.lru_cache(maxsize=None)
def _drawer(spec: tuple, std: float):
    """One jitted program per layout (cached, so every call after the first
    in a process reuses it)."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        flat = {}
        for i, (path, shape, dt) in enumerate(spec):
            if path.split("/")[-1] in NORMS:
                flat[path] = jnp.zeros(shape, dt)
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
            flat[path] = x.astype(dt)
        return nest(flat)

    return jax.jit(draw)


def make(seed: int, c: dict) -> dict:
    """Every weight of configuration ``c`` from ``seed``, in one jitted call."""
    spec = tuple((path, shape, dt) for path, (shape, dt) in sorted(shapes(c).items()))
    draw = _drawer(spec, float(c["initializer_range"]))
    return draw(seed_key(seed))
