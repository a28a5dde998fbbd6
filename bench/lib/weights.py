"""Random weights of a configuration, made on the device from ``--seed``.

One jitted call draws every leaf, in the dtype it is stored in, so set-up
pays one program (cached after the first run) and no host copy.  The same
function gives the reference its weights: nothing of the system under test
is involved.

The leaves, their shapes and dtypes, and which of them start at zero come
from the configuration's architecture module (``bench/lib/arch.py``).
Matrices, the embedding table as stored among them, are drawn from
N(0, initializer_range), the source configuration's value; the leaves the
module names in ``ZEROS`` (norm gains) start at 0.  Leaf ``i`` of the
sorted paths draws from ``fold_in(key, i)``.
"""

from __future__ import annotations

import functools

import numpy as np

from bench.lib import arch


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
def _drawer(spec: tuple, std: float, zeros: tuple):
    """One jitted program per layout (cached, so every call after the first
    in a process reuses it)."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        flat = {}
        for i, (path, shape, dt) in enumerate(spec):
            if path.split("/")[-1] in zeros:
                flat[path] = jnp.zeros(shape, dt)
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
            flat[path] = x.astype(dt)
        return nest(flat)

    return jax.jit(draw)


def make(seed: int, c: dict, model=None) -> dict:
    """Every weight of configuration ``c`` from ``seed``, in one jitted call.
    ``model`` is ``c``'s architecture module (by default the one
    ``arch.of(c)`` finds)."""
    model = model or arch.of(c)
    spec = tuple((path, shape, dt) for path, (shape, dt) in sorted(model.shapes(c).items()))
    draw = _drawer(spec, float(c["initializer_range"]), tuple(model.ZEROS))
    return draw(seed_key(seed))
