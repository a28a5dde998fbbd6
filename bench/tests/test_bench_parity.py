"""The architecture module gives what the harness computed before the
architecture lookup existed: the same weight shapes, FLOP and byte counts
and weights.  The constants were recorded from the harness as it was, where
``bench/lib/weights.py`` and the metric readers held the Mixtral layout and
formulas themselves."""

import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from bench.lib import arch, weights
from bench.reference import checks

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONFIGS = {
    "mixtral-8x7b-train-1chip": ROOT / "bench" / "configs" / "mixtral-8x7b-train-1chip.json",
    "mixtral-8x7b-serve-1chip": ROOT / "bench" / "configs" / "mixtral-8x7b-serve-1chip.json",
    "tiny-moe": ROOT / "bench" / "tests" / "data" / "tiny-moe.json",
}
# (live, context, prompts) of three serving ticks: a full decode tick, a
# tick that admits one prompt, and one that admits three.
TICKS = [(64, 64_000, []), (3, 700, [256]), (17, 5000, [64, 512, 128])]
TOKENS = 4096
SEED = 1234

# Recorded values.  COUNTS: flops_per_token(c, TOKENS), call_bounds(c,
# TOKENS) and tick_bounds(c, *t) for each t of TICKS.  WEIGHT_DIGESTS: the
# first 16 hex digits of the sha256 of each leaf's bytes in
# weights.make(SEED, tiny-moe).
SHAPES = {
    "mixtral-8x7b-train-1chip": {
        "embed": ((32000, 4096), "bfloat16"),
        "layers/norm1": ((1, 4096), "bfloat16"),
        "layers/attn/wq": ((1, 4096, 32, 128), "bfloat16"),
        "layers/attn/wk": ((1, 4096, 8, 128), "bfloat16"),
        "layers/attn/wv": ((1, 4096, 8, 128), "bfloat16"),
        "layers/attn/wo": ((1, 32, 128, 4096), "bfloat16"),
        "layers/norm2": ((1, 4096), "bfloat16"),
        "layers/moe/router": ((1, 4096, 4), "float32"),
        "layers/moe/w_in": ((1, 4, 4096, 14336), "bfloat16"),
        "layers/moe/w_gate": ((1, 4, 4096, 14336), "bfloat16"),
        "layers/moe/w_out": ((1, 4, 14336, 4096), "bfloat16"),
        "final_norm": ((4096,), "bfloat16"),
        "lm_head": ((4096, 32000), "bfloat16"),
    },
    "mixtral-8x7b-serve-1chip": {
        "embed": ((32000, 4096), "bfloat16"),
        "layers/norm1": ((2, 4096), "bfloat16"),
        "layers/attn/wq": ((2, 4096, 32, 128), "bfloat16"),
        "layers/attn/wk": ((2, 4096, 8, 128), "bfloat16"),
        "layers/attn/wv": ((2, 4096, 8, 128), "bfloat16"),
        "layers/attn/wo": ((2, 32, 128, 4096), "bfloat16"),
        "layers/norm2": ((2, 4096), "bfloat16"),
        "layers/moe/router": ((2, 4096, 8), "float32"),
        "layers/moe/w_in": ((2, 8, 4096, 14336), "bfloat16"),
        "layers/moe/w_gate": ((2, 8, 4096, 14336), "bfloat16"),
        "layers/moe/w_out": ((2, 8, 14336, 4096), "bfloat16"),
        "final_norm": ((4096,), "bfloat16"),
        "lm_head": ((4096, 32000), "bfloat16"),
    },
    "tiny-moe": {
        "embed": ((128, 64), "bfloat16"),
        "layers/norm1": ((1, 64), "bfloat16"),
        "layers/attn/wq": ((1, 64, 4, 16), "bfloat16"),
        "layers/attn/wk": ((1, 64, 2, 16), "bfloat16"),
        "layers/attn/wv": ((1, 64, 2, 16), "bfloat16"),
        "layers/attn/wo": ((1, 4, 16, 64), "bfloat16"),
        "layers/norm2": ((1, 64), "bfloat16"),
        "layers/moe/router": ((1, 64, 4), "float32"),
        "layers/moe/w_in": ((1, 4, 64, 128), "bfloat16"),
        "layers/moe/w_gate": ((1, 4, 64, 128), "bfloat16"),
        "layers/moe/w_out": ((1, 4, 128, 64), "bfloat16"),
        "final_norm": ((64,), "bfloat16"),
        "lm_head": ((64, 128), "bfloat16"),
    },
}
COUNTS = {
    "mixtral-8x7b-train-1chip": {
        "flops_per_token": 3252781056.0,
        "call_bounds": (962072674304.0, 771751936.0),
        "tick_bounds": [
            (68293754880.0, 2017525760.0),
            (205834452992.0, 1758248960.0),
            (576193200128.0, 1775861760.0),
        ],
    },
    "mixtral-8x7b-serve-1chip": {
        "flops_per_token": 5719326720.0,
        "call_bounds": (962072674304.0, 1241513984.0),
        "tick_bounds": [
            (119814488064.0, 6591610880.0),
            (410637303808.0, 6073057280.0),
            (1147190771712.0, 6108282880.0),
        ],
    },
    "tiny-moe": {
        "flops_per_token": 1992192.0,
        "call_bounds": (134217728.0, 3211264.0),
        "tick_bounds": [
            (25329664.0, 8430592.0),
            (40591872.0, 328192.0),
            (126749184.0, 878592.0),
        ],
    },
}
WEIGHT_DIGESTS = {
    "embed": ("bfloat16", "066ee67cf49be44c"),
    "final_norm": ("bfloat16", "38723a2e5e8a17aa"),
    "layers/attn/wk": ("bfloat16", "22bf30f12e5c8a45"),
    "layers/attn/wo": ("bfloat16", "e4d53d9aca1a7648"),
    "layers/attn/wq": ("bfloat16", "f537f40e85ec6fe6"),
    "layers/attn/wv": ("bfloat16", "f225ab3571d3e99a"),
    "layers/moe/router": ("float32", "82c8d1800b1f788b"),
    "layers/moe/w_gate": ("bfloat16", "516b1fdcb8ccd2e9"),
    "layers/moe/w_in": ("bfloat16", "20276cf667cf663b"),
    "layers/moe/w_out": ("bfloat16", "3988d89c14cb91c0"),
    "layers/norm1": ("bfloat16", "38723a2e5e8a17aa"),
    "layers/norm2": ("bfloat16", "38723a2e5e8a17aa"),
    "lm_head": ("bfloat16", "856b0aa173e98a4c"),
}


def config(name):
    return json.loads(CONFIGS[name].read_text())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_shapes_and_counts_are_the_recorded_ones(name):
    c = config(name)
    assert {k: (tuple(s), dt) for k, (s, dt) in arch.of(c).shapes(c).items()} == SHAPES[name]
    want = COUNTS[name]
    assert arch.count(c, "flops_per_token")(c, TOKENS) == want["flops_per_token"]
    assert arch.count(c, "call_bounds")(c, TOKENS) == want["call_bounds"]
    assert [arch.count(c, "tick_bounds")(c, *t) for t in TICKS] == want["tick_bounds"]


def test_weights_are_the_recorded_ones():
    w = weights.make(SEED, config("tiny-moe"))
    got = {}
    for path, leaf in zip(checks.leaf_paths(w), jax.tree.leaves(w)):
        a = np.asarray(leaf)
        got[path] = (str(a.dtype), hashlib.sha256(a.tobytes()).hexdigest()[:16])
    assert got == WEIGHT_DIGESTS
