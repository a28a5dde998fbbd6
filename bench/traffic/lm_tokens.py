"""Training traffic: a stream of next-token batches.

Copied from the repository's ``src/repro/data/pipeline.py`` ``SyntheticLM``
(Zipf unigrams over the vocabulary with short repeated motifs injected, one
``numpy`` generator per step seeded from ``(seed, step)``), so that later
changes to the program's pipeline do not move the benchmark's input.

Parameters (the mix's ``.json``): ``seq_len``, ``batch``.  Every step's rows
differ; the same seed gives the same batches.
"""

from __future__ import annotations

import dataclasses

import numpy as np

DRIVER = "train"


@dataclasses.dataclass
class Batch:
    tokens: np.ndarray  # [B, S] int32
    labels: np.ndarray  # [B, S] int32 (next token)


class TokenStream:
    def __init__(self, vocab_size: int, seq_len: int, batch: int, *, seed: int):
        self.vocab = vocab_size
        self.seq = seq_len
        self.batch = batch
        self.seed = int(seed)
        self.step = 0
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        self._probs = (1.0 / ranks) / np.sum(1.0 / ranks)

    def __iter__(self):
        return self

    def __next__(self) -> Batch:
        rng = np.random.default_rng(self.seed * 1_000_003 * 7_919 + self.step)
        b, s = self.batch, self.seq
        toks = rng.choice(self.vocab, size=(b, s + 1), p=self._probs).astype(np.int32)
        for i in range(b):
            motif = rng.integers(0, self.vocab, size=8)
            for _ in range(max(s // 64, 1)):
                off = int(rng.integers(0, s - 8))
                toks[i, off : off + 8] = motif
        self.step += 1
        return Batch(tokens=toks[:, :-1], labels=toks[:, 1:])


def stream(traffic: dict, config: dict, seed: int) -> TokenStream:
    return TokenStream(config["vocab_size"], traffic["seq_len"], traffic["batch"], seed=seed)
