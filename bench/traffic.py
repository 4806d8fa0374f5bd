"""The one generator of training traffic: token batches from a seed.

A training mix (``bench/traffic/<name>.json``) fixes the batch, the
sequence length and the token distribution.  Every batch is a function of
``(seed, step)`` alone, and every step's rows differ.  The program's own
data pipeline feeds the timed path; these are the rows it must feed.
The reference trains on them, and each run holds the rows the pipeline
fed its checked steps against them (``rows_wrong``).

Token distribution ``{"dist": "power", "power": p}``: id = floor(u**p * V)
for u uniform on [0, 1), a heavy head of frequent ids and a long tail, as
in natural text; ``p = 1`` is uniform.
"""

from __future__ import annotations

import numpy as np


class TokenBatches:
    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq"])
        self.dist = traffic["tokens"]
        if self.dist["dist"] != "power":
            raise ValueError(f"unknown token distribution {self.dist}")
        self.vocab = vocab_size
        self.seed = seed

    def tokens(self, step: int) -> np.ndarray:
        # one data-parallel rank: rank 0 of the seed's sequence
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, step, 0]))
        u = rng.random((self.batch, self.seq))
        ids = (u ** float(self.dist["power"]) * self.vocab).astype(np.int64)
        return np.minimum(ids, self.vocab - 1).astype(np.int32)
