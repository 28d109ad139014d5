"""Training traffic: a new batch of uniformly random token ids for every
step, from the seed.  Parameters (a traffic file with ``"generator":
"token_batches"``): ``batch``, ``seq``."""
from __future__ import annotations

import numpy as np


class TokenBatches:
    def __init__(self, params: dict, vocab_size: int, seed: int):
        self.batch, self.seq = int(params["batch"]), int(params["seq"])
        self.vocab = vocab_size
        self._rng = np.random.default_rng(seed)

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq

    def next(self) -> np.ndarray:
        return self._rng.integers(0, self.vocab, size=(self.batch, self.seq),
                                  dtype=np.int32)


def make(params, vocab_size, seed):
    return TokenBatches(params, vocab_size, seed)
