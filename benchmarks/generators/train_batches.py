"""Seeded token batches for a training job: ``n_batches`` distinct batches of
``[batch, seq]`` inputs and labels, uniform over the vocabulary, every row
different.  The driver places them on the device in set-up and cycles them.

Parameters (the mix file's ``params``): ``n_batches``.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int, *, vocab: int, batch: int, seq: int):
    rng = np.random.default_rng([int(seed), 0x7261696E])
    n = int(params["n_batches"])
    x = rng.integers(0, vocab, (n, batch, seq), dtype=np.int32)
    y = rng.integers(0, vocab, (n, batch, seq), dtype=np.int32)
    return x, y
