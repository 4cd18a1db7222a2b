"""Training input: a pool of token sequences drawn from the seed.

Uniform-random token ids: the loss then sits at ln(vocab) and the step does
the same arithmetic as on text.  The pool is ``pool_batches`` global batches
of ``seq_len + 1`` ids (the trainer shifts inputs and targets by one); the
input pipeline shuffles and batches it.  Every seed gives the same sizes.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int, vocab_size: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n = params["pool_batches"] * params["global_batch"]
    return rng.integers(0, vocab_size, (n, params["seq_len"] + 1),
                        dtype=np.int32)
