"""Operations a dense GPT-2 needs, as a function of its configuration file.

``train_flops_per_token``: ``6 N + 12 L h s`` — 2N forward and 4N backward
for the matmul path over N parameters (the position table is not a matmul
and is left out of N; the tied word matrix is counted once, as the head),
plus attention's QK^T and PV at 4 L h s forward, times three for training.
Recomputation (remat) is not counted: MFU counts what the algorithm needs.
Copied from ``bench.py`` ``_transformer_flops_per_token``, with N from the
configuration instead of from a parameter tree.
"""
from __future__ import annotations

from typing import Any, Dict


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that take part in a matmul, biases and norms included
    (they are < 0.1 % and every published 6N count includes them)."""
    d, layers = model["n_embd"], model["n_layer"]
    inner = model.get("n_inner") or 4 * d
    per_layer = (4 * d * d + 4 * d          # q, k, v, out + biases
                 + 2 * d * inner + inner + d   # FFN + biases
                 + 4 * d)                    # two layer norms
    return layers * per_layer + model["vocab_size"] * d + 2 * d


def total_params(model: Dict[str, Any]) -> int:
    return matmul_params(model) + model["n_positions"] * model["n_embd"]


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    return (6.0 * matmul_params(model)
            + 12.0 * model["n_layer"] * model["n_embd"] * seq)
