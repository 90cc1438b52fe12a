"""Operations a federated round requires, counted from the shapes.

The rule (PERF.md states it too):

- Only real tokens count: a row of length l contributes l positions;
  padding up to ``pad_len`` is not work the task needs.
- Attention is causal over the real prefix: position i reads i + 1 keys.
- The classification head is read at one position per row, over the 77
  answer ids (the tied embedding's rows 1..77), not over the vocabulary.
- Training (frozen base, LoRA on q/k/v): the forward pass, plus the
  activation-gradient chain (one product per frozen matrix product and
  two per attention product), plus LoRA's own: each adapter product
  costs three times its forward (forward, input gradient, weight
  gradient).  The first layer's input gradient is not needed and is not
  counted.
- Evaluation is the forward pass.  Aggregation is not counted.
- Recomputed work does not count.
"""
from __future__ import annotations

import numpy as np

from bench.references.decoder import N_CLASSES, Arch


def _per_token(a: Arch, rank: int):
    """(frozen matmul FLOPs, LoRA forward FLOPs, the input-gradient FLOPs
    of the q/k/v products and their adapters) per token per layer."""
    d, f = a.d_model, a.d_ff
    q, kv = a.n_heads * a.head_dim, a.n_kv_heads * a.head_dim
    mlp = (3 if a.mlp == "swiglu" else 2) * d * f
    qkv = d * (q + 2 * kv)
    frozen = 2 * (qkv + q * d + mlp)
    lora = 2 * rank * (3 * d + q + 2 * kv)
    return frozen, lora, 2 * qkv + 2 * 3 * d * rank


def _attention(a: Arch, lengths: np.ndarray) -> np.ndarray:
    """Forward score + value products per row, summed over layers."""
    pairs = lengths.astype(np.float64) * (lengths + 1) / 2
    return 4.0 * a.n_heads * a.head_dim * pairs * a.n_layers


def forward_flops(a: Arch, rank: int, lengths) -> float:
    lengths = np.asarray(lengths, np.float64)
    frozen, lora, _ = _per_token(a, rank)
    tokens = lengths.sum()
    head = 2.0 * a.d_model * N_CLASSES * lengths.size
    return float(tokens * a.n_layers * (frozen + lora)
                 + _attention(a, lengths).sum() + head)


def train_flops(a: Arch, rank: int, lengths) -> float:
    """One training step over rows of the given lengths."""
    lengths = np.asarray(lengths, np.float64)
    frozen, lora, first_dx = _per_token(a, rank)
    tokens = lengths.sum()
    fwd = forward_flops(a, rank, lengths)
    bwd = (tokens * (a.n_layers * (frozen + 2 * lora) - first_dx)
           + 2.0 * _attention(a, lengths).sum()
           + 2.0 * a.d_model * N_CLASSES * lengths.size)
    return float(fwd + bwd)


def train_round_flops(a: Arch, rank: int, lengths, valid) -> float:
    """Every real local step of a client stack fed as ``lengths``
    (C, S, B) with ``valid`` (C, S)."""
    lengths = np.asarray(lengths)
    valid = np.asarray(valid, bool)
    return sum(train_flops(a, rank, lengths[c, s])
               for c in range(valid.shape[0])
               for s in range(valid.shape[1]) if valid[c, s])
