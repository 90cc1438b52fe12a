"""Heterogeneous-rank LoRA aggregation (paper SSIV.A.2 — beyond-paper
feature): clients fine-tune with different ranks matched to their
resources; the server harmonizes scales before aggregation.

Two strategies:
- ``zeropad``: pad every client's A/B to the max rank, weighted FedAvg in
  factor space (exact when B==0 columns stay untouched; the standard
  HETLoRA baseline).
- ``svd``: reconstruct each client's *delta* (alpha/r_c * A_c @ B_c),
  average the deltas (the quantity that actually edits the model), then
  SVD-truncate back to the global rank — scale-exact at the cost of an
  SVD per target matrix.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp

from repro.core.fedavg import fedavg
from repro.peft import lora as lora_lib


def normalize_ranks(client_ranks, n_clients: int,
                    lora_rank: int) -> List[int]:
    """Single source of truth for per-client LoRA rank normalization:
    an empty/None ``client_ranks`` means every client trains at the
    global rank; otherwise the tuple must name every client exactly once
    and stay within [1, lora_rank].  Every rank-dependent code path
    (engines, bucketing, harmonization) starts from this list — the
    degenerate configs (wrong length, all-equal ranks collapsing to one
    bucket) are property-tested in tests/test_property.py."""
    if not client_ranks:
        return [lora_rank] * n_clients
    if len(client_ranks) != n_clients:
        raise ValueError(
            f"client_ranks has {len(client_ranks)} entries for "
            f"{n_clients} clients")
    if any(r < 1 or r > lora_rank for r in client_ranks):
        raise ValueError(
            f"client_ranks must lie in [1, lora_rank={lora_rank}] "
            f"(got {tuple(client_ranks)}); weak clients truncate the "
            "global rank, they never exceed it")
    return list(client_ranks)


def aggregate_hetero(trees: List, ranks: Sequence[int], alpha: float,
                     global_rank: int, weights=None, method: str = "zeropad"):
    if method == "zeropad":
        padded = [lora_lib.pad_rank(t, global_rank) for t in trees]
        return fedavg(padded, weights)
    if method == "svd":
        return _svd_aggregate(trees, ranks, alpha, global_rank, weights)
    raise ValueError(method)


def _svd_aggregate(trees, ranks, alpha, global_rank, weights):
    if weights is None:
        weights = [1.0] * len(trees)
    total = float(sum(weights))
    ws = [w / total for w in weights]
    scale_g = alpha / max(global_rank, 1)

    def combine(*leaves):
        # leaves: one {"a","b"} dict per client
        delta = None
        for w, lf, r in zip(ws, leaves, ranks):
            s = alpha / max(r, 1)
            d = jnp.einsum("...dr,...rf->...df",
                           lf["a"].astype(jnp.float32),
                           lf["b"].astype(jnp.float32)) * (s * w)
            delta = d if delta is None else delta + d
        u, vt = lora_lib.svd_truncate(delta / scale_g, global_rank)
        return {"a": u, "b": vt}

    return _map_lora_leaves(combine, *trees)


def _map_lora_leaves(fn, *trees):
    t0 = trees[0]
    if isinstance(t0, dict) and set(t0) == {"a", "b"}:
        return fn(*trees)
    if isinstance(t0, dict):
        return {k: _map_lora_leaves(fn, *[t[k] for t in trees]) for k in t0}
    if isinstance(t0, (tuple, list)):
        return tuple(
            _map_lora_leaves(fn, *[t[i] for t in trees])
            if t0[i] is not None else None
            for i in range(len(t0)))
    return t0
