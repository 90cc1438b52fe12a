"""Single source of truth for the round engine's per-(round, client,
step) key trees.

Three seeded streams feed a federated run, and every engine combination
(framework x backend x aggregation) must draw from the *same* streams so
parity is by construction rather than by re-derivation:

- **Dropout keys** (``local_rng`` / ``grid_keys``): the per-(client,
  round) root each local job splits its per-step dropout keys from.
  Both execution backends use the same root, so sequential/SPMD agree
  bit-exactly at ``lora_dropout == 0`` and draw equally valid masks
  otherwise.
- **Privacy noise keys** (privacy/dp.noise_key): a domain-separated
  ``fold_in`` chain over (seed, round, client[, step]) built on
  ``fold_chain`` below — never the dropout stream.
- **Batching seeds** are plain ints handed to data/loader.epoch_batches
  (per-framework constants in core/round_program.py).

tests/test_rng.py pins all of these against the literal formulas the
pre-pipeline engines used, so refactors cannot silently shift a stream.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def fold_chain(key, *vals):
    """``fold_in`` chained over ``vals`` — the backend-free derivation
    primitive every key tree in the engine reduces to."""
    for v in vals:
        key = jax.random.fold_in(key, v)
    return key


def host_fold_rng(seed: int, *vals) -> np.random.Generator:
    """Host-side counterpart of ``fold_chain``: a numpy ``Generator``
    seeded by folding ``vals`` into ``PRNGKey(seed)`` and reading the
    resulting key data out as the seed sequence.

    The derivation is order-sensitive and collision-resistant the same
    way the device streams are, so host-side per-entity randomness (a
    virtual client's data shard, for instance) is bit-stable no matter
    which order — or how many times — entities are materialized."""
    key = fold_chain(jax.random.PRNGKey(int(seed)), *(int(v) for v in vals))
    words = np.asarray(jax.random.key_data(key), dtype=np.uint32).ravel().tolist()
    return np.random.default_rng(words)


def local_rng(fed, rnd: int, ci: int):
    """Per-(client, round) dropout-key root for one local job."""
    return jax.random.PRNGKey(fed.seed * 1013 + rnd * 131 + ci)


def grid_keys(fed, rnd: int, cis, n_steps: int):
    """(|cis|, n_steps) dropout-key grid for a stacked SPMD program:
    row k is ``jax.random.split(local_rng(fed, rnd, cis[k]), n_steps)``
    — the exact per-step keys a stacked client consumes."""
    return jnp.stack([jax.random.split(local_rng(fed, rnd, ci), n_steps)
                      for ci in cis])
