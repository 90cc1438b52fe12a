"""core/rng.py is the single source of truth for the engine's key
streams.  These tests pin every stream against the literal formulas the
pre-RoundProgram engines used (PR 4 state), so the dedupe can never
silently shift a stream — seeds, dropout masks and DP noise must replay
bit-identically across refactors."""
import dataclasses

import jax
import numpy as np

from repro.configs.base import FedConfig, PrivacyConfig
from repro.core import rng
from repro.privacy import dp


def _fed(**kw):
    base = dict(framework="fedllm", seed=3,
                privacy=PrivacyConfig(dp_clip=1.0, dp_noise_multiplier=0.5))
    base.update(kw)
    return FedConfig(**base)


def test_local_rng_pinned_to_legacy_formula():
    fed = _fed()
    for rnd in (0, 2, 7):
        for ci in (0, 1, 5):
            want = jax.random.PRNGKey(fed.seed * 1013 + rnd * 131 + ci)
            np.testing.assert_array_equal(
                np.asarray(rng.local_rng(fed, rnd, ci)), np.asarray(want))


def test_grid_keys_pinned_to_legacy_formula():
    """The (C, S) dropout grid the SPMD executor consumes is exactly
    split(local_rng) per row, as the SPMD executor always drew it."""
    fed = _fed(seed=11)
    cis, n_steps = [0, 2, 5], 4
    grid = rng.grid_keys(fed, 3, cis, n_steps)
    for k, ci in enumerate(cis):
        want = jax.random.split(
            jax.random.PRNGKey(fed.seed * 1013 + 3 * 131 + ci), n_steps)
        np.testing.assert_array_equal(np.asarray(grid[k]),
                                      np.asarray(want))


def test_noise_key_pinned_to_legacy_fold_chain():
    """privacy/dp.noise_key through core/rng.fold_chain reproduces the
    PR 4 fold_in chain: PRNGKey(seed) -> 0x5EC7 -> privacy.seed -> rnd
    -> ci -> step."""
    fed = _fed(seed=5, privacy=PrivacyConfig(dp_clip=1.0,
                                             dp_noise_multiplier=0.5,
                                             seed=9))
    for rnd, ci, step in ((0, 0, 0), (2, 1, 3), (7, 5, 1)):
        key = jax.random.fold_in(jax.random.PRNGKey(fed.seed), 0x5EC7)
        key = jax.random.fold_in(key, fed.privacy.seed)
        key = jax.random.fold_in(key, rnd)
        key = jax.random.fold_in(key, ci)
        key = jax.random.fold_in(key, step)
        np.testing.assert_array_equal(
            np.asarray(dp.noise_key(fed, rnd, ci, step)), np.asarray(key))


def test_fold_chain_is_fold_in_composition():
    k0 = jax.random.PRNGKey(0)
    want = jax.random.fold_in(jax.random.fold_in(k0, 3), 7)
    np.testing.assert_array_equal(np.asarray(rng.fold_chain(k0, 3, 7)),
                                  np.asarray(want))


def test_streams_distinct_across_seeds():
    a = rng.local_rng(_fed(seed=0), 1, 1)
    b = rng.local_rng(_fed(seed=1), 1, 1)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    na = dp.noise_key(_fed(seed=0), 1, 1)
    da = rng.local_rng(_fed(seed=0), 1, 1)
    # privacy noise and dropout streams are domain-separated
    assert not np.array_equal(np.asarray(na), np.asarray(da))


def test_noise_key_grid_builds_on_same_chain():
    fed = dataclasses.replace(_fed(), seed=2)
    grid = dp.noise_key_grid(fed, 1, [0, 3], 2)
    for k, ci in enumerate([0, 3]):
        for s in range(2):
            np.testing.assert_array_equal(
                np.asarray(grid[k, s]),
                np.asarray(dp.noise_key(fed, 1, ci, s)))
