"""Upload-seam payload validation (finite check + norm screen).

The round driver (core/round_program.py) runs these over every arrival
before it reaches the aggregate stage: non-finite payloads are always
quarantined; when ``FedConfig.screen_factor > 0`` arrivals whose L2
norm exceeds ``screen_factor`` x the round's median arrival norm are
quarantined too.  Checks are host-side numpy over the payload's float
leaves — they never modify the payload, so a clean run's values and
ledger bytes are untouched.
"""
from __future__ import annotations

import math
from typing import List, Sequence

import jax.numpy as jnp
import numpy as np

from repro import obs


def float_leaves(payload) -> List:
    """The float array leaves of a payload pytree (ints — wire-byte
    counts, token ids — cannot be non-finite and are skipped)."""
    import jax
    out = []
    for x in jax.tree.leaves(payload):
        if hasattr(x, "dtype") and jnp.issubdtype(
                jnp.asarray(x).dtype, jnp.floating):
            out.append(x)
    return out


def arrays_finite(arrays: Sequence) -> bool:
    for x in arrays:
        a = obs.to_host(x).astype(np.float32, copy=False)
        if not np.isfinite(a).all():
            return False
    return True


def arrays_norm(arrays: Sequence) -> float:
    """Global L2 norm over all float leaves (fp32 accumulation — the
    screen threshold is coarse, exact dtype does not matter)."""
    total = 0.0
    for x in arrays:
        a = obs.to_host(x).astype(np.float64, copy=False)
        total += float(np.square(a).sum())
    return math.sqrt(total)


def screen(payload_leaf_lists: Sequence[Sequence],
           screen_factor: float) -> List[bool]:
    """Verdicts (True = keep) for one round's arrivals.

    Computed over the *whole* round at once — flat and cohort-streaming
    drivers therefore quarantine the identical set, keeping ledger
    parity across backends.  The median is taken over the finite
    arrivals only, so a NaN payload cannot poison the screen itself.
    """
    ok = [arrays_finite(leaves) for leaves in payload_leaf_lists]
    if screen_factor > 0.0 and any(ok):
        norms = [arrays_norm(leaves) if good else 0.0
                 for leaves, good in zip(payload_leaf_lists, ok)]
        med = float(np.median([n for n, good in zip(norms, ok) if good]))
        if med > 0.0:
            limit = screen_factor * med
            ok = [good and n <= limit for good, n in zip(ok, norms)]
    return ok
