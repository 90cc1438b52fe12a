"""Asynchronous, staleness-aware aggregation (``FedConfig(aggregation=
"async")``) — the participation/staleness *model* of the round pipeline
(cf. "Federated LLMs: Current Progress and Future Directions",
arXiv:2409.15723): real fleets never deliver every client's update in
lockstep, so the server must fold in *late* knowledge without stalling
the round clock.

Simulation model (FedAsync-style, deterministic under ``FedConfig.seed``):

- ``ParticipationSchedule`` gives every client a seeded per-job delay.
  A free client *starts* a job each round: it pulls the current global
  state, trains locally NOW (so its update reflects the global it saw),
  and the update goes in flight for ``delay`` rounds.
- At each round the server aggregates the updates that *arrive*, each
  weighted by its data weight times the polynomial staleness decay
  ``(1 + s)^-staleness_decay`` where ``s = arrival - start`` rounds.
  Updates staler than ``max_staleness`` are discarded (the client simply
  re-syncs).  The mass of clients that delivered nothing this round
  anchors the current global, so a lone stale straggler cannot yank the
  model.
- ``max_staleness == 0`` forces fully synchronous participation, which
  makes the async schedule coincide with the sync one *exactly* at
  ``lora_dropout == 0`` (tests/test_async_agg.py) — the knob
  interpolates between the paper-literal round and a realistic fleet.

The staleness treatment is uniform across the three frameworks — what
differs is the payload in flight: LoRA **params** for FedLLM, public-set
**logits** for KD-FedLLM, and **client-half adapters** for Split-FedLLM
(activations/grad traffic stays synchronous inside the training round:
the server's half is in the loop while a split client trains).

This module holds only the *model*: the delay schedule, the in-flight
``Job`` bookkeeping (``pop_arrivals``) and the staleness weights, which
core/round_program.py's schedules compose with any framework x
executor, and ``combine_arrivals``, the host-side combine the round
driver's parameter fold falls back to for the combines that cannot
stream (robust order statistics, svd re-factorization of heterogeneous
ranks).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fed_spmd
from repro.core.fedavg import fedavg
from repro.core.heterogeneous import aggregate_hetero
from repro.peft import lora as lora_lib


# --------------------------------------------------------------------------- #
# Participation schedule + staleness weights
# --------------------------------------------------------------------------- #
class ParticipationSchedule:
    """Deterministic per-client availability/delay model.

    Client speed is a per-client *trait*: a "slowness" drawn once from
    the master seed gives each client a Binomial(max_staleness + 1,
    slowness) delay per job — fast clients usually deliver in the round
    they start, slow clients lag several rounds and occasionally exceed
    ``max_staleness`` (the server discards those updates).  Per-client
    generators are consumed one draw per started job, so a fixed seed
    replays the identical schedule on any backend."""

    def __init__(self, n_clients: int, seed: int = 0,
                 max_staleness: int = 4):
        master = np.random.default_rng(seed)
        self.slowness = master.uniform(0.15, 0.85, n_clients)
        self.max_staleness = int(max_staleness)
        self._rngs = [np.random.default_rng((seed, 7919, ci))
                      for ci in range(n_clients)]

    def next_delay(self, ci: int) -> int:
        """Rounds until client ``ci``'s freshly started job delivers
        (0 = the same round it trains)."""
        if self.max_staleness <= 0:
            return 0
        return int(self._rngs[ci].binomial(self.max_staleness + 1,
                                           self.slowness[ci]))

    # -- checkpoint/resume (checkpoint/federated.py) --------------------- #
    def state(self) -> List[dict]:
        """Per-client generator states — the only mutable part (the
        slowness traits re-derive from the seed)."""
        return [g.bit_generator.state for g in self._rngs]

    def load_state(self, states: List[dict]):
        for g, st in zip(self._rngs, states):
            g.bit_generator.state = st


def staleness_weight(staleness: int, decay: float) -> float:
    """Polynomial staleness decay (FedAsync): ``(1 + s)^-decay``."""
    return float((1.0 + staleness) ** (-decay))


@dataclasses.dataclass
class Job:
    """One in-flight client update."""
    client: int
    start: int          # round the client pulled the global + trained
    arrival: int        # round the update lands on the server
    payload: object     # params / logits / client-half adapters


def pop_arrivals(in_flight: Dict[int, Job], rnd: int) -> List[Job]:
    """Jobs delivering this round, in client visit order."""
    arrived = sorted((j for j in in_flight.values() if j.arrival == rnd),
                     key=lambda j: j.client)
    for j in arrived:
        del in_flight[j.client]
    return arrived


def stale_weighted_avg(global_tree, arrivals, total_weight: float, fed,
                       ranks: List[int]):
    """Staleness-weighted FedAvg of arrived parameter trees.

    ``arrivals`` is a list of ``(client, tree, staleness, data_weight)``
    already filtered to ``staleness <= max_staleness``.  The data weight
    of every client that delivered nothing this round anchors the
    current global tree, so the update is a convex combination that
    degenerates to plain (hetero-aware) FedAvg when everyone arrives
    fresh — the sync-equivalence property the tests pin down."""
    trees = [t for _, t, _, _ in arrivals]
    rks = [ranks[ci] for ci, _, _, _ in arrivals]
    ws = [w * staleness_weight(s, fed.staleness_decay)
          for _, _, s, w in arrivals]
    absent = total_weight - sum(w for _, _, _, w in arrivals)
    if absent > 0:
        trees = [global_tree] + trees
        rks = [fed.lora_rank] + rks
        ws = [absent] + ws
    if any(r != fed.lora_rank for r in rks):
        return aggregate_hetero(trees, rks, fed.lora_alpha, fed.lora_rank,
                                ws, fed.hetero_agg)
    return fedavg(trees, ws)


def robust_stale_combine(global_tree, arrivals, total_weight: float, fed,
                         ranks: List[int]):
    """Byzantine-robust counterpart of ``stale_weighted_avg``.

    The robust statistic (``fed_spmd.robust_client_combine``) runs over
    the *arrived* updates only — anchoring absent mass on the current
    global inside a median/trim would let the anchor masquerade as a
    client — and the result is then blended with the current global by
    the staleness-weighted arrived mass ``rho``, preserving the async
    semantics that a thin round moves the model only a little.  When
    everyone arrives fresh (``rho == 1``) the result is exactly the
    robust combine of the cohort.  Heterogeneous ranks are zero-padded
    to the global rank first (order statistics need one client axis)."""
    trees = []
    for ci, t, _, _ in arrivals:
        if ranks[ci] != fed.lora_rank:
            t = lora_lib.pad_rank(t, fed.lora_rank)
        trees.append(t)
    ws = [w * staleness_weight(s, fed.staleness_decay)
          for _, _, s, w in arrivals]
    agg = fed_spmd.robust_client_combine(
        fed_spmd.stack_trees(trees), jnp.asarray(ws, jnp.float32),
        fed.robust_agg, fed.trim_frac, fed.clip_norm)
    absent = total_weight - sum(w for _, _, _, w in arrivals)
    if absent <= 0:
        return agg
    rho = sum(ws) / (absent + sum(ws))
    return jax.tree.map(
        lambda g, a: ((1.0 - rho) * g.astype(jnp.float32)
                      + rho * a.astype(jnp.float32)).astype(g.dtype),
        global_tree, agg)


def combine_arrivals(global_tree, arrivals, total_weight: float, fed,
                     ranks: List[int]):
    """The round's configured host-side combine: plain staleness-weighted
    (hetero-aware) FedAvg, or the robust path when ``fed.robust_agg``
    asks for one."""
    if getattr(fed, "robust_agg", "mean") != "mean" and arrivals:
        return robust_stale_combine(global_tree, arrivals, total_weight,
                                    fed, ranks)
    return stale_weighted_avg(global_tree, arrivals, total_weight, fed,
                              ranks)
