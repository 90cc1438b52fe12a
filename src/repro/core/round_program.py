"""The composable federated round pipeline — ONE driver for every
engine combination the paper's comparison needs:

    framework     x   backend             x   aggregation   (+ privacy, hetero)
    fedllm/kd/split   sequential/spmd/cohort  sync/async

One federated round decomposes into the paper's canonical stages

    broadcast -> local_update -> upload -> aggregate -> evaluate

and the combination axes are orthogonal pieces composed by
``run_program``:

- A **FrameworkProgram** subclass (FedLLM / KD / Split) contributes the
  stage bodies: what a client computes, what crosses the wire (payload
  + shape-derived bytes), and how the server folds one group of
  arrivals (``agg_fold``) and closes the round (``agg_finalize``).  The
  aggregation stage is the base's ``FrameworkProgram.aggregate``, one
  path on every backend: the round's kept arrivals come in one group
  per secure-agg masking cohort — one per start round on ``sequential``
  and ``spmd`` (a single group under sync), one per start chunk under
  ``cohort`` — and fold in turn into one fp32 accumulator (jitted,
  donated ``_fold_add``); robust and svd combines, which cannot
  stream, buffer the round's arrivals instead.  The same stage-specs
  hand the launch layer its jittable round programs (``spmd_round`` —
  launch/steps.py builds the ``fed_round`` dry-run artifacts from
  them).
- An **Executor** decides how per-client work runs.  ``sequential``
  loops clients (the paper-literal reference); ``spmd`` stacks the
  round's ready-set on a leading client axis and runs one jitted
  program per rank bucket (contiguous equal-rank segments for Split,
  whose shared server half scans clients in visit order).  Given a
  mesh, the SPMD executor places the stacked client axis on the mesh's
  client axes with explicit NamedShardings (launch/sharding.py) — the
  client dimension of a real run shards over the pod/data axes, not
  just in the dry-run.  ``cohort`` (CohortStreamingExecutor) is the
  million-virtual-client path: the round's ready set streams through
  the same SPMD stage programs ``FedConfig.cohort_size`` clients at a
  time, each chunk its own secure-agg masking cohort, and clients come
  from a lazy ``data/population.ClientPopulation`` — peak memory is ONE
  cohort, no full-fleet array ever exists.  Under a hierarchical topology
  (``FedConfig.n_edges`` or a multi-pod mesh) the ledger splits wire
  accounting into client->edge and edge->server hops.
- A **Schedule** decides when uploads arrive: ``SyncSchedule`` delivers
  in the start round; ``AsyncSchedule`` wraps the seeded
  ``ParticipationSchedule`` delay model (core/async_agg.py) and the
  aggregate stage folds arrivals in staleness-weighted.
  ``max_staleness == 0`` collapses async onto sync exactly.
- **Privacy is middleware at fixed seams**: per-example DP-SGD clipping
  lives inside the shared train step (core/fedavg.py), payload noise is
  applied at the upload boundary from the dedicated fold_in stream
  (privacy/dp.py), and secure aggregation masks at upload / verifies
  cancellation at aggregate — uniformly, with zero per-driver
  threading.

Ledger bytes are derived from payload shapes on the host, so they are
per-simulated-client and backend-independent by construction
(tests/test_backend_parity.py pins the full engine matrix).

Adding a framework is one FrameworkProgram subclass; adding a
cross-cutting feature is one stage hook or middleware — not an edit to
O(frameworks x backends x aggregation) hand-written drivers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import FedConfig, ModelConfig
from repro.core import fed_spmd
from repro.core import kd as kd_mod
from repro.core import metrics as M
from repro.core import rng as rng_mod
from repro.core import split as split_mod
from repro.core.async_agg import (Job, ParticipationSchedule,
                                  combine_arrivals, pop_arrivals,
                                  staleness_weight)
from repro.core.fedavg import evaluate, make_fns
from repro.core.heterogeneous import normalize_ranks
from repro.data import population as population_mod
from repro.data.loader import epoch_batches
from repro.peft import lora as lora_lib
from repro.privacy import dp as dp_mod
from repro.privacy.secure_agg import SecureAggSession


@dataclasses.dataclass
class FedResult:
    history: List[M.RoundMetrics]
    ledger: M.CommLedger
    final_lora: Dict
    client_flops: List[float]
    # rounds that failed the participation quorum and rolled over with
    # the global state unchanged (fault tolerance; 0 without a quorum)
    rollovers: int = 0

    @property
    def final_accuracy(self) -> float:
        return self.history[-1].accuracy if self.history else 0.0


def _to_jax(batch):
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _evaluate(ctx, lt):
    """The server's evaluation of ``lt`` on the test set."""
    with obs.span("fl.evaluate", round=ctx.rnd):
        return evaluate(ctx.fns, ctx.base, lt, ctx.test, ctx.eval_batch)


# --------------------------------------------------------------------------- #
# Privacy accounting (RDP accountant wiring)
# --------------------------------------------------------------------------- #
def make_accountant(fed: FedConfig, sample_rate: float = 1.0):
    """RDP accountant for the run, or None when DP is off entirely.

    ``sample_rate`` is the per-step subsampling rate q the engines
    report (batch_size / |local data|, worst case over clients) — the
    accountant applies subsampling amplification when q < 1.  A
    clipping-only run (dp_clip > 0, noise 0) gets an accountant whose
    epsilon is ``inf`` — the mechanism is active but offers no
    (eps, delta) guarantee, and reporting 0.0 would claim the strongest
    one instead."""
    if not fed.privacy.dp_enabled:
        return None
    from repro.privacy.accountant import GaussianAccountant
    return GaussianAccountant(fed.privacy.dp_noise_multiplier,
                              fed.privacy.dp_delta,
                              sample_rate=sample_rate)


def round_epsilon(acct, releases: int) -> float:
    """eps at the configured dp_delta after ``releases`` noisy uploads
    per client; 0.0 when DP is not enabled (no accounting, no claim),
    inf when clipping runs without noise."""
    return acct.epsilon(releases) if acct is not None else 0.0


def sample_rate(clients_data: List[Dict], batch_size: int) -> float:
    """Worst-case (largest) per-step subsampling rate over clients:
    q_i = batch_size / |client i's data|, clamped to 1."""
    return max(min(1.0, batch_size / max(len(d["tokens"]), 1))
               for d in clients_data)


# --------------------------------------------------------------------------- #
# Round context: everything the stages share
# --------------------------------------------------------------------------- #
class RoundContext:
    """Run-wide state threaded through every stage: config, data, the
    shared jitted steps, the metrics ledger/cost model, and the privacy
    middleware (accountant + secure-agg session + per-client release
    counters)."""

    def __init__(self, model, base, cfg: ModelConfig, fed: FedConfig,
                 targets, public, clients_data, test, task, batch_size,
                 eval_batch, verbose):
        self.model, self.base, self.cfg, self.fed = model, base, cfg, fed
        self.targets, self.public, self.test = targets, public, test
        # clients_data is a ClientPopulation (eager lists are wrapped at
        # the run_program boundary): indexable/len-able like the old
        # lists, but a lazy population materializes a shard only when a
        # stage actually touches ``clients_data[ci]``
        self.clients_data = population_mod.as_population(clients_data)
        self.task = task
        self.batch_size, self.eval_batch = batch_size, eval_batch
        self.verbose = verbose
        self.rnd = 0                        # the round in progress
        self.n_clients = len(self.clients_data)
        self.fns = make_fns(model, fed, task)
        self.ranks = normalize_ranks(fed.client_ranks, self.n_clients,
                                     fed.lora_rank)
        self.ledger = M.CommLedger()
        self.history: List[M.RoundMetrics] = []
        self.cost = [M.ClientCost() for _ in range(self.n_clients)]
        # per-client sample counts WITHOUT materializing shards (the
        # population knows its weights; for eager lists this is exactly
        # the old [len(d["tokens"]) for d in clients_data])
        self.data_w = self.clients_data.data_weights()
        self.total_w = float(sum(self.data_w))
        # worst-case subsampling rate from the weights — the arithmetic
        # twin of ``sample_rate`` that never touches client data
        self.acct = make_accountant(
            fed, max(min(1.0, batch_size / max(w, 1))
                     for w in self.data_w))
        self.secagg = SecureAggSession(fed)
        self.releases = [0] * self.n_clients   # noisy uploads per client
        # (rnd, ci) -> secure-agg masking-cohort id, populated by the
        # streaming driver (per-chunk cohorts); empty under the flat
        # engines, where the masking cohort is keyed by the start round
        self._cohort_ids: Dict[tuple, int] = {}

    def secagg_start(self, rnd: int, ci: int) -> int:
        """The secure-agg cohort key for client ``ci``'s job started in
        ``rnd`` — the per-chunk cohort id under cohort streaming, the
        start round itself (identity) everywhere else."""
        return self._cohort_ids.get((rnd, ci), rnd)


# --------------------------------------------------------------------------- #
# Schedules: when does an upload arrive at the server?
# --------------------------------------------------------------------------- #
class SyncSchedule:
    """The paper-literal parameter-server round: every client starts a
    job each round and its upload arrives the same round."""

    def __init__(self, fed: FedConfig, n_clients: int):
        self.n = n_clients
        self._pending = []

    def starters(self, rnd: int) -> List[int]:
        return list(range(self.n))

    def submit(self, rnd: int, ci: int, payload, extra_delay: int = 0):
        """``extra_delay`` is a fault-injected straggler lag: the upload
        arrives that many rounds late and flows through the staleness
        weighting like any async arrival."""
        self._pending.append(Job(ci, rnd, rnd + extra_delay, payload))

    def pop_arrivals(self, rnd: int):
        out = sorted((j for j in self._pending if j.arrival == rnd),
                     key=lambda j: j.client)
        self._pending = [j for j in self._pending if j.arrival != rnd]
        return out

    # -- checkpoint/resume (checkpoint/federated.py) --------------------- #
    def jobs(self):
        return list(self._pending)

    def load_jobs(self, jobs):
        self._pending = list(jobs)

    def rng_state(self):
        return None

    def load_rng_state(self, state):
        pass


class AsyncSchedule:
    """FedAsync-style participation: a free client starts a job (pulls
    the current global, trains NOW) and the upload goes in flight for a
    seeded per-job delay (core/async_agg.ParticipationSchedule)."""

    def __init__(self, fed: FedConfig, n_clients: int):
        self.n = n_clients
        self.sched = ParticipationSchedule(n_clients, fed.seed + 17,
                                           fed.max_staleness)
        self.in_flight: Dict[int, object] = {}

    def starters(self, rnd: int) -> List[int]:
        return [ci for ci in range(self.n) if ci not in self.in_flight]

    def submit(self, rnd: int, ci: int, payload, extra_delay: int = 0):
        self.in_flight[ci] = Job(
            ci, rnd, rnd + self.sched.next_delay(ci) + extra_delay, payload)

    def pop_arrivals(self, rnd: int):
        return pop_arrivals(self.in_flight, rnd)

    # -- checkpoint/resume (checkpoint/federated.py) --------------------- #
    def jobs(self):
        return [self.in_flight[ci] for ci in sorted(self.in_flight)]

    def load_jobs(self, jobs):
        self.in_flight = {j.client: j for j in jobs}

    def rng_state(self):
        return self.sched.state()

    def load_rng_state(self, state):
        self.sched.load_state(state)


def make_schedule(fed: FedConfig, n_clients: int):
    return (SyncSchedule if fed.aggregation == "sync"
            else AsyncSchedule)(fed, n_clients)


# --------------------------------------------------------------------------- #
# Executors: how per-client work runs
# --------------------------------------------------------------------------- #
class SequentialExecutor:
    """Python loop over clients, one jitted step per batch — the
    paper-literal reference and the numerical ground truth."""

    backend = "sequential"
    streaming = False

    def __init__(self, ctx: RoundContext, mesh=None):
        self.ctx = ctx                      # mesh ignored: nothing stacked

    # -- shared local fine-tune body (FedLLM a2 / KD b1) ----------------- #
    def _local_finetune(self, program, ci, lt, opt, rnd):
        """One client's epochs of jitted train steps; returns
        (lt, opt, n_tok).  The single loop both the FedLLM and KD
        stages call, so a change to the local update (seed formula,
        privacy hook, ...) can never apply to one framework only."""
        ctx, fed, fns = self.ctx, self.ctx.fed, self.ctx.fns
        r = rng_mod.local_rng(fed, rnd, ci)
        n_tok = 0
        for ep in range(fed.local_epochs):
            for batch in epoch_batches(
                    ctx.clients_data[ci], ctx.batch_size,
                    seed=fed.seed * program.epoch_seed_mult + rnd + ep):
                r, sub = jax.random.split(r)
                lt, opt, _ = fns["train_step"](ctx.base, lt, opt,
                                               _to_jax(batch), sub)
                n_tok += batch["tokens"].size
        return lt, opt, n_tok

    # -- FedLLM a2 ------------------------------------------------------ #
    def train(self, program, jobs, rnd):
        """jobs: [(ci, lt)] -> [(new_lt, n_tok)] in job order."""
        out = []
        for ci, lt in jobs:
            lt, _, n_tok = self._local_finetune(
                program, ci, lt, self.ctx.fns["opt_init"](lt), rnd)
            out.append((lt, n_tok))
        return out

    # -- KD b1 + b2 ----------------------------------------------------- #
    def kd_train_and_logits(self, program, cis, rnd):
        ctx = self.ctx
        out = []
        for ci in cis:
            lt, opt, n_tok = self._local_finetune(
                program, ci, program.lts[ci], program.opts[ci], rnd)
            program.lts[ci], program.opts[ci] = lt, opt
            out.append((kd_mod.client_logits(ctx.fns, ctx.base, lt,
                                             ctx.public, ctx.eval_batch),
                        n_tok))
        return out

    # -- KD b8 ---------------------------------------------------------- #
    def kd_distill(self, program, cis, glob, rnd):
        ctx, fed = self.ctx, self.ctx.fed
        for ci in cis:
            program.lts[ci], program.opts[ci], _ = kd_mod.distill(
                ctx.fns, ctx.base, program.lts[ci], program.opts[ci],
                ctx.public, glob, fed.kd_epochs, ctx.eval_batch,
                seed=fed.seed + 31 * rnd + ci)

    # -- Split c1-c5 (server half threads through in visit order) ------- #
    def split_train(self, program, jobs, rnd):
        """jobs: [(ci, c_init)] -> [(c_lt, n_tok, n_steps, shape)]."""
        ctx, fed = self.ctx, self.ctx.fed
        sfns = program.sfns
        out = []
        for ci, c_init in jobs:
            c_lt, c_opt = c_init, sfns["opt_init"](c_init)
            r = rng_mod.local_rng(fed, rnd, ci)
            n_tok, n_steps, shape = 0, 0, None
            for batch in epoch_batches(
                    ctx.clients_data[ci], ctx.batch_size,
                    seed=fed.seed * program.epoch_seed_mult + rnd):
                r, sub = jax.random.split(r)
                nkey = dp_mod.noise_key(fed, rnd, ci, n_steps) \
                    if fed.privacy.dp_enabled else None
                c_lt, program.s_lt, c_opt, program.s_opt, _ = \
                    sfns["split_train_step"](
                        program.base_c, program.base_s, c_lt, program.s_lt,
                        c_opt, program.s_opt, _to_jax(batch), sub, nkey)
                n_tok += batch["tokens"].size
                n_steps += 1
                shape = batch["tokens"].shape
            out.append((c_lt, n_tok, n_steps, shape))
        return out


class SpmdExecutor:
    """Ready-set stacked on a leading client axis, one jitted program
    per rank bucket (``fed_spmd``).  Split fuses only contiguous
    equal-rank runs (``rank_segments``) so the shared server half keeps
    the paper's client visit order.  With ``mesh`` set, stacked inputs
    are placed with explicit client-axis NamedShardings
    (launch/sharding.py) so the client dimension shards over the mesh's
    pod/data axes in a real run."""

    backend = "spmd"
    streaming = False

    def __init__(self, ctx: RoundContext, mesh=None):
        from repro.launch.mesh import auto_axes
        self.ctx = ctx
        self.mesh = None if mesh is None else auto_axes(mesh)
        self._bucket_update = None
        self._kfns = None
        self._seg_step = None

    # -- mesh placement ------------------------------------------------- #
    # With a mesh, every operand of a stacked program lives on it: the
    # client-stacked ones sharded on the client axis, the shared ones
    # (base weights, public batches, the global teacher) replicated, so
    # a jitted call never mixes one-device and mesh-placed arguments.
    # What a stacked program returns goes back to the default device
    # before it leaves the executor: the server's programs (aggregation,
    # evaluation, upload compression) run there, and a Pallas TPU kernel
    # fed mesh-placed operands would need a partitioning it cannot have.
    def _shard(self, *trees):
        if self.mesh is not None:
            from repro.launch.sharding import shard_client_tree
            trees = tuple(shard_client_tree(self.mesh, t) for t in trees)
        return trees if len(trees) > 1 else trees[0]

    def _shared(self, tree):
        if self.mesh is None:
            return tree
        from repro.launch.sharding import replicate_tree
        return replicate_tree(self.mesh, tree)

    def _home(self, *trees):
        if self.mesh is not None:
            trees = jax.device_put(trees, jax.devices()[0])
        return trees if len(trees) > 1 else trees[0]

    @functools.cached_property
    def _base(self):
        return self._shared(self.ctx.base)

    # -- FedLLM a2 ------------------------------------------------------ #
    def train(self, program, jobs, rnd):
        ctx, fed, fns = self.ctx, self.ctx.fed, self.ctx.fns
        if self._bucket_update is None:
            self._bucket_update = fed_spmd.make_bucket_update(
                ctx.model, fed, ctx.task, mesh=self.mesh)
        by_ci = dict(jobs)
        seeds = [fed.seed * program.epoch_seed_mult + rnd + ep
                 for ep in range(fed.local_epochs)]
        results = {}
        for rank, cis in fed_spmd.rank_buckets(ctx.ranks, list(by_ci)):
            stacked_lt = fed_spmd.stack_trees([by_ci[ci] for ci in cis])
            stacked_opt = fed_spmd.stack_for_clients(
                fns["opt_init"](by_ci[cis[0]]), len(cis))
            batches, valid, n_tok = fed_spmd.stack_client_batches(
                [ctx.clients_data[ci] for ci in cis], ctx.batch_size, seeds)
            keys = rng_mod.grid_keys(fed, rnd, cis, valid.shape[1])
            stacked_lt, stacked_opt, batches, keys, valid_m = self._shard(
                stacked_lt, stacked_opt, batches, keys, jnp.asarray(valid))
            with obs.span("fl.exec.update", round=rnd, clients=len(cis)):
                new_lt, _, _ = self._bucket_update(
                    self._base, stacked_lt, stacked_opt, batches, keys,
                    valid_m)
            for k, (ci, t) in enumerate(
                    zip(cis, fed_spmd.unstack_tree(self._home(new_lt)))):
                results[ci] = (t, n_tok[k])
        return [results[ci] for ci, _ in jobs]

    # -- KD b1 + b2 ----------------------------------------------------- #
    def kd_train_and_logits(self, program, cis, rnd):
        ctx, fed = self.ctx, self.ctx.fed
        if self._kfns is None:
            self._kfns = fed_spmd.make_kd_spmd_fns(ctx.model, fed, ctx.task,
                                                   mesh=self.mesh)
        kfns, lts, opts = self._kfns, program.lts, program.opts
        seeds = [fed.seed * program.epoch_seed_mult + rnd + ep
                 for ep in range(fed.local_epochs)]
        results = {}
        for rank, bcis in fed_spmd.rank_buckets(ctx.ranks, cis):
            sl = fed_spmd.stack_trees([lts[ci] for ci in bcis])
            so = fed_spmd.stack_trees([opts[ci] for ci in bcis])
            batches, valid, n_tok = fed_spmd.stack_client_batches(
                [ctx.clients_data[ci] for ci in bcis], ctx.batch_size,
                seeds)
            keys = rng_mod.grid_keys(fed, rnd, bcis, valid.shape[1])
            sl, so, batches, keys, valid_m = self._shard(
                sl, so, batches, keys, jnp.asarray(valid))
            with obs.span("fl.exec.update", round=rnd, clients=len(bcis)):
                sl, so, _ = kfns["client_update"](self._base, sl, so,
                                                  batches, keys, valid_m)
                logits = self._public_logits(sl)
            sl, so, logits = self._home(sl, so, logits)
            for k, (ci, lt, opt) in enumerate(zip(
                    bcis, fed_spmd.unstack_tree(sl),
                    fed_spmd.unstack_tree(so))):
                lts[ci], opts[ci] = lt, opt
                results[ci] = (logits[k], n_tok[k])
        return [results[ci] for ci in cis]

    # -- KD b8 ---------------------------------------------------------- #
    def kd_distill(self, program, cis, glob, rnd):
        lts, opts = program.lts, program.opts
        for rank, bcis in fed_spmd.rank_buckets(self.ctx.ranks, cis):
            sl = fed_spmd.stack_trees([lts[ci] for ci in bcis])
            so = fed_spmd.stack_trees([opts[ci] for ci in bcis])
            sl, so = self._shard(sl, so)
            with obs.span("fl.exec.update", round=rnd, clients=len(bcis)):
                sl, so = self._distill(sl, so, glob, rnd, bcis)
            sl, so = self._home(sl, so)
            for ci, lt, opt in zip(bcis, fed_spmd.unstack_tree(sl),
                                   fed_spmd.unstack_tree(so)):
                lts[ci], opts[ci] = lt, opt

    # -- KD b2/b6 and b8 over a client stack ---------------------------- #
    def _public_logits(self, stacked_lt):
        """b2/b6 for every client at once — same batch order and
        original-row-order scatter as kd.client_logits, giving (C, N, D)
        with row i holding public sample i's logits."""
        ctx = self.ctx
        outs = []
        for batch in epoch_batches(ctx.public, ctx.eval_batch, seed=0,
                                   drop_remainder=False):
            outs.append(self._kfns["batched_logits"](
                self._base, stacked_lt, self._shared(_to_jax(batch))))
        stacked = jnp.concatenate(outs, axis=1)
        perm = jnp.asarray(kd_mod._epoch_perm(len(ctx.public["tokens"]), 0))
        return jnp.zeros_like(stacked).at[:, perm].set(stacked)

    def _distill(self, stacked_lt, stacked_opt, teacher, rnd, client_ids):
        """b8 for every client in a (bucket-)stack at once; per-client
        RNG streams match the sequential executor's
        PRNGKey(seed + 31r + ci)."""
        ctx, fed = self.ctx, self.ctx.fed
        rngs = self._shard(jnp.stack(
            [jax.random.PRNGKey(fed.seed + 31 * rnd + ci)
             for ci in client_ids]))
        n = len(ctx.public["tokens"])
        for ep in range(fed.kd_epochs):
            perm = kd_mod._epoch_perm(n, ep)
            start = 0
            for batch in epoch_batches(ctx.public, ctx.eval_batch, seed=ep,
                                       drop_remainder=False):
                sel = perm[start:start + len(batch["tokens"])]
                start += len(batch["tokens"])
                jb, t = self._shared((_to_jax(batch),
                                      jnp.asarray(teacher[sel])))
                rngs, subs = fed_spmd.split_each(rngs)
                stacked_lt, stacked_opt, _ = self._kfns["batched_kd_step"](
                    self._base, stacked_lt, stacked_opt, jb, t, subs)
        return stacked_lt, stacked_opt

    # -- Split segments (server carry threads segment-after-segment) ---- #
    def split_train(self, program, jobs, rnd):
        ctx, fed = self.ctx, self.ctx.fed
        if self._seg_step is None:
            self._seg_step = jax.jit(fed_spmd.make_split_spmd_segment(
                ctx.model, fed, ctx.task, sfns=program.sfns))
        by_ci = dict(jobs)
        noised = fed.privacy.noise_std > 0.0
        results = {}
        # NOTE: the client axis of a split segment is *scanned* (shared
        # server carry), so it is never mesh-sharded — only the batch
        # dims inside a step shard (see SplitProgram.spmd_round for the
        # client-sharded cc2 reduction in the launch artifact).
        for rank, cis in fed_spmd.rank_segments(ctx.ranks, list(by_ci)):
            batches, valid, n_tok = fed_spmd.stack_client_batches(
                [ctx.clients_data[ci] for ci in cis], ctx.batch_size,
                [fed.seed * program.epoch_seed_mult + rnd])
            keys = rng_mod.grid_keys(fed, rnd, cis, valid.shape[1])
            extra = (dp_mod.noise_key_grid(fed, rnd, cis,
                                           valid.shape[1]),) if noised \
                else ()
            with obs.span("fl.exec.update", round=rnd, clients=len(cis)):
                stacked_c, program.s_lt, program.s_opt, _ = self._seg_step(
                    program.base_c, program.base_s, by_ci[cis[0]],
                    program.s_lt, program.s_opt, batches, keys,
                    jnp.asarray(valid), *extra)
            shape = tuple(batches["tokens"].shape[-2:])
            for k, (ci, t) in enumerate(
                    zip(cis, fed_spmd.unstack_tree(stacked_c))):
                results[ci] = (t, n_tok[k], int(valid[k].sum()), shape)
        return [results[ci] for ci, _ in jobs]


class CohortStreamingExecutor(SpmdExecutor):
    """The million-virtual-client executor (``backend="cohort"``): the
    per-chunk compute IS the SPMD executor's — the driver streams the
    round's ready set through it ``FedConfig.cohort_size`` clients at a
    time and folds partial aggregates between chunks with the jitted
    donated-buffer folds below, so peak memory is one cohort.  jit
    caches the stacked programs per (chunk size, rank, n_steps)
    signature, so every full-size chunk reuses one compile."""

    backend = "cohort"
    streaming = True


# -- streaming partial-aggregate folds -------------------------------------- #
# One jitted fold, accumulator donated: the python loop over cohorts
# re-uses the accumulator's buffers instead of materializing a new tree
# per chunk (the "donated-buffer python loop" variant of lax.scan-ing
# the cohort stream — chunk payloads live on the host, so a scan over
# them would have to materialize the full fleet first).
@functools.partial(jax.jit, donate_argnums=(0,))
def _fold_add(acc, tree, w):
    w = jnp.asarray(w, jnp.float32)
    return jax.tree.map(lambda a, x: a + w * x.astype(jnp.float32),
                        acc, tree)


def _fold_zeros(tree):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def _cohort_chunks(seq, size: int):
    """Chunk a client-id/job sequence into cohorts (<=0: one chunk)."""
    seq = list(seq)
    if size <= 0 or size >= len(seq):
        return [seq] if seq else []
    return [seq[i:i + size] for i in range(0, len(seq), size)]


def _cohort_uid(rnd: int, idx: int) -> int:
    """Unique masking-cohort id for chunk ``idx`` of round ``rnd`` —
    keys SecureAggSession cohorts and seeds their pairwise masks, so it
    only needs to be deterministic and collision-free across the run
    (chunk counts are far below the 1e6 stride)."""
    return rnd * 1_000_003 + idx


def _stream_fold_params(ctx, state, kept, global_tree):
    """Shared FedLLM/Split a4/cc2 fold: one masking group of arrivals
    into the running staleness-weighted parameter sum.  The zeropad
    hetero path is linear per leaf, so it streams chunk-by-chunk in one
    fp32 accumulator; svd re-factorization is not, so it buffers the
    round's arrivals instead (documented O(arrivals-this-round)
    exception to the one-cohort memory bound)."""
    fed = ctx.fed
    if not kept:
        return state
    # non-linear combines (svd re-factorization, robust order
    # statistics) cannot stream; buffer the round's arrivals instead
    if fed.robust_agg != "mean" or (
            fed.hetero_agg == "svd" and any(r != fed.lora_rank
                                            for r in ctx.ranks)):
        if state is None:
            state = ("buf", [])
        state[1].extend(kept)
        return state
    if state is None:
        state = ("sum", _fold_zeros(global_tree), 0.0, 0.0)
    _, acc, w_sum, raw = state
    for ci, tree, s, w in kept:
        if ctx.ranks[ci] != fed.lora_rank:
            tree = lora_lib.pad_rank(tree, fed.lora_rank)
        ws = w * staleness_weight(s, fed.staleness_decay)
        acc = _fold_add(acc, tree, ws)
        w_sum += ws
        raw += w
    return ("sum", acc, w_sum, raw)


def _finalize_param_fold(ctx, state, global_tree):
    """Close a ``_stream_fold_params`` round: anchor the absent data
    mass on the current global (the same convex combination
    ``stale_weighted_avg`` forms) and normalize.  Returns the new
    global tree — ``global_tree`` untouched when nothing was kept."""
    if state is None:
        return global_tree
    if state[0] == "buf":
        return combine_arrivals(global_tree, state[1], ctx.total_w,
                                ctx.fed, ctx.ranks)
    _, acc, w_sum, raw = state
    absent = ctx.total_w - raw
    if absent > 0:
        acc = _fold_add(acc, global_tree, absent)
        w_sum += absent
    return jax.tree.map(
        lambda a, g: (a / np.float32(w_sum)).astype(g.dtype),
        acc, global_tree)


class _LazyClientState:
    """List-like per-client state materialized on first touch.  The
    eager engines touch every index up front, reproducing the old
    list-of-all-clients bit-for-bit; under cohort streaming over a lazy
    population only participants ever materialize (KD is inherently
    per-client-stateful — a touched client's adapter IS retained after
    its cohort, the documented exception to statelessness)."""

    def __init__(self, n: int, factory):
        self._n = int(n)
        self._factory = factory
        self._vals: Dict[int, object] = {}

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, ci):
        if ci not in self._vals:
            self._vals[ci] = self._factory(ci)
        return self._vals[ci]

    def __setitem__(self, ci, val):
        self._vals[ci] = val


EXECUTORS = {"sequential": SequentialExecutor, "spmd": SpmdExecutor,
             "cohort": CohortStreamingExecutor}


# --------------------------------------------------------------------------- #
# Framework stage-specs
# --------------------------------------------------------------------------- #
class FrameworkProgram:
    """One framework's stage bodies, composed by ``run_program``.  A
    subclass gives ``broadcast``, ``local_update``, ``upload``,
    ``record_arrival``, the ``agg_fold`` / ``agg_finalize`` pair,
    ``edge_payload_bytes``, ``evaluate``, ``final_state``,
    ``state_dict`` / ``load_state_dict`` for crash recovery, and the
    launch layer's ``spmd_round``.  The aggregation stage itself is the
    base's ``aggregate``, the same on every backend."""

    def aggregate(self, ctx, ex, kept, arrived, rnd):
        """The server's aggregation stage, called once a round.
        ``kept`` holds the round's kept arrivals ``(client, payload,
        staleness, data weight)`` in one group per secure-agg masking
        cohort (one per start round on the flat backends, so a single
        group under sync; one per start chunk under cohort streaming);
        each group folds into the running state in turn, then
        ``agg_finalize`` closes the round over the ``arrived`` client
        ids.  Both are empty when the round rolls over."""
        state = self.agg_init(ctx)
        for group in kept:
            state = self.agg_fold(ctx, ex, state, group, rnd)
        self.agg_finalize(ctx, ex, state, arrived, rnd)

    def agg_init(self, ctx):
        return None

    def payload_bytes(self, ctx, payload) -> int:
        return M.tree_bytes(payload)

    def payload_arrays(self, payload):
        return jax.tree.leaves(payload)


class FedLLMProgram(FrameworkProgram):
    """FedLLMs (paper SSII.A): a1 broadcast global LoRA params, a2 local
    PEFT fine-tuning, a3 upload the tuned params, a4 FedAvg."""

    name = "fedllm"
    epoch_seed_mult = 997

    def __init__(self, ctx: RoundContext):
        key = jax.random.PRNGKey(ctx.fed.seed + 1)
        self.global_lt = lora_lib.init_lora(key, ctx.base, ctx.targets,
                                            ctx.fed.lora_rank,
                                            ctx.fed.lora_alpha)

    def broadcast(self, ctx, cohort, rnd):
        jobs = []
        for ci in cohort:
            lt = lora_lib.maybe_truncate_rank(self.global_lt, ctx.ranks[ci],
                                              ctx.fed.lora_rank)
            ctx.ledger.record(rnd, ci, "lora_params", M.DOWN,
                              M.tree_bytes(lt))
            jobs.append((ci, lt))
        return jobs

    def local_update(self, ctx, ex, jobs, rnd):
        outs = ex.train(self, jobs, rnd)
        for (ci, _), (new_lt, n_tok) in zip(jobs, outs):
            ctx.cost[ci].add_train(ctx.cfg, n_tok,
                                   lora_lib.n_params(new_lt))
        return [(ci, new_lt)
                for (ci, _), (new_lt, _) in zip(jobs, outs)]

    def upload(self, ctx, outs, rnd):
        payloads = []
        for ci, lt in outs:
            lt = dp_mod.privatize_tree(lt, dp_mod.noise_key(ctx.fed, rnd,
                                                            ci),
                                       ctx.fed.privacy.noise_std)
            ctx.secagg.collect(ctx.secagg_start(rnd, ci), ci, lt)
            ctx.releases[ci] += 1
            payloads.append((ci, lt))
        return payloads

    def record_arrival(self, ctx, job, rnd):
        ctx.ledger.record(rnd, job.client, "lora_params", M.UP,
                          M.tree_bytes(job.payload))
        if ctx.fed.privacy.dp_enabled:
            ctx.ledger.record(rnd, job.client, "dp_meta", M.UP,
                              M.DP_META_BYTES)

    # -- a4: the parameter fold ---------------------------------------- #
    def agg_fold(self, ctx, ex, state, kept, rnd):
        return _stream_fold_params(ctx, state, kept, self.global_lt)

    def agg_finalize(self, ctx, ex, state, arrived, rnd):
        self.global_lt = _finalize_param_fold(ctx, state, self.global_lt)

    def edge_payload_bytes(self, ctx) -> int:
        return M.tree_bytes(self.global_lt)

    def evaluate(self, ctx):
        return _evaluate(ctx, self.global_lt)

    def final_state(self, ctx):
        return self.global_lt

    # -- checkpoint/resume (checkpoint/federated.py) --------------------- #
    def state_dict(self, ctx):
        return {"global_lt": self.global_lt}

    def load_state_dict(self, ctx, st):
        self.global_lt = st["global_lt"]

    @staticmethod
    def spmd_round(model, fed: FedConfig, task: str = "classification",
                   n_edges: int = 1):
        """The jittable whole-round program for the launch layer: the
        vmapped local scans plus the client-axis FedAvg all-reduce —
        the two-hop per-edge partial sum + cross-edge tree reduce
        (``fed_spmd.hierarchical_client_mean``) when ``n_edges > 1``."""
        return fed_spmd.make_spmd_round(model, fed, task, n_edges=n_edges)


class KDProgram(FrameworkProgram):
    """KD-FedLLMs (paper SSII.B): params never cross the wire — clients
    upload public-set logits (b3), the server fuses knowledge (b4),
    distills (b5), and re-broadcasts global knowledge (b6-b8)."""

    name = "kd"
    epoch_seed_mult = 991

    def __init__(self, ctx: RoundContext):
        fed = ctx.fed
        key = jax.random.PRNGKey(fed.seed + 2)
        # per-client adapters/optimizers materialize on first
        # participation — the same fold_in(key, ci) init as the old
        # eager lists (bit-identical values), but a million-virtual-
        # client run only ever allocates the clients that train
        self.lts = _LazyClientState(
            ctx.n_clients,
            lambda ci: lora_lib.init_lora(jax.random.fold_in(key, ci),
                                          ctx.base, ctx.targets,
                                          ctx.ranks[ci], fed.lora_alpha))
        self.opts = _LazyClientState(
            ctx.n_clients, lambda ci: ctx.fns["opt_init"](self.lts[ci]))
        self.server_lt = lora_lib.init_lora(jax.random.fold_in(key, 999),
                                            ctx.base, ctx.targets,
                                            fed.lora_rank, fed.lora_alpha)
        self.server_opt = ctx.fns["opt_init"](self.server_lt)
        self.n_lora = _LazyClientState(
            ctx.n_clients, lambda ci: lora_lib.n_params(self.lts[ci]))
        self.glob = None            # latest global knowledge (b6)
        self.pub_tok = ctx.public["tokens"].size

    def broadcast(self, ctx, cohort, rnd):
        return list(cohort)         # no param download in KD

    def local_update(self, ctx, ex, jobs, rnd):
        outs = ex.kd_train_and_logits(self, jobs, rnd)
        for ci, (_, n_tok) in zip(jobs, outs):
            ctx.cost[ci].add_train(ctx.cfg, n_tok, self.n_lora[ci])
            ctx.cost[ci].add_fwd(ctx.cfg, self.pub_tok)
        return [(ci, logits) for ci, (logits, _) in zip(jobs, outs)]

    def upload(self, ctx, outs, rnd):
        payloads = []
        for ci, logits in outs:
            logits = dp_mod.privatize_logits(
                logits, dp_mod.noise_key(ctx.fed, rnd, ci), ctx.fed)
            lg, wire = kd_mod.compress_for_wire(logits, ctx.fed)
            ctx.secagg.collect(ctx.secagg_start(rnd, ci), ci, lg)
            ctx.releases[ci] += 1
            payloads.append((ci, (lg, wire)))
        return payloads

    def record_arrival(self, ctx, job, rnd):
        ctx.ledger.record(rnd, job.client, "logits", M.UP, job.payload[1])
        if ctx.fed.privacy.dp_enabled:
            ctx.ledger.record(rnd, job.client, "dp_meta", M.UP,
                              M.DP_META_BYTES)

    def payload_bytes(self, ctx, payload) -> int:
        return payload[1]

    def payload_arrays(self, payload):
        return [payload[0]]

    # -- b4-b8 ---------------------------------------------------------- #
    def agg_fold(self, ctx, ex, state, kept, rnd):
        """Fold one masking group's logits into the running b4 teacher
        sum (the weighted mean is linear, so it streams exactly).  A
        robust combine is not linear, so it buffers the round's
        arrivals instead — the same documented O(arrivals-this-round)
        exception the svd harmonizer makes."""
        if not kept:
            return state
        if ctx.fed.robust_agg != "mean":
            if state is None:
                state = ["buf", []]
            state[1].extend(kept)
            return state
        if state is None:
            state = [None, 0.0]
        acc, w_sum = state
        for ci, p, s, w in kept:
            lg = jnp.asarray(p[0])
            ws = w * staleness_weight(s, ctx.fed.staleness_decay)
            acc = _fold_add(acc if acc is not None else _fold_zeros(lg),
                            lg, ws)
            w_sum += ws
        return [acc, w_sum]

    def agg_finalize(self, ctx, ex, state, arrived, rnd):
        """b5 server distill from the normalized teacher, then the
        b6-b8 re-sync of the arrived clients against the latest global
        knowledge — streamed in cohort-sized chunks under cohort
        streaming (one stacked distill program per chunk)."""
        fed = ctx.fed
        if state is not None and isinstance(state[0], str):   # robust buffer
            ws = [w * staleness_weight(s, fed.staleness_decay)
                  for _, _, s, w in state[1]]
            state = [fed_spmd.robust_client_combine(
                jnp.stack([jnp.asarray(p[0], jnp.float32)
                           for _, p, _, _ in state[1]]),
                jnp.asarray(ws, jnp.float32), fed.robust_agg,
                fed.trim_frac, fed.clip_norm), 1.0]
        if state is not None and state[1] > 0:
            teacher = (state[0] / np.float32(state[1])).astype(jnp.float32)
            self.server_lt, self.server_opt, _ = kd_mod.distill(
                ctx.fns, ctx.base, self.server_lt, self.server_opt,
                ctx.public, teacher, fed.kd_epochs, ctx.eval_batch,
                seed=fed.seed + rnd)
            self.glob = kd_mod.client_logits(ctx.fns, ctx.base,
                                             self.server_lt, ctx.public,
                                             ctx.eval_batch)
        if arrived and self.glob is not None:
            glob_wire = kd_mod.logit_wire_bytes(self.glob.shape, fed)
            for chunk in _cohort_chunks(
                    arrived, fed.cohort_size if ex.streaming else 0):
                for ci in chunk:
                    ctx.ledger.record(rnd, ci, "logits", M.DOWN, glob_wire)
                    ctx.cost[ci].add_train(ctx.cfg,
                                           self.pub_tok * fed.kd_epochs,
                                           self.n_lora[ci])
                ex.kd_distill(self, chunk, self.glob, rnd)

    def edge_payload_bytes(self, ctx) -> int:
        if self.glob is None:
            return 0
        return kd_mod.logit_wire_bytes(self.glob.shape, ctx.fed)

    def evaluate(self, ctx):
        return _evaluate(ctx, self.server_lt)

    def final_state(self, ctx):
        return self.server_lt

    # -- checkpoint/resume (checkpoint/federated.py) --------------------- #
    def state_dict(self, ctx):
        """Only the *materialized* client adapters are snapshotted —
        untouched clients re-materialize from the fold_in(key, ci)
        factory bit-identically on resume."""
        return {"lts": dict(self.lts._vals), "opts": dict(self.opts._vals),
                "server_lt": self.server_lt, "server_opt": self.server_opt,
                "glob": self.glob}

    def load_state_dict(self, ctx, st):
        self.lts._vals = dict(st["lts"])
        self.opts._vals = dict(st["opts"])
        self.server_lt = st["server_lt"]
        self.server_opt = st["server_opt"]
        self.glob = st["glob"]

    @staticmethod
    def spmd_round(model, fed: FedConfig, task: str = "classification"):
        """The jittable whole-round program for the launch layer:
        vmapped b1 local update, batched b2 public logits (with the b3
        privacy mechanism when configured), b4 client-axis knowledge
        reduction, b5 server distillation, b6 global logits and vmapped
        b8 client distillation — one program."""
        fns = make_fns(model, fed, task)
        local_update = fed_spmd.make_local_update(model, fed, task)
        noised = fed.privacy.noise_std > 0.0

        def kd_round_core(base, slt, sopt, server_lt, server_opt, batches,
                          keys, valid, weights, public_batch, client_keys,
                          server_key, noise_keys=None):
            slt, sopt, _ = jax.vmap(
                local_update, in_axes=(None, 0, 0, 0, 0, 0))(
                    base, slt, sopt, batches, keys, valid)
            logits = jax.vmap(fns["logits_fn"], in_axes=(None, 0, None))(
                base, slt, public_batch)                   # (C, Bp, D)
            if fed.privacy.dp_enabled:
                # b3 mechanism: per-client row-clipped noisy knowledge
                if noised:
                    logits = jax.vmap(
                        lambda lg, k: dp_mod.privatize_rows(lg, k, fed))(
                            logits, noise_keys)
                else:
                    logits = dp_mod.privatize_rows(logits, None, fed)
            if fed.robust_agg != "mean":
                teacher = fed_spmd.robust_client_combine(
                    logits.astype(jnp.float32), weights, fed.robust_agg,
                    fed.trim_frac, fed.clip_norm)
            else:
                teacher = kd_mod.aggregate_knowledge_batched(logits,
                                                             weights)
            server_lt, server_opt, _ = fns["kd_step"](
                base, server_lt, server_opt, public_batch, teacher,
                server_key)
            glob = fns["logits_fn"](base, server_lt, public_batch)
            slt, sopt, _ = jax.vmap(
                fns["kd_step"], in_axes=(None, 0, 0, None, None, 0))(
                    base, slt, sopt, public_batch, glob, client_keys)
            return slt, sopt, server_lt, server_opt

        return kd_round_core


class SplitProgram(FrameworkProgram):
    """Split-FedLLMs (paper SSII.C): c1-c5 split training (activations
    up, gradients down, server half in the loop) plus the cc1-cc4
    FedAvg of the *client-side* adapters."""

    name = "split"
    epoch_seed_mult = 983

    def __init__(self, ctx: RoundContext):
        fed, cfg = ctx.fed, ctx.cfg
        self.sfns = split_mod.make_split_fns(ctx.model, fed, ctx.task)
        L = self.sfns["n_client_groups"]
        key = jax.random.PRNGKey(fed.seed + 3)
        full_lt = lora_lib.init_lora(key, ctx.base, ctx.targets,
                                     fed.lora_rank, fed.lora_alpha)
        self.c_global, self.s_lt = split_mod.split_lora(full_lt, L)
        self.base_c, self.base_s = split_mod.split_base(
            ctx.base, L, cfg.is_encoder_decoder)
        self.s_opt = self.sfns["opt_init"](self.s_lt)
        self.frac_client = L / max(self.sfns["n_groups"], 1)
        self.label_bytes = ctx.batch_size * 4 \
            if "labels" in ctx.clients_data[0] else 0
        self.joined = full_lt

    def broadcast(self, ctx, cohort, rnd):
        jobs = []
        for ci in cohort:
            c_init = lora_lib.maybe_truncate_rank(
                self.c_global, ctx.ranks[ci], ctx.fed.lora_rank)
            ctx.ledger.record(rnd, ci, "lora_params", M.DOWN,
                              M.tree_bytes(c_init))                    # cc3
            jobs.append((ci, c_init))
        return jobs

    def local_update(self, ctx, ex, jobs, rnd):
        outs = ex.split_train(self, jobs, rnd)
        priv = ctx.fed.privacy
        res = []
        for (ci, _), (c_lt, n_tok, n_steps, shape) in zip(jobs, outs):
            if n_steps:          # a sub-batch-size client trains 0 steps
                up, down = self.sfns["wire_bytes_per_batch"](shape)
                for _ in range(n_steps):
                    ctx.ledger.record(rnd, ci, "activations", M.UP,
                                      up + self.label_bytes)           # c2
                    ctx.ledger.record(rnd, ci, "act_grads", M.DOWN,
                                      down)                            # c4
                    if priv.dp_enabled:
                        ctx.ledger.record(rnd, ci, "dp_meta", M.UP,
                                          M.DP_META_BYTES)
            ctx.releases[ci] += n_steps     # per-client c2 noise events
            ctx.cost[ci].add_train(ctx.cfg, n_tok,
                                   lora_lib.n_params(c_lt),
                                   frac_layers=self.frac_client)
            res.append((ci, c_lt))
        return res

    def upload(self, ctx, outs, rnd):
        # the c2 activation noise is Split's DP mechanism (inside the
        # step); the cc1 adapter upload is masked but not noised
        for ci, c_lt in outs:
            ctx.secagg.collect(ctx.secagg_start(rnd, ci), ci, c_lt)
        return outs

    def record_arrival(self, ctx, job, rnd):
        ctx.ledger.record(rnd, job.client, "lora_params", M.UP,
                          M.tree_bytes(job.payload))                   # cc1

    # -- cc2: the parameter fold over the client halves ----------------- #
    def agg_fold(self, ctx, ex, state, kept, rnd):
        return _stream_fold_params(ctx, state, kept, self.c_global)

    def agg_finalize(self, ctx, ex, state, arrived, rnd):
        self.c_global = _finalize_param_fold(ctx, state, self.c_global)
        self.joined = split_mod.join_lora(self.c_global, self.s_lt)

    def edge_payload_bytes(self, ctx) -> int:
        return M.tree_bytes(self.c_global)

    def evaluate(self, ctx):
        return _evaluate(ctx, self.joined)

    def final_state(self, ctx):
        return self.joined

    # -- checkpoint/resume (checkpoint/federated.py) --------------------- #
    def state_dict(self, ctx):
        return {"c_global": self.c_global, "s_lt": self.s_lt,
                "s_opt": self.s_opt}

    def load_state_dict(self, ctx, st):
        self.c_global, self.s_lt = st["c_global"], st["s_lt"]
        self.s_opt = st["s_opt"]
        self.joined = split_mod.join_lora(self.c_global, self.s_lt)

    @staticmethod
    def spmd_round(model, fed: FedConfig, task: str = "generative",
                   sfns=None, client_sharding=None):
        """The jittable whole-round program for the launch layer;
        ``client_sharding(ndim) -> NamedSharding`` pins the stacked
        client-half axis to the mesh's client axes before the closing
        cc2 reduction."""
        return fed_spmd.make_split_spmd_round(
            model, fed, task, sfns=sfns, client_sharding=client_sharding)


PROGRAMS = {"fedllm": FedLLMProgram, "kd": KDProgram,
            "split": SplitProgram}


# --------------------------------------------------------------------------- #
# The driver: one loop for every engine combination
# --------------------------------------------------------------------------- #
def run_program(model, base, cfg: ModelConfig, fed: FedConfig, targets,
                public: Dict, clients_data: List[Dict], test: Dict,
                task: str, batch_size: int, eval_batch: int,
                verbose: bool, backend: str = "sequential",
                mesh=None, checkpoint_every: int = 0,
                checkpoint_dir: str = None,
                resume_from: str = None) -> FedResult:
    """Run ``fed.rounds`` federated rounds of ``fed.framework`` through
    the composed pipeline.  ``backend`` selects the executor; ``mesh``
    (optional) makes the SPMD executor shard the stacked client axis
    over the mesh's client axes.

    Fault tolerance (src/repro/faults/): when ``fed.faults`` is active
    a seeded FaultPlan drops, delays, or corrupts uploads at the
    injection seam between local_update and upload; every arrival then
    passes the validation middleware (finite check + optional norm
    screen), offenders are quarantined (ledger ``quarantine`` events,
    secure-agg discard -> the cohort's survivors run the normal mask
    recovery), and a round whose surviving arrivals fall below
    ``fed.quorum`` x |starters| rolls over deterministically with the
    global state unchanged.

    Crash recovery: ``checkpoint_every > 0`` snapshots the complete run
    state (program params/optimizers, in-flight payloads, schedule RNG,
    secure-agg session, ledger, history, release counters) into
    ``checkpoint_dir`` after every k-th round via
    checkpoint/federated.py; ``resume_from`` restores the latest
    snapshot in a directory and continues — bit-exactly equal to the
    uninterrupted run (ledger bytes, metrics, final params)."""
    ctx = RoundContext(model, base, cfg, fed, targets, public,
                       clients_data, test, task, batch_size, eval_batch,
                       verbose)
    program = PROGRAMS[fed.framework](ctx)
    ex = EXECUTORS[backend](ctx, mesh)
    schedule = make_schedule(fed, ctx.n_clients)
    streaming = ex.streaming
    if streaming:
        from repro.launch import mesh as mesh_lib
        n_edges = fed.n_edges or mesh_lib.n_edges(mesh)
    else:
        n_edges = 1
    hierarchical = streaming and n_edges > 1
    if hierarchical:
        # two-hop topology: every per-client wire event is the first
        # hop now (client -> its edge aggregator); the edge -> server
        # hop is charged per live edge after each aggregation below
        ctx.ledger.default_hop = M.CLIENT_EDGE
    tag = f"{fed.framework}/{backend}" + \
        ("/async" if fed.aggregation == "async" else "")

    # -- fault-tolerance middleware ------------------------------------- #
    plan = None
    if fed.faults.enabled:
        from repro.faults import FaultPlan
        plan = FaultPlan(fed, ctx.n_clients)

    def _submit(outs, rnd):
        """The upload seam: Byzantine corruption happens BEFORE the
        upload stage (so privacy noise / compression / secure-agg
        masking all apply to what the corrupt client actually sends),
        dropout loses the payload after it (the bytes were spent —
        charged as ``retransmit``), stragglers submit with extra lag."""
        if plan is not None:
            outs = [(ci, plan.corrupt(p, rnd, ci)) for ci, p in outs]
        for ci, payload in program.upload(ctx, outs, rnd):
            if plan is not None and plan.dropped(rnd, ci):
                ctx.ledger.record(rnd, ci, "retransmit", M.UP,
                                  program.payload_bytes(ctx, payload))
                ctx.secagg.discard(ctx.secagg_start(rnd, ci), ci)
                continue
            extra = plan.extra_delay(rnd, ci) if plan is not None else 0
            schedule.submit(rnd, ci, payload, extra)

    def _start(cohort, rnd):
        """Broadcast, local update and upload for one starting cohort."""
        with obs.span("fl.broadcast", round=rnd):
            jobs = program.broadcast(ctx, cohort, rnd)
        with obs.span("fl.local_update", round=rnd):
            outs = program.local_update(ctx, ex, jobs, rnd)
        with obs.span("fl.upload", round=rnd):
            _submit(outs, rnd)

    def _screen(arrivals):
        """Validation verdicts for the whole round's arrivals at once
        (norm screen medians are round-global, so every backend
        quarantines the identical set)."""
        if not arrivals:
            return []
        from repro.faults import guard as fault_guard
        return fault_guard.screen(
            [program.payload_arrays(j.payload) for j in arrivals],
            fed.screen_factor)

    def _quarantine(j, rnd):
        ctx.ledger.record(rnd, j.client, "quarantine", M.UP,
                          program.payload_bytes(ctx, j.payload))
        ctx.secagg.discard(ctx.secagg_start(j.start, j.client), j.client)

    def _aggregate(rnd, starters):
        """The round's arrivals -> (arrived, rolled over).  Arrivals are
        screened round-globally, then handled one secure-agg masking
        cohort at a time (the start round on the flat backends, the
        start chunk under cohort streaming): quarantine, wire records,
        the staleness cut and mask delivery.  The program then folds
        the kept groups in one ``aggregate`` call."""
        arrivals = schedule.pop_arrivals(rnd)
        ok = _screen(arrivals)
        groups: Dict[int, List] = {}
        for j, good in zip(arrivals, ok):
            groups.setdefault(ctx.secagg_start(j.start, j.client),
                              []).append((j, good))
        kept, arrived, used_edges = [], [], set()
        for gi, (gkey, gjobs) in enumerate(groups.items()):
            group, delivered = [], []
            for j, good in gjobs:
                if not good:
                    _quarantine(j, rnd)
                    continue
                arrived.append(j.client)
                program.record_arrival(ctx, j, rnd)
                s = rnd - j.start
                if s <= fed.max_staleness:
                    group.append((j.client, j.payload, s,
                                  ctx.data_w[j.client]))
                    delivered.append((gkey, j.client))
                else:
                    ctx.secagg.discard(gkey, j.client)
            ctx.secagg.deliver(ctx.ledger, rnd, delivered)
            kept.append(group)
            used_edges.add(gi % n_edges)
        roll = bool(fed.quorum > 0 and starters and sum(map(len, kept))
                    < fed.quorum * len(starters))
        if roll:
            # below quorum: the payloads were received and their
            # secure-agg masks settled, but the round rolls over —
            # nothing folds into the global state
            kept, arrived = [], []
        program.aggregate(ctx, ex, kept, arrived, rnd)
        if hierarchical and arrived:
            # second hop: each edge that aggregated a cohort this
            # round forwards one fused payload up and pulls the new
            # global down (negative ids denote edge aggregators)
            eb = program.edge_payload_bytes(ctx)
            for e in sorted(used_edges):
                ctx.ledger.record(rnd, -(e + 1), "edge_agg", M.UP,
                                  eb, hop=M.EDGE_SERVER)
                ctx.ledger.record(rnd, -(e + 1), "edge_agg", M.DOWN,
                                  eb, hop=M.EDGE_SERVER)
        return len(arrived), roll

    # -- crash recovery -------------------------------------------------- #
    mgr = None
    if checkpoint_every and checkpoint_every > 0:
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(checkpoint_dir)
    start_rnd, rollovers = 0, 0
    if resume_from:
        from repro.checkpoint import federated as fed_ckpt
        start_rnd, rollovers = fed_ckpt.restore_run(resume_from, ctx,
                                                    program, schedule)

    for rnd in range(start_rnd, fed.rounds):
        ctx.rnd = rnd
        with obs.span("fl.round", round=rnd):
            # start cohort: free clients pull state and form this round's
            # secure-agg masking cohort (payloads are created — and masked
            # — now, even when they deliver rounds later)
            starters = schedule.starters(rnd)
            # under cohort streaming the ready set streams through the
            # stacked programs one cohort-sized chunk at a time, each
            # chunk its own secure-agg masking cohort so its payloads can
            # be freed the moment the whole chunk delivers; the flat
            # backends start one chunk keyed by the start round
            for k, chunk in enumerate(_cohort_chunks(
                    starters, fed.cohort_size if streaming else 0)):
                cid = None
                if streaming:
                    cid = _cohort_uid(rnd, k)
                    for ci in chunk:
                        ctx._cohort_ids[(rnd, ci)] = cid
                ctx.secagg.begin_cohort(ctx.ledger, rnd, chunk,
                                        cohort_id=cid)
                _start(chunk, rnd)
            # arrivals: record wire traffic, drop too-stale updates (their
            # pairwise masks recovered like any absent cohort member's)
            with obs.span("fl.aggregate", round=rnd):
                arrived_n, rolled = _aggregate(rnd, starters)
            rollovers += int(rolled)
            acc, loss = program.evaluate(ctx)
            flops = float(np.mean([c.flops for c in ctx.cost])) \
                if ctx.cost else 0.0
            ctx.history.append(M.RoundMetrics(
                rnd, acc, loss, ctx.ledger.mean_client_bytes_per_round(),
                flops, epsilon=round_epsilon(ctx.acct,
                                             max(ctx.releases, default=0))))
            if verbose:
                print(f"[{tag}] round {rnd}: acc={acc:.4f} loss={loss:.4f}"
                      + (f" arrived={arrived_n}"
                         if fed.aggregation == "async" else ""))
            if mgr is not None and (rnd + 1) % checkpoint_every == 0:
                from repro.checkpoint import federated as fed_ckpt
                fed_ckpt.save_run(mgr, ctx, program, schedule, rnd,
                                  rollovers)
    return FedResult(ctx.history, ctx.ledger, program.final_state(ctx),
                     [c.flops for c in ctx.cost], rollovers=rollovers)
