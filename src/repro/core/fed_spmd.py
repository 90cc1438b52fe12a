"""SPMD federated rounds — the hardware-adapted execution backend for
all three paper frameworks (DESIGN SS2, ``FedConfig(backend="spmd")``).

The paper's clients are edge devices; on a TPU fleet a "client" is a pod
(or mesh slice).  Here one jitted program runs EVERY client's local work
simultaneously (clients = leading axis, vmapped) and performs the
server-side aggregation as a reduction over that axis — which, with the
client axis sharded over the multi-pod mesh's ``pod`` dimension, lowers
to a single cross-pod all-reduce: the parameter-server round of the
paper becomes one collective.

Per framework:

- FedLLM (``make_spmd_round``): vmapped local fine-tune scans + weighted
  FedAvg as a client-axis mean.
- KD-FedLLM (``make_kd_spmd_fns``): vmapped local fine-tune, batched
  logit production on the public set, and vmapped client-side
  distillation; knowledge aggregation is the client-axis reduction in
  ``kd.aggregate_knowledge_batched``.
- Split-FedLLM (``make_split_spmd_round``): stacked client-side LoRA
  halves with ONE shared server half.  The server carry scans the client
  axis (the paper's round trains the shared server layers
  client-after-client, so a lockstep-parallel server would change the
  optimization trajectory); the closing FedAvg of the client halves is
  still a client-axis reduction.

Clients with ragged batch counts are padded and masked (``valid``): a
masked step returns the carry unchanged, so every client performs
exactly the step sequence the sequential backend would.  The runtime
runs these programs from core/round_program.py's ``SpmdExecutor``; the
``fed_round`` dry-run target (launch/dryrun.py --step fed_round)
compiles them against the production meshes.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import FedConfig
from repro.data.loader import epoch_batches
from repro.models.factory import Model


# --------------------------------------------------------------------------- #
# Stacking utilities (host side)
# --------------------------------------------------------------------------- #
def stack_for_clients(tree, n_clients: int):
    """Broadcast one tree to a leading client axis (a1: distribute)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_clients,) + x.shape), tree)


def stack_trees(trees: Sequence):
    """Stack identically-structured per-client trees on a new axis 0."""
    with obs.span("fl.exec.stack_trees"):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def split_keys(key, n_clients: int, n_steps: int):
    """(C, S) grid of PRNG keys (works for legacy and typed key arrays)."""
    keys = jax.random.split(key, n_clients * n_steps)
    return keys.reshape((n_clients, n_steps) + keys.shape[1:])


def split_each(stacked_keys):
    """Per-client ``jax.random.split``: (C,)-stacked keys -> (next, sub)."""
    out = jax.vmap(jax.random.split)(stacked_keys)
    return out[:, 0], out[:, 1]


def stack_client_batches(clients_data: List[Dict], batch_size: int,
                         seeds: Sequence[int]):
    """Materialize every client's shuffled epoch batches as stacked
    arrays with a leading (client, step) axis plus a validity mask.

    ``seeds`` is the per-epoch seed sequence handed to ``epoch_batches``
    — the same one the sequential backend uses, so each client sees the
    exact same batch order under both backends.  Clients with fewer
    batches than the longest are padded by repeating their last batch
    with ``valid=False``; the scanned round step drops those updates, so
    per-client step counts are preserved exactly.

    Returns ``(batches, valid, n_tok)``: batches leaves are
    (C, S, B, ...) jnp arrays, ``valid`` a (C, S) bool ndarray, and
    ``n_tok`` the per-client real token counts for the cost model.
    """
    with obs.span("fl.exec.stack_batches"):
        per_client = []
        for data in clients_data:
            client_batches = []
            for seed in seeds:
                client_batches.extend(
                    epoch_batches(data, batch_size, seed=seed))
            per_client.append(client_batches)
        n_steps = [len(b) for b in per_client]
        if min(n_steps) == 0:
            raise ValueError(
                "spmd backend: every client needs at least one full batch "
                f"(batch_size={batch_size}, client sizes="
                f"{[len(d['tokens']) for d in clients_data]})")
        n_tok = [sum(b["tokens"].size for b in bs) for bs in per_client]
        S = max(n_steps)
        valid = np.zeros((len(per_client), S), bool)
        rows = []
        for ci, bs in enumerate(per_client):
            valid[ci, :len(bs)] = True
            padded = bs + [bs[-1]] * (S - len(bs))
            rows.append({k: np.stack([b[k] for b in padded])
                         for k in bs[0]})
        batches = {k: jnp.asarray(np.stack([r[k] for r in rows]))
                   for k in rows[0]}
    # stacked steps past a client's own count are padding (valid=False)
    obs.count("steps_stacked", valid.size)
    obs.count("steps_valid", int(valid.sum()))
    return batches, valid, n_tok


def unstack_tree(stacked):
    """Inverse of ``stack_trees``: a list of per-client trees from a
    leading-axis stack (host-side seam between bucketed programs and the
    cross-bucket harmonization)."""
    with obs.span("fl.exec.unstack_tree"):
        n = jax.tree.leaves(stacked)[0].shape[0]
        return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


def rank_buckets(ranks: Sequence[int], clients: Sequence[int] = None):
    """Group client indices by LoRA rank: ``[(rank, [client, ...]), ...]``
    ordered by first occurrence, client order preserved within a bucket.
    Each bucket runs as one jitted stacked program (clients in a bucket
    share tree shapes, so they stack on a leading axis)."""
    if clients is None:
        clients = range(len(ranks))
    out: Dict[int, List[int]] = {}
    for ci in clients:
        out.setdefault(ranks[ci], []).append(ci)
    return list(out.items())


def rank_segments(ranks: Sequence[int], clients: Sequence[int] = None):
    """Maximal runs of equal-rank clients in visit order:
    ``[(rank, [client, ...]), ...]``.  Split-FedLLM buckets this way —
    the shared server half is trained client-after-client (paper
    schedule), so only contiguous equal-rank runs may fuse into one
    stacked program without reordering the server-half trajectory."""
    segs: List = []
    if clients is None:
        clients = range(len(ranks))
    for ci in clients:
        if segs and ranks[ci] == segs[-1][0]:
            segs[-1][1].append(ci)
        else:
            segs.append((ranks[ci], [ci]))
    return segs


def _select(ok, new, old):
    """Keep ``new`` where the step was real, the carry otherwise."""
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)


def _normalized(weights):
    """Weights normalized to sum 1, degrading to uniform when the total
    is zero (a fully-dropped cohort must not turn the aggregate into
    NaN).  Bit-transparent for any positive total: the guarded divisor
    equals the plain sum, so existing parity pins are unaffected."""
    w = weights.astype(jnp.float32)
    s = w.sum()
    return jnp.where(s > 0, w / jnp.where(s > 0, s, 1.0),
                     1.0 / w.shape[0])


def weighted_client_mean(stacked_tree, weights):
    """FedAvg as a reduction over the leading client axis (fp32 accum,
    like core/fedavg.fedavg) — one all-reduce when that axis is sharded."""
    w = _normalized(weights)

    def mean(x):
        wx = w.reshape((-1,) + (1,) * (x.ndim - 1))
        return (wx * x.astype(jnp.float32)).sum(axis=0).astype(x.dtype)

    return jax.tree.map(mean, stacked_tree)


def hierarchical_client_mean(stacked_tree, weights, n_edges: int):
    """FedAvg as the two-hop reduction of a real cross-device topology:
    the client axis is reshaped to (edges, clients_per_edge), each edge
    reduces its own clients to a weighted partial sum (a per-pod
    ``psum`` when the client axis is sharded over the mesh's ``pod``
    axis — the per-edge slice is pod-local by construction), and the
    per-edge partials fold through a pairwise halving tree (log2(edges)
    cross-pod combine steps, unrolled at trace time).

    Numerically this reassociates the fp32 accumulation of
    ``weighted_client_mean`` — same normalized weights, same fp32
    accum, fp32-tolerant agreement — while lowering to the per-pod
    reduce + cross-pod tree the hierarchical topology actually runs.
    Degenerates to the flat reduction when ``n_edges <= 1`` or the
    client count doesn't tile the edges."""
    C = weights.shape[0]
    if n_edges <= 1 or C % n_edges:
        return weighted_client_mean(stacked_tree, weights)
    we = _normalized(weights).reshape(n_edges, C // n_edges)

    def mean(x):
        xe = x.reshape((n_edges, C // n_edges) + x.shape[1:])
        wx = we.reshape(we.shape + (1,) * (x.ndim - 1))
        part = (wx * xe.astype(jnp.float32)).sum(axis=1)   # per-edge psum
        while part.shape[0] > 1:                           # cross-edge tree
            m = part.shape[0] // 2
            part = jnp.concatenate(
                [part[:m] + part[m:2 * m], part[2 * m:]], axis=0)
        return part[0].astype(x.dtype)

    return jax.tree.map(mean, stacked_tree)


# --------------------------------------------------------------------------- #
# Byzantine-robust client-axis reductions (src/repro/faults/)
# --------------------------------------------------------------------------- #
def robust_client_combine(stacked_tree, weights, method: str,
                          trim_frac: float = 0.2, clip_norm: float = 0.0):
    """Byzantine-robust drop-in for ``weighted_client_mean`` over the
    stacked client axis (``FedConfig.robust_agg``):

    - ``median``: coordinate-wise median.  Unweighted — order statistics
      ignore data weights; tolerates < C/2 corrupt clients per
      coordinate.
    - ``trimmed_mean``: per coordinate, sort the client axis and drop
      ``floor(trim_frac * C)`` values from each end before the
      (unweighted) mean; tolerates up to the trimmed count corrupt.
    - ``norm_clip``: clip each client update's global L2 norm to
      ``clip_norm`` (0 = the cohort's median norm), then take the
      usual weighted mean — bounds any single client's pull without
      discarding honest heavy updates.

    All methods accumulate in fp32 and cast back to the leaf dtype,
    like the plain mean.  They never change payload shapes, so ledger
    bytes under a robust aggregate match the plain engines exactly.
    """
    if method in ("mean", None, ""):
        return weighted_client_mean(stacked_tree, weights)
    C = jax.tree.leaves(stacked_tree)[0].shape[0]
    if method == "median":
        return jax.tree.map(
            lambda x: jnp.median(x.astype(jnp.float32), axis=0)
            .astype(x.dtype), stacked_tree)
    if method == "trimmed_mean":
        k = int(trim_frac * C)
        if 2 * k >= C:
            k = (C - 1) // 2

        def tmean(x):
            s = jnp.sort(x.astype(jnp.float32), axis=0)
            return s[k:C - k].mean(axis=0).astype(x.dtype)

        return jax.tree.map(tmean, stacked_tree)
    if method == "norm_clip":
        sq = sum(jnp.square(x.astype(jnp.float32))
                 .reshape(C, -1).sum(axis=1)
                 for x in jax.tree.leaves(stacked_tree))
        norms = jnp.sqrt(sq)                                   # (C,)
        tau = jnp.asarray(clip_norm, jnp.float32) if clip_norm > 0 \
            else jnp.median(norms)
        scale = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-12))
        clipped = jax.tree.map(
            lambda x: (scale.reshape((-1,) + (1,) * (x.ndim - 1))
                       * x.astype(jnp.float32)).astype(x.dtype),
            stacked_tree)
        return weighted_client_mean(clipped, weights)
    raise ValueError(f"unknown robust_agg {method!r}")


def client_combine(stacked_tree, weights, fed: FedConfig):
    """The round's configured client-axis reduction: the plain weighted
    mean, or the Byzantine-robust combine when ``fed.robust_agg`` says
    so.  Robust statistics do not decompose over edges, so a robust
    combine is always the flat (single-hop) reduction — hierarchical
    runs fall back to it whole-cohort."""
    if fed.robust_agg != "mean":
        return robust_client_combine(stacked_tree, weights, fed.robust_agg,
                                     fed.trim_frac, fed.clip_norm)
    return weighted_client_mean(stacked_tree, weights)


# --------------------------------------------------------------------------- #
# Shared local-update machinery (FedLLM a2 / KD b1)
# --------------------------------------------------------------------------- #
def make_local_update(model: Model, fed: FedConfig,
                      task: str = "classification"):
    """Returns local_update(base, lt, opt, batches, keys, valid) scanning
    one client's batch sequence — the building block vmapped over the
    client axis by every SPMD round.  The per-batch step is the
    sequential backend's own train_step body (fedavg.make_fns), so the
    backends can never drift apart on the local loss/optimizer."""
    from repro.core.fedavg import make_fns

    train_step = make_fns(model, fed, task)["train_step_impl"]

    def local_update(base, lt, opt, client_batches, keys, valid):
        def body(carry, step):
            lt, opt = carry
            batch, key, ok = step
            new_lt, new_opt, loss = train_step(base, lt, opt, batch, key)
            return (_select(ok, new_lt, lt), _select(ok, new_opt, opt)), \
                jnp.where(ok, loss, 0.0)

        (lt, opt), losses = jax.lax.scan(
            body, (lt, opt), (client_batches, keys, valid))
        return lt, opt, losses.sum() / jnp.maximum(valid.sum(), 1)

    return local_update


def client_program(fn, in_axes, mesh=None):
    """jit(vmap(fn)) over a leading client axis (``in_axes`` as vmap's:
    0 for client-stacked arguments, None for shared ones; every output
    is client-stacked).  With ``mesh``, each device runs the vmapped
    program on its own slice of the client stack (shard_map over the
    mesh's client axes): the compiler cannot partition a Pallas TPU
    kernel, and per-client work needs no communication anyway.  (No
    varying-axis check: a Pallas call declares no varying axes for its
    outputs.)"""
    mapped = jax.vmap(fn, in_axes=in_axes)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import client_axes
        c = P(client_axes(mesh))
        mapped = jax.shard_map(
            mapped, mesh=mesh, out_specs=c, check_vma=False,
            in_specs=tuple(P() if a is None else c for a in in_axes))
    return jax.jit(mapped)


def make_bucket_update(model: Model, fed: FedConfig,
                       task: str = "classification", mesh=None):
    """jit(vmap(local_update)) WITHOUT the closing FedAvg: the building
    block for per-rank bucketing and async participation, where the
    round driver folds the clients' trees across buckets
    (core/round_program.FrameworkProgram.aggregate).  One
    program object — jax recompiles per (bucket size, rank, n_steps)
    signature and caches each variant.  ``mesh``: see client_program."""
    local_update = make_local_update(model, fed, task)
    return client_program(local_update, (None, 0, 0, 0, 0, 0), mesh)


# --------------------------------------------------------------------------- #
# 1) FedLLM round (a1-a4)
# --------------------------------------------------------------------------- #
def make_spmd_round(model: Model, fed: FedConfig,
                    task: str = "classification", n_edges: int = 1):
    """Returns round_step(base, stacked_lt, stacked_opt, batches, keys,
    valid, weights[, noise_keys]) where stacked_* have a leading client
    axis C and ``batches`` leaves are (C, n_steps, B, ...).  Output LoRA
    is already aggregated and redistributed (identical across the client
    axis, like a1 of the next round); the pre-aggregation *uploaded*
    trees come back too, so the host can run the secure-agg masking
    overlay and the per-client wire accounting on exactly what crossed
    the wire.

    With ``PrivacyConfig`` noise active the extra ``noise_keys`` input
    is one key per client slot (privacy/dp.noise_key — the same keys
    the sequential backend folds in), and the DP payload noise is added
    to every client's tree *before* the client-axis FedAvg, mirroring
    the a3 upload boundary.

    ``n_edges > 1`` swaps the closing a4 reduction for the two-hop
    ``hierarchical_client_mean`` — per-edge (per-pod) partial sums
    feeding a cross-edge pairwise tree — matching the client -> edge ->
    server topology the launch layer compiles on multi-pod meshes."""
    local_update = make_local_update(model, fed, task)
    noise_std = fed.privacy.noise_std

    def round_step(base, stacked_lt, stacked_opt, batches, keys, valid,
                   weights, noise_keys=None):
        new_lt, new_opt, losses = jax.vmap(
            local_update, in_axes=(None, 0, 0, 0, 0, 0))(
                base, stacked_lt, stacked_opt, batches, keys, valid)
        if noise_std > 0.0:
            from repro.privacy import dp as dp_mod
            new_lt = jax.vmap(
                lambda t, k: dp_mod.privatize_tree(t, k, noise_std))(
                    new_lt, noise_keys)
        # a4: weighted FedAvg == client-axis reduction -> all-reduce
        # (or the per-pod psum + cross-pod tree when edges are in play;
        # a robust_agg overrides both — order statistics don't
        # decompose over edges, so the robust combine is always flat)
        if fed.robust_agg != "mean":
            avg = client_combine(new_lt, weights, fed)
        else:
            avg = hierarchical_client_mean(new_lt, weights, n_edges) \
                if n_edges > 1 else weighted_client_mean(new_lt, weights)
        # a1 of the next round: broadcast back to every client slot
        C = jax.tree.leaves(stacked_lt)[0].shape[0]
        redist = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), avg)
        return redist, new_opt, losses, new_lt

    return round_step


# --------------------------------------------------------------------------- #
# 2) KD-FedLLM stages (b1/b2/b8 batched over clients)
# --------------------------------------------------------------------------- #
def make_kd_spmd_fns(model: Model, fed: FedConfig,
                     task: str = "classification", mesh=None):
    """Batched KD-FedLLM stages, clients on the leading axis:

    - client_update(base, slt, sopt, batches, keys, valid): vmapped b1
      local fine-tuning (each client scans its own private batches).
    - batched_logits(base, slt, public_batch): b2/b6 knowledge
      production for every client at once -> (C, B, D).
    - batched_kd_step(base, slt, sopt, public_batch, teacher, keys):
      one vmapped b8 distillation step against shared global knowledge.

    Knowledge aggregation (b4) is ``kd.aggregate_knowledge_batched``.
    ``mesh``: see client_program.
    """
    from repro.core.fedavg import make_fns

    fns = make_fns(model, fed, task)
    local_update = make_local_update(model, fed, task)
    client_update = client_program(local_update, (None, 0, 0, 0, 0, 0),
                                   mesh)
    batched_logits = client_program(fns["logits_fn"], (None, 0, None), mesh)
    batched_kd_step = client_program(fns["kd_step"],
                                     (None, 0, 0, None, None, 0), mesh)
    return {"client_update": client_update,
            "batched_logits": batched_logits,
            "batched_kd_step": batched_kd_step}


# --------------------------------------------------------------------------- #
# 3) Split-FedLLM round (c1-c5 + cc1-cc4)
# --------------------------------------------------------------------------- #
def make_split_spmd_round(model: Model, fed: FedConfig,
                          task: str = "classification", sfns=None,
                          client_sharding=None):
    """One program for the whole Split-FedLLM round.

    Client-side LoRA halves are stacked on a leading client axis and the
    closing FedAvg (cc2) is a weighted reduction over it.  The shared
    server half is a carry scanned over the client axis — the paper's
    round trains the server layers client-after-client, and preserving
    that order keeps the SPMD backend numerically equivalent to the
    sequential one (a lockstep-parallel server is a different algorithm,
    not an execution backend).

    Returns round_step(base_c, base_s, c_global, s_lt, s_opt, batches,
    keys, valid, weights[, nkeys]) -> (new_c_global, s_lt, s_opt,
    losses, stacked_c).  ``stacked_c`` is the per-client uploaded
    half (for the host's secure-agg overlay); ``nkeys`` is the
    (C, S)-stacked privacy noise-key grid consumed by the c2 activation
    mechanism when DP noise is active — the same per-(client, step)
    fold_in stream the sequential backend passes, so noise is
    bit-identical across backends.

    ``client_sharding(ndim) -> NamedSharding`` (optional) pins the
    stacked client-half axis to the mesh's client axes before the
    closing cc2 reduction: the scan emits the per-client halves, the
    constraint lays them out client-sharded, and the FedAvg lowers to a
    cross-client all-reduce (launch/steps.py passes this for the
    mesh-sharded dry-run).
    """
    from repro.core import split as split_mod

    if sfns is None:
        sfns = split_mod.make_split_fns(model, fed, task)
    step = sfns["split_step"]
    opt_init = sfns["opt_init"]
    noised = fed.privacy.noise_std > 0.0

    def round_step(base_c, base_s, c_global, s_lt, s_opt, batches, keys,
                   valid, weights, nkeys=None):
        def per_client(carry, client):
            s_lt, s_opt = carry

            def body(inner, x):
                c_lt, c_opt, s_lt, s_opt = inner
                batch, key, ok = x[:3]
                nk = x[3] if noised else None
                nc, ns, nco, nso, loss = step(base_c, base_s, c_lt, s_lt,
                                              c_opt, s_opt, batch, key, nk)
                return (_select(ok, nc, c_lt), _select(ok, nco, c_opt),
                        _select(ok, ns, s_lt), _select(ok, nso, s_opt)), \
                    jnp.where(ok, loss, 0.0)

            # cc3: fresh client copy of the global client-side LoRA
            (c_lt, _, s_lt, s_opt), losses = jax.lax.scan(
                body, (c_global, opt_init(c_global), s_lt, s_opt), client)
            return (s_lt, s_opt), (c_lt, losses)

        xs = (batches, keys, valid) + ((nkeys,) if noised else ())
        (s_lt, s_opt), (stacked_c, losses) = jax.lax.scan(
            per_client, (s_lt, s_opt), xs)
        if client_sharding is not None:
            stacked_c = jax.lax.with_sharding_constraint(
                stacked_c,
                jax.tree.map(lambda x: client_sharding(x.ndim), stacked_c))
        # cc2: FedAvg of the client halves — client-axis reduction
        # (robust combine when configured)
        new_c_global = client_combine(stacked_c, weights, fed)
        return new_c_global, s_lt, s_opt, losses, stacked_c

    return round_step


def make_split_spmd_segment(model: Model, fed: FedConfig,
                            task: str = "classification", sfns=None):
    """One stacked program for a contiguous equal-rank client *segment*
    of a heterogeneous Split-FedLLM round (``rank_segments``).

    Like ``make_split_spmd_round``'s scan, but (1) every client starts
    from ``c_init`` — the global client half already truncated to the
    segment's rank — and (2) the closing FedAvg is left to the host,
    which harmonizes ranks across segments.  The server carry enters
    and leaves the program, so threading it segment-after-segment
    reproduces the sequential backend's exact client visit order.

    Returns seg_step(base_c, base_s, c_init, s_lt, s_opt, batches, keys,
    valid[, nkeys]) -> (stacked_c, s_lt, s_opt, losses).  ``nkeys`` as
    in ``make_split_spmd_round``: the (|seg|, S) privacy noise-key grid
    for the c2 activation mechanism when DP noise is active.
    """
    from repro.core import split as split_mod

    if sfns is None:
        sfns = split_mod.make_split_fns(model, fed, task)
    step = sfns["split_step"]
    opt_init = sfns["opt_init"]
    noised = fed.privacy.noise_std > 0.0

    def seg_step(base_c, base_s, c_init, s_lt, s_opt, batches, keys,
                 valid, nkeys=None):
        def per_client(carry, client):
            s_lt, s_opt = carry

            def body(inner, x):
                c_lt, c_opt, s_lt, s_opt = inner
                batch, key, ok = x[:3]
                nk = x[3] if noised else None
                nc, ns, nco, nso, loss = step(base_c, base_s, c_lt, s_lt,
                                              c_opt, s_opt, batch, key, nk)
                return (_select(ok, nc, c_lt), _select(ok, nco, c_opt),
                        _select(ok, ns, s_lt), _select(ok, nso, s_opt)), \
                    jnp.where(ok, loss, 0.0)

            (c_lt, _, s_lt, s_opt), losses = jax.lax.scan(
                body, (c_init, opt_init(c_init), s_lt, s_opt), client)
            return (s_lt, s_opt), (c_lt, losses)

        xs = (batches, keys, valid) + ((nkeys,) if noised else ())
        (s_lt, s_opt), (stacked_c, losses) = jax.lax.scan(
            per_client, (s_lt, s_opt), xs)
        return stacked_c, s_lt, s_opt, losses

    return seg_step
