"""The FedLLM framework's part of a run, found by the traffic file's
``framework`` key (``bench/frameworks/<framework>.py``).

A framework module gives the harness:

- ``program_class(base_cls, drv)``: the program's class with its stages
  wrapped (timed in the traced run, the first round's outputs kept);
- ``patches(drv)``: further ``(module, name, value)`` wrappers of the
  program's functions for one ``run_federated`` call;
- ``round_flops(arch, traffic, fed, test)``: required operations of
  each window round, from what the round fed (``fed``);
- ``readings(first, inputs, log)``: every number the comparison can
  read, the program's first round against the plain reference's.

FedLLM: each client trains its LoRA adapter for one local epoch, the
server takes the data-weighted mean and evaluates it on the test set.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench import flops as bflops
from bench import program as bprog
from bench.references import decoder as ref


def program_class(base_cls, drv):
    """The FedLLM program with its stages wrapped."""

    class Bench(base_cls):
        def __init__(self, ctx):
            super().__init__(ctx)
            self.global_lt = bprog.lora_for(self.global_lt, drv.lora_init)

        def local_update(self, ctx, ex, jobs, rnd):
            outs = drv.timed("local_update", super().local_update, ctx, ex,
                             jobs, rnd, block=lambda o: [t for _, t in o])
            if drv.rounds_done == 0:
                drv.first["client_lora"] = [bprog.lora_from(t)
                                            for _, t in outs]
                drv.first["clients"] = [ci for ci, _ in outs]
            return outs

        def aggregate(self, ctx, ex, kept, arrived, rnd):
            drv.timed("aggregate", super().aggregate, ctx, ex, kept,
                      arrived, rnd, block=lambda _: self.global_lt)
            if drv.rounds_done == 0:
                drv.first["global_lora"] = bprog.lora_from(self.global_lt)

        def evaluate(self, ctx):
            acc, loss = drv.timed("evaluate", super().evaluate, ctx)
            if drv.rounds_done == 0:
                drv.first["eval_loss"] = loss
                drv.first["eval_acc"] = acc
                drv.first["ledger_bytes"] = sum(
                    e.bytes for e in ctx.ledger.events if e.round == 0)
            drv.round_end()
            return acc, loss

    Bench.__name__ = base_cls.__name__
    return Bench


def patches(drv) -> List:
    """Keep what the round fed (every round, for the operation count;
    the first round's batches for the check) and each client's mean
    training loss of the first round."""
    from repro.core import fed_spmd

    stack_batches = fed_spmd.stack_client_batches
    make_update = fed_spmd.make_bucket_update

    def stack_client_batches(*a, **kw):
        # keep references only: reading them back now would wait for the
        # device inside the window; they are read after it closes
        out = drv.timed("stack_client_batches", stack_batches, *a, **kw)
        batches, valid, _ = out
        drv.fed[drv.rounds_done].append((batches["lengths"], valid))
        if drv.rounds_done == 0:
            drv.first.setdefault("feed", []).append((batches, valid))
        return out

    def make_bucket_update(*a, **kw):
        update = make_update(*a, **kw)

        def call(*args):
            out = update(*args)
            if drv.rounds_done == 0:
                drv.first.setdefault("client_loss", []).append(out[2])
            return out
        return call

    return [(fed_spmd, "stack_client_batches", stack_client_batches),
            (fed_spmd, "make_bucket_update", make_bucket_update)]


def round_flops(arch, traffic: Dict, fed: List, test) -> float:
    """Local training of every client fed, and the test set's forward
    pass (bench/flops.py states the rule)."""
    rank = traffic["fed"]["lora_rank"]
    return (sum(bflops.train_round_flops(arch, rank, ln, v)
                for ln, v in fed)
            + bflops.forward_flops(arch, rank, test["lengths"]))


# --------------------------------------------------------------------------- #
# The check
# --------------------------------------------------------------------------- #
def client_feed(feed, clients: List[int]):
    """Per-bucket (batches, valid) -> one (batches, valid) per client, in
    the order of ``clients`` (the order the buckets were fed)."""
    out = []
    for batches, valid in feed:
        for c in range(valid.shape[0]):
            out.append(({k: v[c] for k, v in batches.items()}, valid[c]))
    if len(out) != len(clients) or clients != sorted(clients):
        raise ValueError(f"fed clients {clients} do not match the buckets")
    return out


def _row_key(tokens, label, length) -> bytes:
    return tokens.tobytes() + label.tobytes() + length.tobytes()


def check_feed(per_client, shards, batch_size) -> int:
    """Rows fed to local training that are not distinct rows of the
    client's own shard, plus rows of full batches that were not fed."""
    bad = 0
    for (batches, valid), shard in zip(per_client, shards):
        own = {_row_key(*r) for r in
               zip(shard["tokens"], shard["labels"], shard["lengths"])}
        seen = set()
        rows = list(zip(
            batches["tokens"][valid].reshape(-1,
                                             batches["tokens"].shape[-1]),
            batches["labels"][valid].reshape(-1),
            batches["lengths"][valid].reshape(-1)))
        for r in rows:
            k = _row_key(*r)
            bad += (k not in own) or (k in seen)
            seen.add(k)
        full = (len(shard["tokens"]) // batch_size) * batch_size
        bad += max(0, full - len(rows))
    return int(bad)


def reference_round(arch, weights, lora0, traffic, per_client, shards, test,
                    dtype=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST) -> Dict:
    """The first round, by the plain reference: each client's local
    epoch over the batches it was fed, the data-weighted mean, the test
    loss of the mean, and the wire bytes."""
    fed = traffic["fed"]
    scale = fed["lora_alpha"] / fed["lora_rank"]

    def cast(tree):
        return jax.tree.map(
            lambda x: x if x.dtype == dtype else x.astype(dtype), tree)
    params, lora0 = cast(weights), cast(lora0)
    epoch = ref.make_local_epoch(arch, scale, fed["lr"], precision)
    loras, losses, grad_norms = [], [], []
    for batches, valid in per_client:
        steps = {k: jnp.asarray(v[valid]) for k, v in batches.items()}
        lo, ls, gn = epoch(params, lora0, steps)
        loras.append(jax.tree.map(np.asarray, lo))
        losses.append(float(np.mean(np.asarray(ls, np.float64))))
        grad_norms.append(jax.tree.map(np.asarray, gn))
    glob = ref.fedavg(loras, [len(s["tokens"]) for s in shards])
    eb = traffic["eval_batch"]
    n = len(test["tokens"])
    tb = {k: jnp.asarray(v.reshape((n // eb, eb) + v.shape[1:]))
          for k, v in test.items()}
    loss_sum, _ = ref.make_eval(arch, scale, precision)(
        params, cast(jax.tree.map(jnp.asarray, glob)), tb)
    n_lora = sum(x.size for x in jax.tree.leaves(lora0))
    return {"client_loss": np.asarray(losses),
            "client_lora": loras,
            "global_lora": jax.tree.map(np.asarray, glob),
            "eval_loss": float(loss_sum) / n,
            # down and up, every client, float32 adapters
            "ledger_bytes": 2 * len(shards) * 4 * n_lora,
            "grad_norms": grad_norms}


def readings(first: Dict, inputs: Dict, log=print) -> Dict[str, float]:
    """The program's first round (what the wrappers kept) against the
    reference's on the batches each client was fed."""
    feed = [({k: np.asarray(v) for k, v in b.items()}, np.asarray(val))
            for b, val in first["feed"]]
    shards, traffic = inputs["shards"], inputs["traffic"]
    per_client = client_feed(feed, first["clients"])
    prog = {"client_loss": np.concatenate(
                [np.asarray(x) for x in first["client_loss"]]),
            "client_lora": [jax.tree.map(np.asarray, x)
                            for x in first["client_lora"]],
            "global_lora": jax.tree.map(np.asarray, first["global_lora"]),
            "eval_loss": first["eval_loss"],
            "ledger_bytes": first["ledger_bytes"],
            "feed_bad_rows": check_feed(per_client, shards,
                                        traffic["batch_size"])}
    t = time.perf_counter()
    reference = reference_round(inputs["arch"], inputs["weights"],
                                inputs["lora0"], traffic, per_client,
                                shards, inputs["test"])
    log(f"reference seconds={time.perf_counter() - t:.3f} "
        f"eval loss program={first['eval_loss']:.6f} "
        f"acc={first['eval_acc']:.4f} reference={reference['eval_loss']:.6f}")
    init = jax.tree.map(np.asarray, inputs["lora0"])
    return compare.readings(prog, reference, init, reference["grad_norms"])
