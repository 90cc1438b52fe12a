"""Round-level crash recovery for the federated driver.

``save_run`` snapshots *everything mutable* in a
``core/round_program.run_program`` run after round ``rnd`` completes:
the program's global state (LoRA trees / KD teacher state / split
halves and server optimizer), the schedule's in-flight payloads and
participation RNGs, the secure-agg session (cohorts + fixed-point
vectors, bit-exact), the CommLedger, metric history, per-client cost
and DP release counters.  ``restore_run`` rebuilds all of it and hands
back the round to resume from, so a killed run resumed from its last
checkpoint finishes **bit-identical** to an uninterrupted one
(tests/test_faults.py pins ledger bytes, history and final params).

Everything *derivable* from ``FedConfig.seed`` — fault plans, local
dropout keys, DP noise keys, batch orders, secure-agg pair masks — is a
pure function of (seed, round, client) by construction (core/rng), so
it never needs to be stored: replay after resume regenerates it
exactly.
"""
from __future__ import annotations

from typing import Optional, Tuple

from repro.checkpoint.manager import CheckpointManager
from repro.core import metrics as M
from repro.core.async_agg import Job


def save_run(mgr: CheckpointManager, ctx, program, schedule, rnd: int,
             rollovers: int) -> str:
    """Snapshot the run after round ``rnd`` (resume continues at
    ``rnd + 1``)."""
    state = {
        "round": int(rnd) + 1,
        "rollovers": int(rollovers),
        "program": program.state_dict(ctx),
        "jobs": [{"client": int(j.client), "start": int(j.start),
                  "arrival": int(j.arrival), "payload": j.payload}
                 for j in schedule.jobs()],
        # numpy Generator states: nested dicts of strings and (big)
        # python ints — JSON round-trips them exactly
        "sched_rngs": schedule.rng_state(),
        "secagg": ctx.secagg.state_dict(),
        "ledger": {
            "default_hop": ctx.ledger.default_hop,
            "events": [[int(e.round), int(e.client), e.name, e.direction,
                        int(e.bytes), e.hop] for e in ctx.ledger.events],
        },
        "history": [[int(m.round), float(m.accuracy), float(m.loss),
                     float(m.comm_bytes_per_client), float(m.client_flops),
                     float(m.epsilon)] for m in ctx.history],
        "cost": [float(c.flops) for c in ctx.cost],
        "releases": [int(r) for r in ctx.releases],
        "cohort_ids": {f"{r}:{c}": int(v)
                       for (r, c), v in ctx._cohort_ids.items()},
    }
    return mgr.save_state(rnd + 1, state,
                          metadata={"framework": ctx.fed.framework,
                                    "rounds": int(ctx.fed.rounds)})


def restore_run(directory: str, ctx, program, schedule,
                step: Optional[int] = None) -> Tuple[int, int]:
    """Load the latest (or ``step``-th) snapshot from ``directory`` into
    a freshly constructed run -> (start_round, rollovers)."""
    st, _ = CheckpointManager(directory).restore_state(step)
    program.load_state_dict(ctx, st["program"])
    schedule.load_jobs([Job(int(j["client"]), int(j["start"]),
                            int(j["arrival"]), j["payload"])
                        for j in st["jobs"]])
    if st["sched_rngs"] is not None:
        schedule.load_rng_state(st["sched_rngs"])
    ctx.secagg.load_state_dict(st["secagg"])
    ctx.ledger.default_hop = st["ledger"]["default_hop"]
    ctx.ledger.events = [M.CommEvent(r, c, name, d, b, hop)
                         for r, c, name, d, b, hop
                         in st["ledger"]["events"]]
    ctx.history[:] = [M.RoundMetrics(r, acc, loss, cb, fl, epsilon=eps)
                      for r, acc, loss, cb, fl, eps in st["history"]]
    for c, fl in zip(ctx.cost, st["cost"]):
        c.flops = fl
    ctx.releases[:] = [int(r) for r in st["releases"]]
    ctx._cohort_ids = {}
    for key, v in st["cohort_ids"].items():
        r, c = key.split(":")
        ctx._cohort_ids[(int(r), int(c))] = int(v)
    return int(st["round"]), int(st["rollovers"])
