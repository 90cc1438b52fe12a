"""Readings that set the limits of the correctness comparison.

    python3 bench/control.py --workload <name> --seeds 11 12 13

At the cell's own sizes, for each seed, the float32 reference runs the
first round, and stands in for the program three times:

- ``control``: the reference in bfloat16 with default-precision products
  (the precision below the float32 the configuration states);
- ``half_batch``: float32, each step fed only the first half of its
  batch, the mean taken over that half;
- ``altered``: float32, one client's adapter altered where it is
  produced (its B factors scaled by 1.5, as a doubly folded or wrong
  LoRA scale would);
- ``exchange_left_out`` (cells on more than one chip): float32, the
  server given chip 0's client adapters in place of every other chip's.

A state left unchanged reads 1 on the adapter numbers by construction
and needs no run.  Each stand-in's numbers (bench/compare.py) print on
one line per seed.  The benchmark's own runs never run this.
"""
import argparse
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def epoch_feed(shards, batch_size: int, seed: int):
    """One shuffled epoch of full batches per client."""
    import numpy as np
    out = []
    for c, shard in enumerate(shards):
        n = len(shard["tokens"])
        perm = np.random.default_rng([seed, c]).permutation(n)
        perm = perm[: (n // batch_size) * batch_size].reshape(-1, batch_size)
        out.append(({k: v[perm] for k, v in shard.items()},
                     np.ones(len(perm), bool)))
    return out


def readings_for_seed(cell, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import compare, harness, spec
    from bench import weights as bweights
    from bench.references import decoder

    arch, _, shards, test = harness.make_inputs(cell, seed)
    t = cell.traffic
    weights = bweights.make_weights(seed, arch)
    lora0 = bweights.make_lora(seed, arch, t["fed"]["lora_rank"])
    feed = epoch_feed(shards, t["batch_size"], seed)
    init = jax.tree.map(np.asarray, lora0)

    fw = spec.framework(t["framework"])

    def run(per_client, **kw):
        return fw.reference_round(arch, weights, lora0, t, per_client,
                                  shards, test, **kw)

    ref = run(feed)
    out = {}
    control = run(feed, dtype=jnp.bfloat16,
                  precision=jax.lax.Precision.DEFAULT)
    out["control"] = compare.readings(control, ref, init, ref["grad_norms"])
    half = [({k: v[:, : t["batch_size"] // 2] for k, v in b.items()}, val)
            for b, val in feed]
    out["half_batch"] = compare.readings(run(half), ref, init,
                                         ref["grad_norms"])
    altered = dict(ref, client_lora=list(ref["client_lora"]))
    altered["client_lora"][0] = {
        tgt: {"a": ab["a"], "b": np.asarray(ab["b"]) * 1.5}
        for tgt, ab in ref["client_lora"][0].items()}
    altered["global_lora"] = jax.tree.map(np.asarray, decoder.fedavg(
        altered["client_lora"], [len(s["tokens"]) for s in shards]))
    out["altered"] = compare.readings(altered, ref, init, ref["grad_norms"])
    if cell.chips > 1:
        # the server gets chip 0's client results in place of the others'
        n = len(shards)
        per_chip = n // cell.chips
        gathered = [ref["client_lora"][c % per_chip] for c in range(n)]
        left_out = dict(ref, client_lora=gathered)
        left_out["global_lora"] = jax.tree.map(np.asarray, decoder.fedavg(
            gathered, [len(s["tokens"]) for s in shards]))
        out["exchange_left_out"] = compare.readings(left_out, ref, init,
                                                    ref["grad_norms"])
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import json

    import jax

    from bench import harness, spec
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    harness.check_devices(cell.chips)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = readings_for_seed(cell, seed)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t0,
                          "device": jax.devices()[0].device_kind,
                          **out}), flush=True)


if __name__ == "__main__":
    main()
