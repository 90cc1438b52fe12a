"""Time the flash-attention kernel alone on a TPU, forward and forward +
backward, at the shapes the models run it at.

Each timing is one jitted ``lax.scan`` over ``--layers`` stacked inputs
(distinct per layer, so nothing is shared or hoisted), warmed up, then run
``--repeats`` times; the median wall time over the layers is the per-call
time.  Variants: ``rule`` (heads per step as ``pick_heads_per_step``
chooses), ``hb<n>`` (forced), and ``parent`` (the kernel module given by
``--parent-kernel``, e.g. from an unpacked older commit).

    PYTHONPATH=src python scripts/time_flash_attention.py \\
        [--parent-kernel old/src/repro/kernels/flash_attention.py] \\
        [--sweep gpt2-train=1,4,8,16,32] [--out fa_timing.json]

On a CPU it runs the kernels in interpret mode (a check of the script,
not a timing).
"""
import argparse
import importlib.util
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa

# name: (clients, B, H, KV, S, D, backward)
SHAPES = {
    # GPT-2 124M, batch 16 x 80: the local update (3 clients, vmapped) and
    # one evaluation batch.
    "gpt2-train": (3, 16, 12, 12, 80, 64, True),
    "gpt2-eval": (0, 16, 12, 12, 80, 64, False),
    # Qwen2-1.5B (GQA 12/2, head_dim 128) at batch 2 x 80.
    "qwen2-train": (3, 2, 12, 2, 80, 128, True),
    # A long sequence: full 128 x 128 blocks.
    "long-2048": (0, 1, 12, 12, 2048, 64, True),
    "long-2048-gqa": (0, 1, 12, 2, 2048, 128, True),
}
TINY = {name: (c, 2, h, kv, 16, 8, bwd)
        for name, (c, _, h, kv, _, _, bwd) in SHAPES.items()}


def load_parent(path):
    spec = importlib.util.spec_from_file_location("parent_flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.flash_attention


def attention_fn(variant, parent, interpret, S):
    blk = min(128, S)
    if variant == "parent":
        return lambda q, k, v: parent(q, k, v, causal=True, bq=blk, bkv=blk,
                                      interpret=interpret)
    hb = 0 if variant == "rule" else int(variant[2:])
    return lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, bq=blk, bkv=blk, heads_per_step=hb,
        interpret=interpret)


def timed(shape, variant, parent, interpret, layers, repeats, seed):
    clients, B, H, KV, S, D, backward = shape
    attn = attention_fn(variant, parent, interpret, S)
    if backward:
        step = jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) * q),
                        argnums=(0, 1, 2))
    else:
        step = attn
    if clients:
        step = jax.vmap(step)
    lead = (layers, clients) if clients else (layers,)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], lead + (B * H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], lead + (B * KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], lead + (B * KV, S, D), jnp.float32)

    @jax.jit
    def run(q, k, v):
        def body(c, x):
            out = step(*x)
            return c + sum(jnp.sum(o[..., :1, :1]) for o in
                           jax.tree.leaves(out)), None
        return jax.lax.scan(body, jnp.float32(0), (q, k, v))[0]

    t0 = time.perf_counter()
    run(q, k, v).block_until_ready()
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        run(q, k, v).block_until_ready()
        times.append((time.perf_counter() - t0) / layers)
    return compile_s, times


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent-kernel", default=None)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--sweep", action="append", default=[],
                    help="shape=hb,hb,...: forced heads per step to time")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    on_tpu = jax.default_backend() == "tpu"
    table = SHAPES if on_tpu else TINY
    parent = load_parent(args.parent_kernel) if args.parent_kernel else None
    sweep = {}
    for item in args.sweep:
        name, hbs = item.split("=")
        sweep[name] = [f"hb{h}" for h in hbs.split(",")]
    rows = []
    for name in args.shapes.split(","):
        shape = table[name]
        _, B, H, KV, S, D, _ = shape
        blk = min(128, S)
        hb = fa.pick_heads_per_step(B * H, H // KV, blk, blk, D, 4)
        variants = (["parent"] if parent else []) + ["rule"] + \
            sweep.get(name, [])
        for variant in variants:
            row = {"shape": name, "variant": variant, "rule_hb": hb,
                   "device": jax.devices()[0].device_kind}
            try:
                compile_s, times = timed(shape, variant, parent,
                                         not on_tpu, args.layers,
                                         args.repeats, args.seed)
            except jax.errors.JaxRuntimeError as e:   # e.g. out of VMEM
                row["error"] = str(e).split("\n")[0][:300]
            else:
                row.update(per_call_ms=statistics.median(times) * 1e3,
                           min_ms=min(times) * 1e3, max_ms=max(times) * 1e3,
                           compile_s=round(compile_s, 2))
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
