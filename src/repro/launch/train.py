"""Runnable training driver (CPU-scale): LoRA fine-tune of any --arch
(reduced variant by default) on the synthetic Markov LM corpus.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 50 --batch 8 --seq 64 [--full-size] [--ckpt-dir /tmp/ck]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import FedConfig
from repro.configs.registry import ARCHS, get_config
from repro.core.fedavg import make_fns
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.models.factory import build_model
from repro.peft import lora as lora_lib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-tiny", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (big!) instead of .reduced()")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_size and not args.arch.startswith("gpt2"):
        cfg = cfg.reduced()
    model = build_model(cfg)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"family={cfg.family}")

    key = jax.random.PRNGKey(args.seed)
    base = model.init(key)
    fed = FedConfig(lora_rank=args.rank, lr=args.lr, lora_dropout=0.0,
                    lora_targets=lora_lib.default_targets(cfg))
    fns = make_fns(model, fed, task="generative")
    lt = lora_lib.init_lora(jax.random.fold_in(key, 1), base,
                            fed.lora_targets, args.rank)
    opt = fns["opt_init"](lt)
    print(f"LoRA params: {lora_lib.n_params(lt)/1e3:.1f}k "
          f"(targets={fed.lora_targets})")

    corpus = synthetic.markov_corpus(200_000, cfg.vocab_size,
                                     seed=args.seed)
    batches = synthetic.lm_batches(corpus, args.batch, args.seq,
                                   seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    t0, losses = time.time(), []
    for step in range(args.steps):
        batch = next(batches)
        jb = {"tokens": jnp.asarray(batch["tokens"][:, :args.seq]),
              "lengths": jnp.full((args.batch,), args.seq, jnp.int32),
              "labels": jnp.zeros((args.batch,), jnp.int32)}
        if cfg.n_image_tokens:
            jb["img_embeds"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, step),
                (args.batch, cfg.n_image_tokens, cfg.image_embed_dim))
        if cfg.is_encoder_decoder:
            jb["enc_embeds"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, step),
                (args.batch, cfg.encoder_seq_len, cfg.d_model))
        key, sub = jax.random.split(key)
        lt, opt, loss = fns["train_step"](base, lt, opt, jb, sub)
        losses.append(float(loss))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {losses[-1]:.4f}  "
                  f"({(time.time()-t0)/(step+1):.2f}s/step)")
        if ckpt and (step + 1) % 25 == 0:
            ckpt.save(step + 1, lt, {"loss": losses[-1]})

    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return 0 if last < first else 1


if __name__ == "__main__":
    raise SystemExit(main())
