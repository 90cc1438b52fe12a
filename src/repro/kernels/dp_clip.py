"""Fused DP-SGD clip-scale-accumulate Pallas TPU kernel.

The hot loop of client-side DP-SGD (privacy/dp.py): given a client's
stacked per-example LoRA gradients flattened to a (B, P) matrix, emit
the mean of the per-example-clipped rows

    out[p] = (1/B) * sum_b g[b, p] * min(1, C / ||g[b, :]||_2)

in one pass over HBM per phase.  Two pallas calls share the work:

  * ``_norm_kernel`` — grid over P blocks, accumulating the (B, 1)
    per-example squared norms in the revisited output block (fp32
    accumulation regardless of input dtype — the dtype-safe guard the
    bf16 trees need lives in the scale computation, not the leaves).
  * ``_clip_acc_kernel`` — grid over P blocks again: load the (B, bp)
    gradient block and the finished (B, 1) norms, scale each row by
    ``min(1, C / max(norm, eps))`` and reduce the example axis to a
    (1, bp) output block.  Clip, scale and accumulate are fused — the
    (B, P) per-example gradients are never re-materialized scaled.

Forward-only semantics by design (no ``custom_vjp``): the kernel runs
*on* gradients, after ``jax.grad``, so nothing ever differentiates
through it.  Dispatch lives in kernels/ops.clip_mean_rows (kernel under
the ``pallas`` policy, kernels/ref.clip_mean_rows_ref under ``xla``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.optim.clip import EPS   # one eps for host, ref and kernel


def _norm_kernel(g_ref, n_ref):
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        n_ref[...] = jnp.zeros_like(n_ref)

    g = g_ref[...].astype(jnp.float32)
    n_ref[...] += jnp.sum(g * g, axis=-1, keepdims=True)


def _clip_acc_kernel(g_ref, n_ref, o_ref, *, clip: float, inv_b: float):
    g = g_ref[...].astype(jnp.float32)                     # (B, bp)
    norm = jnp.sqrt(n_ref[...])                            # (B, 1)
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, EPS))
    o_ref[...] = jnp.sum(g * scale, axis=0,
                         keepdims=True) * jnp.float32(inv_b)


@functools.partial(jax.jit, static_argnames=("clip", "bp", "interpret"))
def dp_clip_mean_rows(g, *, clip: float, bp: int = 2048,
                      interpret: bool = True):
    """g: (B, P) stacked per-example grads -> (1, P) fp32 mean of rows
    clipped to L2 norm ``clip``.  ``P % bp == 0`` (kernels/ops pads)."""
    B, P = g.shape
    bp = min(bp, P)
    assert P % bp == 0, (P, bp)
    grid = (P // bp,)
    norms = pl.pallas_call(
        _norm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((B, bp), lambda i: (0, i))],
        out_specs=pl.BlockSpec((B, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        name="dp_clip_norm",
        interpret=interpret,
    )(g)
    return pl.pallas_call(
        functools.partial(_clip_acc_kernel, clip=clip, inv_b=1.0 / B),
        grid=grid,
        in_specs=[pl.BlockSpec((B, bp), lambda i: (0, i)),
                  pl.BlockSpec((B, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, bp), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, P), jnp.float32),
        name="dp_clip_acc",
        interpret=interpret,
    )(g, norms)
