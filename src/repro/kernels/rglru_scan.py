"""RG-LRU recurrence Pallas TPU kernel:  h_t = a_t * h_{t-1} + b_t.

RecurrentGemma's temporal hot loop.  Gates (a, b = sqrt(1-a^2)*i*x) are
computed by dense matmuls outside (models/rglru.py); the kernel runs the
elementwise recurrence with the (1, bw) hidden state resident in VMEM
across time blocks — no HBM round-trip per step, unlike an XLA while
loop which spills the carry.

Grid (B, W/bw, S/bt), time innermost; within a block a sequential fori
over bt steps (elementwise VPU work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, o_ref, hf_ref, h_ref, *, bt: int,
            nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)          # (bt, bw)
    b = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bt, step, h_ref[0])
    h_ref[0] = h

    @pl.when(ti == nt - 1)
    def _finish():
        hf_ref[...] = h_ref[...].astype(hf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bw", "bt", "interpret"))
def rglru_scan(a, b, h0, *, bw: int = 128, bt: int = 128,
               interpret: bool = True):
    """a, b: (B, S, W); h0: (B, W).  Returns (h (B,S,W), h_final (B,W))."""
    B, S, W = a.shape
    bw = min(bw, W)
    bt = min(bt, S)
    assert W % bw == 0 and S % bt == 0
    nt = S // bt
    kernel = functools.partial(_kernel, bt=bt, nt=nt)
    return pl.pallas_call(
        kernel,
        grid=(B, W // bw, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda bb, w, t: (bb, t, w)),
            pl.BlockSpec((1, bt, bw), lambda bb, w, t: (bb, t, w)),
            pl.BlockSpec((1, bw), lambda bb, w, t: (bb, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bw), lambda bb, w, t: (bb, t, w)),
            pl.BlockSpec((1, bw), lambda bb, w, t: (bb, w)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, W), jnp.float32),
                   jax.ShapeDtypeStruct((B, W), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        name="rglru_scan",
        interpret=interpret,
    )(a, b, h0)
