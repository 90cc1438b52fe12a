"""RWKV-6 WKV recurrence Pallas TPU kernel.

Per (batch, head): S_t = diag(w_t) S_{t-1} + k_t^T v_t,
                   y_t = r_t (S_{t-1} + diag(u) k_t^T v_t).

The (D, D) state stays resident in VMEM across the whole sequence —
the property that makes RWKV decode O(1) in context length also makes
the train-time scan a single-buffer VMEM kernel (64x64 fp32 = 16 KiB).
Grid (BH, S/bt), time innermost, fori over bt steps inside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sf_ref, s_ref, *,
            bt: int, nt: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)          # (bt, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = jnp.exp(lw_ref[0].astype(jnp.float32))
    u = u_ref[0].astype(jnp.float32)          # (D,)

    def step(t, S):
        kv = k[t][:, None] * v[t][None, :]            # (D, D)
        y = r[t] @ (S + u[:, None] * kv)              # (D,)
        o_ref[0, t, :] = y.astype(o_ref.dtype)
        return w[t][:, None] * S + kv

    S = jax.lax.fori_loop(0, bt, step, s_ref[0])
    s_ref[0] = S

    @pl.when(ti == nt - 1)
    def _finish():
        sf_ref[...] = s_ref[...].astype(sf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def rwkv6_scan(r, k, v, logw, u, *, bt: int = 64, interpret: bool = True):
    """r,k,v,logw: (BH, S, D); u: (BH, D).  Returns (y (BH,S,D),
    S_final (BH,D,D))."""
    BH, S, D = r.shape
    bt = min(bt, S)
    assert S % bt == 0
    nt = S // bt
    kernel = functools.partial(_kernel, bt=bt, nt=nt)
    return pl.pallas_call(
        kernel,
        grid=(BH, nt),
        in_specs=[
            pl.BlockSpec((1, bt, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, bt, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, D), lambda b, t: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, D), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, D, D), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
                   jax.ShapeDtypeStruct((BH, D, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, D, D), jnp.float32)],
        name="rwkv6_scan",
        interpret=interpret,
    )(r, k, v, logw, u)
