"""Fused LoRA matmul Pallas TPU kernel:  y = x @ W + (x @ A) @ B.

The PEFT hot spot of every framework in the paper: with LoRA bound to a
projection, XLA materializes the (T, r) intermediate x@A in HBM between
two small matmuls.  This kernel keeps the rank-r panel (A-block, B-block
and the (bm, r) running x@A accumulator) resident in VMEM alongside the
main (bm, bn) accumulator, so the low-rank path costs no extra HBM
traffic — the W tiles dominate, exactly as in the un-adapted matmul.

Grid (m, n, k), k innermost; fp32 accumulators; MXU-aligned tiles
(multiples of 128 on m/n, 512 on k by default).

Differentiable via ``jax.custom_vjp``: the backward pass is two more
fused Pallas kernels that preserve the forward's no-extra-HBM-traffic
property for the low-rank path —

  * ``dx = g @ Wᵀ + (g @ Bᵀ) @ Aᵀ`` reads W/A/B in their *native* layout
    (contracting on the N axis; no XLA transposes) and keeps the (bm, r)
    ``g @ Bᵀ`` panel resident in VMEM, emitting it as the ``gb`` residual
    for the dA kernel.
  * ``dW = xᵀg``, ``dA = xᵀ(gBᵀ)`` and ``dB = (xA)ᵀg`` are three
    *separate* pallas calls, each keeping its accumulator VMEM-resident
    across the m sweep.  Keeping dW out of the dA/dB calls matters: in
    PEFT training W is a frozen closed-over constant, its cotangent is
    dropped, and jaxpr DCE then eliminates the whole dense (K, N)
    reduction — the backward costs only dx plus the two rank-r panels,
    mirroring the forward's no-extra-HBM-traffic property.  The (M, r)
    ``x @ A`` panel is saved from the forward instead of being
    recomputed — it is rank-r, i.e. free relative to any (M, K) or
    (K, N) residual.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fwd_kernel(x_ref, w_ref, a_ref, b_ref, o_ref, xa_out_ref, acc_ref,
                xa_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(
        x, w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    xa_ref[...] += jax.lax.dot(
        x, a_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        low = jax.lax.dot(xa_ref[...], b_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + low).astype(o_ref.dtype)
        xa_out_ref[...] = xa_ref[...]


def _fwd_call(x, w, a, b, bm: int, bk: int, bn: int, interpret: bool):
    """Returns (y (M, N), xa (M, r)) — xa is the resident x@A panel."""
    M, K = x.shape
    _, N = w.shape
    r = a.shape[-1]
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    nm, nn, nk = M // bm, N // bn, K // bk
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
                   pl.BlockSpec((bm, r), lambda i, j, k: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, N), x.dtype),
                   jax.ShapeDtypeStruct((M, r), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
        name="lora_matmul_fwd",
        interpret=interpret,
    )(x, w, a, b)


# --------------------------------------------------------------------------- #
# Backward kernels
# --------------------------------------------------------------------------- #
def _dx_kernel(g_ref, w_ref, a_ref, b_ref, dx_ref, gb_out_ref, acc_ref,
               gb_ref, *, nn: int):
    """dx[m, k] = Σ_n g[m, n] w[k, n]  +  (Σ_n g[m, n] b[r, n]) aᵀ[r, k].

    Grid (m, k, n), n innermost.  W/A/B are read in their native (K, N) /
    (K, r) / (r, N) layouts — the contraction runs over the N axis, so no
    host/XLA transpose is ever materialized.  The (bm, r) g@Bᵀ panel is
    emitted once (at k-block 0) as the residual for the dA kernel.
    """
    j = pl.program_id(1)
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    g = g_ref[...].astype(jnp.float32)                      # (bm, bn)
    acc_ref[...] += jax.lax.dot_general(
        g, w_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (bm, bk)
    gb_ref[...] += jax.lax.dot_general(
        g, b_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (bm, r)

    @pl.when(n == nn - 1)
    def _finish():
        low = jax.lax.dot_general(
            gb_ref[...], a_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bm, bk)
        dx_ref[...] = (acc_ref[...] + low).astype(dx_ref.dtype)

        @pl.when(j == 0)
        def _emit_gb():
            gb_out_ref[...] = gb_ref[...]


def _dx_call(g, w, a, b, bm: int, bk: int, bn: int, interpret: bool,
             out_dtype):
    M, N = g.shape
    K = w.shape[0]
    r = a.shape[-1]
    nm, nk, nn = M // bm, K // bk, N // bn
    return pl.pallas_call(
        functools.partial(_dx_kernel, nn=nn),
        grid=(nm, nk, nn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, n: (i, n)),
            pl.BlockSpec((bk, bn), lambda i, j, n: (j, n)),
            pl.BlockSpec((bk, r), lambda i, j, n: (j, 0)),
            pl.BlockSpec((r, bn), lambda i, j, n: (0, n)),
        ],
        out_specs=[pl.BlockSpec((bm, bk), lambda i, j, n: (i, j)),
                   pl.BlockSpec((bm, r), lambda i, j, n: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, K), out_dtype),
                   jax.ShapeDtypeStruct((M, r), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
        name="lora_matmul_dx",
        interpret=interpret,
    )(g, w, a, b)


def _dw_kernel(x_ref, g_ref, dw_ref, accw_ref, *, nm: int):
    """dW[k, n] = Σ_m x[m, k] g[m, n].  Grid (k, n, m), m innermost.

    dW lives in its OWN pallas call (not fused with dA/dB) so that when
    W is a frozen closed-over constant — every PEFT step in this repo —
    the dropped cotangent lets jaxpr DCE remove this whole dense (K, N)
    reduction from the backward."""
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        accw_ref[...] = jnp.zeros_like(accw_ref)

    accw_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), g_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (bk, bn)

    @pl.when(t == nm - 1)
    def _finish():
        dw_ref[...] = accw_ref[...].astype(dw_ref.dtype)


def _dw_call(x, g, bm: int, bk: int, bn: int, interpret: bool, w_dtype):
    M, K = x.shape
    N = g.shape[1]
    nk, nn, nm = K // bk, N // bn, M // bm
    return pl.pallas_call(
        functools.partial(_dw_kernel, nm=nm),
        grid=(nk, nn, nm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (t, i)),
            pl.BlockSpec((bm, bn), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), w_dtype),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        name="lora_matmul_dw",
        interpret=interpret,
    )(x, g)


def _panel_grad_kernel(lhs_ref, panel_ref, out_ref, acc_ref, *, nm: int):
    """out[l, r] = Σ_m lhs[m, l] panel[m, r] — the shared shape of the
    rank-r grads dA = xᵀ(gBᵀ) and dB = ((xA)ᵀ g)ᵀ-style reductions.
    Grid (l, m), m innermost; the (bl, r) accumulator stays resident."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...].astype(jnp.float32), panel_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (bl, r)

    @pl.when(t == nm - 1)
    def _finish():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _panel_grad_call(lhs, panel, bm: int, bl: int, interpret: bool,
                     out_dtype):
    """lhs (M, L), panel (M, r) fp32 -> (L, r)."""
    M, L = lhs.shape
    r = panel.shape[-1]
    nl, nm = L // bl, M // bm
    return pl.pallas_call(
        functools.partial(_panel_grad_kernel, nm=nm),
        grid=(nl, nm),
        in_specs=[pl.BlockSpec((bm, bl), lambda i, t: (t, i)),
                  pl.BlockSpec((bm, r), lambda i, t: (t, 0))],
        out_specs=pl.BlockSpec((bl, r), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, r), out_dtype),
        scratch_shapes=[pltpu.VMEM((bl, r), jnp.float32)],
        name="lora_matmul_panel_grad",
        interpret=interpret,
    )(lhs, panel)


# --------------------------------------------------------------------------- #
# custom_vjp plumbing
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _lora_matmul(x, w, a, b, bm, bk, bn, interpret):
    y, _ = _fwd_call(x, w, a, b, bm, bk, bn, interpret)
    return y


def _lora_matmul_fwd(x, w, a, b, bm, bk, bn, interpret):
    y, xa = _fwd_call(x, w, a, b, bm, bk, bn, interpret)
    return y, (x, w, a, b, xa)


def _lora_matmul_bwd(bm, bk, bn, interpret, res, g):
    x, w, a, b, xa = res
    g = g.astype(x.dtype)
    dx, gb = _dx_call(g, w, a, b, bm, bk, bn, interpret, x.dtype)
    dw = _dw_call(x, g, bm, bk, bn, interpret, w.dtype)
    da = _panel_grad_call(x, gb, bm, bk, interpret, a.dtype)
    db = _panel_grad_call(g, xa, bm, bn, interpret, b.dtype).T
    return dx, dw, da, db.astype(b.dtype)


_lora_matmul.defvjp(_lora_matmul_fwd, _lora_matmul_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def lora_matmul(x, w, a, b, *, bm: int = 128, bk: int = 512, bn: int = 128,
                interpret: bool = True):
    """x: (M, K); w: (K, N); a: (K, r); b: (r, N) -> (M, N).

    Scale (alpha/r) is expected folded into ``b`` (peft/lora.bind).
    Differentiable: ``jax.grad`` through this runs the fused Pallas
    backward kernels (dx / dW / dA / dB)."""
    M, K = x.shape
    _, N = w.shape
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    return _lora_matmul(x, w, a, b, bm, bk, bn, interpret)
