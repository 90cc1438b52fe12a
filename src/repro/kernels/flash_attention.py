"""Flash attention Pallas TPU kernel: online-softmax blockwise attention
with causal masking, sliding windows (mixtral/recurrentgemma local
attention) and GQA via index-mapped KV head sharing.

Layout: q (BH, Sq, D), k/v (BKV, Skv, D) with BH = B*H, BKV = B*KV.
Grid (BH, nq, nkv), kv innermost; the (bq, D) output accumulator and the
online-softmax (m, l) statistics live in VMEM scratch across kv steps.
Fully-masked (q-block, kv-block) pairs are skipped with pl.when — for
causal attention that's half the work; for a sliding window all blocks
outside the band.

Differentiable via ``jax.custom_vjp`` with the standard recompute-based
flash backward: the forward saves only (q, k, v, o, lse) — nothing
(Sq, Skv)-shaped — and the backward replays the score blocks from q/k
plus the per-row logsumexp:

    p   = exp(q·kᵀ·scale − lse)          (masked, recomputed per block)
    dv  = pᵀ do
    ds  = p (do·vᵀ − D),   D = rowsum(do ∘ o)
    dq  = scale · ds k      (dq kernel: grid (BH, nq, nkv))
    dk  = scale · dsᵀ q     (dkv kernel: grid (BKV, nkv, G·nq) — the
                             innermost axis walks every q block of every
                             query head sharing the kv head, so GQA's
                             head-group sum happens in the VMEM
                             accumulator, not in HBM)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_mask(q_start, kv_start, bq: int, bkv: int, causal: bool,
                window: int):
    """(bq, bkv) boolean attend-mask for one (q-block, kv-block) pair."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, kv_pos <= q_pos)
    if window > 0:
        mask = jnp.logical_and(mask, kv_pos > q_pos - window)
    return mask


def _block_reachable(q_start, kv_start, bq: int, bkv: int, causal: bool,
                     window: int):
    """Scalar predicate: does this (q-block, kv-block) pair attend at all?"""
    reachable = True
    if causal:
        reachable = kv_start <= q_start + bq - 1
    if window > 0:
        reachable = jnp.logical_and(
            reachable, kv_start + bkv - 1 > q_start - window)
    return reachable


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, window: int, bq: int,
                bkv: int, nkv: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq + q_offset          # absolute position of first query
    kv_start = ki * bkv

    @pl.when(_block_reachable(q_start, kv_start, bq, bkv, causal, window))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _block_mask(q_start, kv_start, bq, bkv, causal, window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nkv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _fwd_call(q, k, v, causal: bool, window: int, q_offset: int, bq: int,
              bkv: int, interpret: bool):
    """Returns (o (BH, Sq, D), lse (BH, Sq, 1) fp32)."""
    BH, Sq, D = q.shape
    BKV, Skv, _ = k.shape
    G = BH // BKV
    assert Sq % bq == 0 and Skv % bkv == 0
    nq, nkv = Sq // bq, Skv // bkv
    kernel = functools.partial(
        _fwd_kernel, scale=D ** -0.5, causal=causal, window=window, bq=bq,
        bkv=bkv, nkv=nkv, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b // G, j, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)


# --------------------------------------------------------------------------- #
# Backward kernels
# --------------------------------------------------------------------------- #
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref,
               acc_ref, *, scale: float, causal: bool, window: int,
               bq: int, bkv: int, nkv: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq + q_offset
    kv_start = ki * bkv

    @pl.when(_block_reachable(q_start, kv_start, bq, bkv, causal, window))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, D)
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _block_mask(q_start, kv_start, bq, bkv, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
        dp = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - dd_ref[0])                         # (bq, bkv)
        acc_ref[...] += jax.lax.dot(ds, k,
                                    preferred_element_type=jnp.float32)

    @pl.when(ki == nkv - 1)
    def _finish():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref,
                dv_ref, acck_ref, accv_ref, *, scale: float, causal: bool,
                window: int, bq: int, bkv: int, nq: int, nt: int,
                q_offset: int):
    ki = pl.program_id(1)
    t = pl.program_id(2)                                  # g * nq + qi

    @pl.when(t == 0)
    def _init():
        acck_ref[...] = jnp.zeros_like(acck_ref)
        accv_ref[...] = jnp.zeros_like(accv_ref)

    q_start = (t % nq) * bq + q_offset
    kv_start = ki * bkv

    @pl.when(_block_reachable(q_start, kv_start, bq, bkv, causal, window))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, D)
        do = do_ref[0].astype(jnp.float32)                # (bq, D)
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _block_mask(q_start, kv_start, bq, bkv, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
        accv_ref[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bkv, D)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dd_ref[0])                         # (bq, bkv)
        acck_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bkv, D)

    @pl.when(t == nt - 1)
    def _finish():
        dk_ref[0] = (acck_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = accv_ref[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, causal: bool, window: int, q_offset: int,
              bq: int, bkv: int, interpret: bool):
    BH, Sq, D = q.shape
    BKV, Skv, _ = k.shape
    G = BH // BKV
    nq, nkv = Sq // bq, Skv // bkv
    scale = D ** -0.5
    # D_i = rowsum(do ∘ o): elementwise + reduce — XLA, nothing (Sq,Skv)
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                 keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, nkv=nkv,
                          q_offset=q_offset),
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        name="flash_attention_dq",
        interpret=interpret,
    )(q, k, v, do, lse, dd)

    nt = G * nq
    q_spec = pl.BlockSpec((1, bq, D),
                          lambda b, j, t: (b * G + t // nq, t % nq, 0))
    row_spec = pl.BlockSpec((1, bq, 1),
                            lambda b, j, t: (b * G + t // nq, t % nq, 0))
    kv_spec = pl.BlockSpec((1, bkv, D), lambda b, j, t: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, nq=nq, nt=nt,
                          q_offset=q_offset),
        grid=(BKV, nkv, nt),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((BKV, Skv, D), k.dtype),
                   jax.ShapeDtypeStruct((BKV, Skv, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, D), jnp.float32),
                        pltpu.VMEM((bkv, D), jnp.float32)],
        name="flash_attention_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, dd)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom_vjp plumbing
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, q_offset, bq, bkv, interpret):
    return _fwd_call(q, k, v, causal, window, q_offset, bq, bkv,
                     interpret)[0]


def _flash_fwd(q, k, v, causal, window, q_offset, bq, bkv, interpret):
    o, lse = _fwd_call(q, k, v, causal, window, q_offset, bq, bkv,
                       interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, q_offset, bq, bkv, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse, do, causal, window, q_offset, bq,
                     bkv, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "n_q_heads", "bq", "bkv", "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    n_q_heads: int = 0, bq: int = 128, bkv: int = 128,
                    q_offset: int = 0, interpret: bool = True):
    """q: (BH, Sq, D); k, v: (BKV, Skv, D).  GQA when BKV < BH: kv head
    index = bh//G with G = BH//BKV (requires contiguous (b, h) layout).

    Returns (BH, Sq, D).  Differentiable: ``jax.grad`` through this runs
    the recompute-based flash backward kernels (dq + GQA-aware dk/dv)."""
    _, Sq, _ = q.shape
    _, Skv, _ = k.shape
    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    return _flash(q, k, v, causal, window, q_offset, bq, bkv, interpret)
