"""Flash attention Pallas TPU kernel: online-softmax blockwise attention
with causal masking, sliding windows (mixtral/recurrentgemma local
attention) and GQA via index-mapped KV head sharing.

Layout: q (BH, Sq, D), k/v (BKV, Skv, D) with BH = B*H, BKV = B*KV.
Grid (BH/hb, nq, nkv), kv innermost: each step takes ``hb`` contiguous
(batch, head) rows and walks them in a static loop, so that a step's work
covers its fixed cost, which one head's blocks do not.  The
(hb, bq, D) output accumulator and the online-softmax (m, l) statistics
live in VMEM scratch across kv steps.  Fully-masked (q-block, kv-block)
pairs are skipped with pl.when — for causal attention that's half the
work; for a sliding window all blocks outside the band.

A step's hb query heads share whole kv heads (hb % G == 0: hb/G kv heads
per step) or lie inside one kv head's group (G % hb == 0: one kv head).
Each head's arithmetic is the same for every hb;
``pick_heads_per_step`` picks hb from the shapes.

Differentiable via ``jax.custom_vjp`` with the standard recompute-based
flash backward: the forward saves only (q, k, v, o, lse) — nothing
(Sq, Skv)-shaped — and the backward replays the score blocks from q/k
plus the per-row logsumexp:

    p   = exp(q·kᵀ·scale − lse)          (masked, recomputed per block)
    dv  = pᵀ do
    ds  = p (do·vᵀ − D),   D = rowsum(do ∘ o)
    dq  = scale · ds k      (dq kernel: grid (BH/hb, nq, nkv))
    dk  = scale · dsᵀ q     (dkv kernel: grid (BKV/kvb, nkv, nq·G·kvb/hb)
                             — the innermost axis walks every q block of
                             every query head sharing the step's kv
                             heads, so GQA's head-group sum happens in
                             the VMEM accumulator, not in HBM)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Head blocking (``pick_heads_per_step``).  A grid step has a fixed cost
# (issue, DMA latency) that one (80, 64) or even one (128, 128) score
# block does not cover; a step of this many score elements (hb·bq·bkv:
# 16 heads at 80 tokens, 6 at 128-token blocks) does.
_STEP_SCORES = 96 * 1024
# Budget for one step's double-buffered blocks and scratch: three quarters
# of the 16 MiB scoped-VMEM default, the rest left to the kernel's own
# temporaries (score, probability and gradient tiles).
_STEP_VMEM_BYTES = 12 << 20
_LANES = 128


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) block, padded to the (8·4/itemsize,
    128) tile: a (bq, 1) row of statistics takes a full 128 lanes."""
    sub = 32 // itemsize
    return (-(-rows // sub) * sub) * (-(-cols // _LANES) * _LANES) * itemsize


def step_vmem_bytes(hb: int, group: int, bq: int, bkv: int, d: int,
                    itemsize: int) -> int:
    """VMEM of one grid step at ``hb`` heads per step, the largest of the
    three passes: double-buffered in/out blocks plus fp32 scratch."""
    kvb = max(hb // group, 1)
    q = _tile_bytes(bq, d, itemsize)
    kv = _tile_bytes(bkv, d, itemsize)
    q32, kv32 = _tile_bytes(bq, d, 4), _tile_bytes(bkv, d, 4)
    row = _tile_bytes(bq, 1, 4)
    fwd = 2 * (hb * (2 * q + row) + 2 * kvb * kv) + hb * (q32 + 2 * row)
    dq = 2 * (hb * (3 * q + 2 * row) + 2 * kvb * kv) + hb * q32
    dkv = 2 * (hb * (2 * q + 2 * row) + 4 * kvb * kv) + 2 * kvb * kv32
    return max(fwd, dq, dkv)


def pick_heads_per_step(bh: int, group: int, bq: int, bkv: int, d: int,
                        itemsize: int) -> int:
    """(batch·head) rows per grid step, from shapes only: the smallest
    divisor of ``bh`` whose step holds ``_STEP_SCORES`` score elements,
    else the largest that fits ``_STEP_VMEM_BYTES``; always a multiple or
    a divisor of the GQA group ``group``, so a step's query heads share
    whole kv heads."""
    best = 1
    for hb in range(1, bh + 1):
        if bh % hb or (hb % group and group % hb):
            continue
        if step_vmem_bytes(hb, group, bq, bkv, d, itemsize) > \
                _STEP_VMEM_BYTES:
            break
        best = hb
        if hb * bq * bkv >= _STEP_SCORES:
            break
    return best


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


def _kv_heads(hb: int, group: int):
    """Static (kv head in block, its query heads in block) pairs."""
    per_kv = min(hb, group)
    return [(j, range(j * per_kv, (j + 1) * per_kv))
            for j in range(hb // per_kv)]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, window: int, bq: int,
                bkv: int, nkv: int, q_offset: int, hb: int, group: int):
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
        mask = _block_mask(q_start, kv_start, bq, bkv, causal, window)
        for j, heads in _kv_heads(hb, group):
            k = k_ref[j].astype(jnp.float32)              # (bkv, D)
            v = v_ref[j].astype(jnp.float32)
            for h in heads:
                q = q_ref[h].astype(jnp.float32) * scale  # (bq, D)
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                s = jnp.where(mask, s, NEG_INF)

                m_prev = m_ref[h]                         # (bq, 1)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(
                    p, v, preferred_element_type=jnp.float32)
                m_ref[h] = m_new

    @pl.when(ki == nkv - 1)
    def _finish():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _blocking(BH: int, BKV: int, hb: int):
    """(G, kvb, gpb): query heads per kv head, kv heads per step, and
    steps per kv head group along the dkv kernel's inner axis."""
    G = BH // BKV
    assert BH % hb == 0 and (hb % G == 0 or G % hb == 0), (BH, G, hb)
    kvb = max(hb // G, 1)
    return G, kvb, G * kvb // hb


def _fwd_call(q, k, v, causal: bool, window: int, q_offset: int, bq: int,
              bkv: int, hb: int, interpret: bool):
    """Returns (o (BH, Sq, D), lse (BH, Sq, 1) fp32)."""
    BH, Sq, D = q.shape
    BKV, Skv, _ = k.shape
    G, kvb, _ = _blocking(BH, BKV, hb)
    assert Sq % bq == 0 and Skv % bkv == 0
    nq, nkv = Sq // bq, Skv // bkv
    kernel = functools.partial(
        _fwd_kernel, scale=D ** -0.5, causal=causal, window=window, bq=bq,
        bkv=bkv, nkv=nkv, q_offset=q_offset, hb=hb, group=G)
    q_spec = pl.BlockSpec((hb, bq, D), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((kvb, bkv, D),
                           lambda b, i, j: (b * hb // (G * kvb), j, 0))
    return pl.pallas_call(
        kernel,
        grid=(BH // hb, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((hb, bq, 1), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, bq, D), jnp.float32),
                        pltpu.VMEM((hb, bq, 1), jnp.float32),
                        pltpu.VMEM((hb, bq, 1), jnp.float32)],
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)


# --------------------------------------------------------------------------- #
# Backward kernels
# --------------------------------------------------------------------------- #
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref,
               acc_ref, *, scale: float, causal: bool, window: int,
               bq: int, bkv: int, nkv: int, q_offset: int, hb: int,
               group: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq + q_offset
    kv_start = ki * bkv

    @pl.when(_block_reachable(q_start, kv_start, bq, bkv, causal, window))
    def _compute():
        mask = _block_mask(q_start, kv_start, bq, bkv, causal, window)
        for j, heads in _kv_heads(hb, group):
            k = k_ref[j].astype(jnp.float32)              # (bkv, D)
            v = v_ref[j].astype(jnp.float32)
            for h in heads:
                q = q_ref[h].astype(jnp.float32)          # (bq, D)
                s = jax.lax.dot_general(q * scale, k,
                                        (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                p = jnp.where(mask, jnp.exp(s - lse_ref[h]), 0.0)
                dp = jax.lax.dot_general(
                    do_ref[h].astype(jnp.float32), v,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = p * (dp - dd_ref[h])                 # (bq, bkv)
                acc_ref[h] += jax.lax.dot(ds, k,
                                          preferred_element_type=jnp.float32)

    @pl.when(ki == nkv - 1)
    def _finish():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref,
                dv_ref, acck_ref, accv_ref, *, scale: float, causal: bool,
                window: int, bq: int, bkv: int, nq: int, nt: int,
                q_offset: int, hb: int, group: int):
    ki = pl.program_id(1)
    t = pl.program_id(2)                          # head sub-group * nq + qi

    @pl.when(t == 0)
    def _init():
        acck_ref[...] = jnp.zeros_like(acck_ref)
        accv_ref[...] = jnp.zeros_like(accv_ref)

    q_start = (t % nq) * bq + q_offset
    kv_start = ki * bkv

    @pl.when(_block_reachable(q_start, kv_start, bq, bkv, causal, window))
    def _compute():
        mask = _block_mask(q_start, kv_start, bq, bkv, causal, window)
        for j, heads in _kv_heads(hb, group):
            k = k_ref[j].astype(jnp.float32)              # (bkv, D)
            v = v_ref[j].astype(jnp.float32)
            acck, accv = acck_ref[j], accv_ref[j]
            for h in heads:
                q = q_ref[h].astype(jnp.float32)          # (bq, D)
                do = do_ref[h].astype(jnp.float32)        # (bq, D)
                s = jax.lax.dot_general(q * scale, k,
                                        (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                p = jnp.where(mask, jnp.exp(s - lse_ref[h]), 0.0)
                accv = accv + jax.lax.dot_general(
                    p, do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # (bkv, D)
                dp = jax.lax.dot_general(
                    do, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = p * (dp - dd_ref[h])                 # (bq, bkv)
                acck = acck + jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # (bkv, D)
            acck_ref[j], accv_ref[j] = acck, accv

    @pl.when(t == nt - 1)
    def _finish():
        dk_ref[...] = (acck_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = accv_ref[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, causal: bool, window: int, q_offset: int,
              bq: int, bkv: int, hb: int, interpret: bool):
    BH, Sq, D = q.shape
    BKV, Skv, _ = k.shape
    G, kvb, gpb = _blocking(BH, BKV, hb)
    nq, nkv = Sq // bq, Skv // bkv
    scale = D ** -0.5
    # D_i = rowsum(do ∘ o): elementwise + reduce — XLA, nothing (Sq,Skv)
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                 keepdims=True)

    q_spec = pl.BlockSpec((hb, bq, D), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((hb, bq, 1), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((kvb, bkv, D),
                           lambda b, i, j: (b * hb // (G * kvb), j, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, nkv=nkv,
                          q_offset=q_offset, hb=hb, group=G),
        grid=(BH // hb, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((hb, bq, D), jnp.float32)],
        name="flash_attention_dq",
        interpret=interpret,
    )(q, k, v, do, lse, dd)

    nt = gpb * nq
    q_spec = pl.BlockSpec((hb, bq, D),
                          lambda b, j, t: (b * gpb + t // nq, t % nq, 0))
    row_spec = pl.BlockSpec((hb, bq, 1),
                            lambda b, j, t: (b * gpb + t // nq, t % nq, 0))
    kv_spec = pl.BlockSpec((kvb, bkv, D), lambda b, j, t: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, nq=nq, nt=nt,
                          q_offset=q_offset, hb=hb, group=G),
        grid=(BKV // kvb, nkv, nt),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((BKV, Skv, D), k.dtype),
                   jax.ShapeDtypeStruct((BKV, Skv, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((kvb, bkv, D), jnp.float32),
                        pltpu.VMEM((kvb, bkv, D), jnp.float32)],
        name="flash_attention_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, dd)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom_vjp plumbing
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, q_offset, bq, bkv, hb, interpret):
    return _fwd_call(q, k, v, causal, window, q_offset, bq, bkv, hb,
                     interpret)[0]


def _flash_fwd(q, k, v, causal, window, q_offset, bq, bkv, hb, interpret):
    o, lse = _fwd_call(q, k, v, causal, window, q_offset, bq, bkv, hb,
                       interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, q_offset, bq, bkv, hb, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse, do, causal, window, q_offset, bq,
                     bkv, hb, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "n_q_heads", "bq", "bkv", "q_offset",
    "heads_per_step", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    n_q_heads: int = 0, bq: int = 128, bkv: int = 128,
                    q_offset: int = 0, heads_per_step: int = 0,
                    interpret: bool = True):
    """q: (BH, Sq, D); k, v: (BKV, Skv, D).  GQA when BKV < BH: kv head
    index = bh//G with G = BH//BKV (requires contiguous (b, h) layout).

    ``heads_per_step`` (batch·head) rows go through each grid step; 0
    lets ``pick_heads_per_step`` choose from the shapes.

    Returns (BH, Sq, D).  Differentiable: ``jax.grad`` through this runs
    the recompute-based flash backward kernels (dq + GQA-aware dk/dv)."""
    BH, Sq, D = q.shape
    BKV, Skv, _ = k.shape
    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    hb = heads_per_step or pick_heads_per_step(
        BH, BH // BKV, bq, bkv, D, q.dtype.itemsize)
    return _flash(q, k, v, causal, window, q_offset, bq, bkv, hb, interpret)
