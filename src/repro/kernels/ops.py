"""Model-facing jit'd wrappers around the Pallas kernels: reshape from
model-layer layouts to kernel layouts, choose block shapes, and select
interpret mode (Python emulation on CPU; compiled on real TPU).

This module is also the **kernel dispatch layer**: ``ModelConfig``
carries a ``kernel_policy`` (``"xla" | "pallas" | "auto"``) which the
round engine / model facade resolve into an ambient policy scope here
(mirroring models/common's ``shard_hints`` pattern).  The LoRA
projection (peft/lora.lora_apply), attention (models/attention) and the
KD loss (models/loss.kd_kl) consult ``use_pallas()`` at trace time, so
every framework trains *through* the fused fwd+bwd kernels when the
policy selects them — the three hot-path kernels are differentiable via
``jax.custom_vjp`` (kernels/{lora_matmul,kd_loss,flash_attention}).

``auto`` resolves to ``pallas`` on a real TPU backend and ``xla``
everywhere else (interpret-mode Pallas is a correctness tool, not a fast
path).
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from repro.kernels import dp_clip as _dp
from repro.kernels import flash_attention as _fa
from repro.kernels import kd_loss as _kd
from repro.kernels import lora_matmul as _lm
from repro.kernels import quantize as _q
from repro.kernels import rglru_scan as _rg
from repro.kernels import rwkv6_scan as _rw

INTERPRET = jax.default_backend() != "tpu"

# --------------------------------------------------------------------------- #
# Kernel policy (ModelConfig.kernel_policy -> ambient dispatch scope)
# --------------------------------------------------------------------------- #
POLICIES = ("xla", "pallas", "auto")
_ACTIVE = "xla"


def resolve(policy: str) -> str:
    """``auto`` -> ``pallas`` on TPU, ``xla`` elsewhere."""
    if policy not in POLICIES:
        raise ValueError(f"unknown kernel_policy {policy!r} "
                         f"(expected one of {POLICIES})")
    if policy == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return policy


@contextlib.contextmanager
def policy_scope(policy: str):
    """Make ``policy`` the ambient kernel policy while tracing/executing.

    Entered by core/rounds.run_federated (covers both execution backends)
    and by models/factory.Model.forward (direct model use)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = resolve(policy)
    try:
        yield
    finally:
        _ACTIVE = prev


def use_pallas() -> bool:
    return _ACTIVE == "pallas"


# --------------------------------------------------------------------------- #
# Block-shape selection
# --------------------------------------------------------------------------- #
def fit_block(n: int, cap: int, align: int = 128) -> int:
    """Block size for a dim of ``n`` under a VMEM budget of ``cap``:
    the largest divisor of ``n`` that is <= ``cap``, preferring
    lane-aligned (multiple-of-``align``) divisors.  This is the
    chunk-size fallback for dims the default block doesn't divide:
    e.g. V=151936 with bv=2048 yields 128 (aligned) rather than
    silently streaming the whole vocab through one VMEM block — the
    memory wall the kernels exist to avoid.

    Dims with only pathological divisors (primes, 50257-style vocabs
    whose best divisor would shred the grid) fall back to the whole dim
    as a single block rather than a degenerate tiny-block grid: a
    too-large block is slow-but-correct, a width-1 grid of thousands of
    steps is neither."""
    cap = min(cap, n)
    best = 1
    for d in range(cap, 0, -1):
        if n % d == 0:
            if d % align == 0:
                return d
            if best == 1:
                best = d
    # no aligned divisor: accept the plain one unless it is degenerate
    if best >= max(cap // 8, 1):
        return best
    return n


def lora_matmul(x, w, a, b, block_m: int = 128, block_k: int = 512,
                block_n: int = 128):
    """x: (..., K) -> (..., N) with LoRA fused.  Pads M to the tile.

    Differentiable end-to-end (fused Pallas backward kernels)."""
    *lead, K = x.shape
    M = math.prod(lead)
    xf = x.reshape(M, K)
    bm = min(block_m, M)
    pad = (-M) % bm
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = _lm.lora_matmul(xf, w, a, b, bm=bm, bk=fit_block(K, block_k),
                          bn=fit_block(w.shape[1], block_n),
                          interpret=INTERPRET)
    if pad:
        out = out[:M]
    return out.reshape(*lead, w.shape[1])


def mha_attention(q, k, v, causal: bool = True, window: int = 0,
                  q_offset: int = 0, bq: int = 128, bkv: int = 128):
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D) -> (B, Sq, H, D).

    Differentiable (recompute-based flash backward, GQA-aware)."""
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, D)
    out = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                              q_offset=q_offset, bq=fit_block(Sq, bq),
                              bkv=fit_block(Skv, bkv),
                              interpret=INTERPRET)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def kd_loss(teacher, student, temperature: float = 1.0, mask=None,
            br: int = 128, bv: int = 2048):
    """teacher/student: (..., V) -> scalar mean KD loss (masked).

    Differentiable w.r.t. both logit sets (streaming backward kernel)."""
    V = teacher.shape[-1]
    t = teacher.reshape(-1, V)
    s = student.reshape(-1, V)
    R = t.shape[0]
    brr = min(br, R)
    pad = (-R) % brr
    if pad:
        t = jnp.pad(t, ((0, pad), (0, 0)))
        s = jnp.pad(s, ((0, pad), (0, 0)))
    bvv = fit_block(V, bv)
    if bvv != V and bvv % 128:
        # Mosaic takes a lane block only when it is 128-aligned or the
        # whole dim (V = 50257 would give 1733): pad the vocab to a
        # multiple of 128 with columns that carry no probability mass
        # in either distribution (exp(-1e30 - m) == 0, and t - s == 0).
        vp = ((0, 0), (0, (-V) % 128))
        t = jnp.pad(t, vp, constant_values=_kd.NEG_INF)
        s = jnp.pad(s, vp, constant_values=_kd.NEG_INF)
        bvv = fit_block(t.shape[1], bv)
    rows = _kd.kd_loss_rows(t, s, temperature=temperature, br=brr,
                            bv=bvv, interpret=INTERPRET)[:R, 0]
    if mask is not None:
        m = mask.reshape(-1).astype(jnp.float32)
        return jnp.sum(rows * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(rows)


def rglru(a, b, h0, bw: int = 128, bt: int = 128):
    """a, b: (B, S, W); h0: (B, W) -> (h (B,S,W), h_final)."""
    W = a.shape[-1]
    S = a.shape[1]
    return _rg.rglru_scan(a, b, h0, bw=fit_block(W, bw),
                          bt=fit_block(S, bt), interpret=INTERPRET)


def rwkv6(r, k, v, logw, u, bt: int = 64):
    """(B, S, H, D) layout + u (H, D) -> (y (B,S,H,D), S_f (B,H,D,D))."""
    B, S, H, D = r.shape
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    uf = jnp.tile(u, (B, 1))
    y, Sf = _rw.rwkv6_scan(flat(r), flat(k), flat(v), flat(logw), uf,
                           bt=fit_block(S, bt), interpret=INTERPRET)
    return (y.reshape(B, H, S, D).transpose(0, 2, 1, 3),
            Sf.reshape(B, H, D, D))


def _row_blocks(xf, br: int):
    """Row block for a (R, C) row kernel and ``xf`` zero-padded to a
    multiple of it: the whole R when R <= ``br``, else ``br`` (a
    multiple of 8, the sublane tile Mosaic needs).  Zero rows quantize
    to zeros and are sliced off by the caller."""
    R = xf.shape[0]
    br = min(br, R)
    pad = (-R) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    return xf, br


def quantize(x, bits: int = 8, br: int = 8):
    """x: (..., C) -> (q int8, scale fp32 (..., 1))."""
    *lead, C = x.shape
    R = math.prod(lead)
    xf, br = _row_blocks(x.reshape(R, C), br)
    q, sc = _q.quantize_rows(xf, bits=bits, br=br, interpret=INTERPRET)
    return q[:R].reshape(*lead, C), sc[:R].reshape(*lead, 1)


def quantize_pack4(x, br: int = 8):
    """x: (..., C) -> (packed uint8 (..., ceil(C/2)), scale (..., 1)).

    Odd C is zero-padded by one column before packing."""
    *lead, C = x.shape
    R = math.prod(lead)
    xf = x.reshape(R, C)
    if C % 2:
        xf = jnp.pad(xf, ((0, 0), (0, 1)))
    xf, br = _row_blocks(xf, br)
    q, sc = _q.quantize_pack4_rows(xf, br=br, interpret=INTERPRET)
    return q[:R].reshape(*lead, (C + 1) // 2), sc[:R].reshape(*lead, 1)


def clip_mean_rows(g, clip: float, block_p: int = 2048):
    """g: (B, P) stacked per-example grads -> (P,) fp32 mean of the
    per-example L2-clipped rows — the DP-SGD clip-scale-accumulate step
    (privacy/dp.py).  Under the ``pallas`` policy this is the fused
    two-phase kernel (kernels/dp_clip.py); otherwise the XLA reference.
    Forward-only (runs on gradients; never differentiated through)."""
    from repro.kernels import ref as _ref
    if not use_pallas():
        return _ref.clip_mean_rows_ref(g, clip)
    P = g.shape[1]
    return _dp.dp_clip_mean_rows(g, clip=float(clip),
                                 bp=fit_block(P, block_p),
                                 interpret=INTERPRET)[0]


def topk_quantize(x, k: int, bits: int = 8, br: int = 8):
    """x: (..., V) -> (q int8 (..., k), idx int32 (..., k), scale (..., 1)).

    The fused KD b3 upload: selection + quantization on-device.  Under
    the ``pallas`` policy this is the one-pass Pallas kernel; otherwise
    the XLA reference (lax.top_k + symmetric rounding) — bit-identical
    outputs (tests/test_kernels.py), still device-resident, but without
    interpret-mode emulation cost on CPU."""
    from repro.kernels import ref as _ref
    if not use_pallas():
        return _ref.topk_quantize_rows_ref(x, k, bits)
    *lead, V = x.shape
    R = math.prod(lead)
    xf, br = _row_blocks(x.reshape(R, V), br)
    q, idx, sc = _q.topk_quantize_rows(xf, k=k, bits=bits, br=br,
                                       interpret=INTERPRET)
    return (q[:R].reshape(*lead, k), idx[:R].reshape(*lead, k),
            sc[:R].reshape(*lead, 1))
