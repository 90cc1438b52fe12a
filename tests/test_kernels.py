"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(charter c: for each Pallas kernel, sweep shapes/dtypes and
assert_allclose against the ref.py oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression
from repro.kernels import flash_attention as _fa
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kd_loss import kd_loss_rows
from repro.kernels.lora_matmul import lora_matmul
from repro.kernels.quantize import (quantize_pack4_rows, quantize_rows,
                                    topk_quantize_rows)
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("M,K,N,r", [(128, 256, 128, 4), (256, 512, 384, 8),
                                     (128, 1024, 256, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_sweep(M, K, N, r, dtype):
    ks = jax.random.split(jax.random.PRNGKey(M + N + r), 4)
    x = rand(ks[0], (M, K), dtype)
    w = rand(ks[1], (K, N), dtype, 0.05)
    a = rand(ks[2], (K, r), dtype, 0.05)
    b = rand(ks[3], (r, N), dtype, 0.05)
    out = lora_matmul(x, w, a, b, bm=128, bk=256, bn=128)
    expect = ref.lora_matmul_ref(x, w, a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **TOL[dtype])


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("Sq,D,H,KV", [(128, 64, 4, 4), (256, 64, 4, 2),
                                       (256, 128, 8, 1),
                                       # short blocks, many heads per step
                                       (48, 64, 12, 12), (64, 128, 20, 4),
                                       # Qwen2-1.5B: GQA 12/2 at 80 tokens
                                       (80, 128, 12, 2)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
def test_flash_attention_sweep(Sq, D, H, KV, causal, window):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(Sq + D + window), 3)
    q = rand(ks[0], (B * H, Sq, D))
    k = rand(ks[1], (B * KV, Sq, D))
    v = rand(ks[2], (B * KV, Sq, D))
    # 48 and 80 tokens are one block, as ops.fit_block gives them
    blk = 64 if Sq % 64 == 0 else Sq
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=blk, bkv=blk)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    B, S, D = 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (rand(kk, (B, S, D), jnp.bfloat16) for kk in ks)
    out = flash_attention(q, k, v, bq=64, bkv=64)
    expect = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("R,V,br,bv", [(64, 1024, 32, 256),
                                       (128, 4096, 64, 512),
                                       (32, 512, 32, 512)])
@pytest.mark.parametrize("T", [1.0, 2.0, 4.0])
def test_kd_loss_sweep(R, V, br, bv, T):
    ks = jax.random.split(jax.random.PRNGKey(R + V), 2)
    t = rand(ks[0], (R, V), scale=3.0)
    s = rand(ks[1], (R, V), scale=3.0)
    rows = kd_loss_rows(t, s, temperature=T, br=br, bv=bv)
    expect = ref.kd_loss_rows_ref(t, s, T)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(expect),
                               rtol=1e-4, atol=1e-5)


def test_kd_loss_zero_when_identical():
    t = rand(jax.random.PRNGKey(3), (32, 2048), scale=5.0)
    rows = kd_loss_rows(t, t, temperature=2.0, br=32, bv=256)
    np.testing.assert_allclose(np.asarray(rows), 0.0, atol=1e-5)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("B,S,W,bt,bw", [(2, 64, 128, 16, 128),
                                         (1, 128, 256, 64, 128),
                                         (3, 32, 128, 32, 64)])
def test_rglru_scan_sweep(B, S, W, bt, bw):
    ks = jax.random.split(jax.random.PRNGKey(B * S + W), 3)
    a = jax.nn.sigmoid(rand(ks[0], (B, S, W)))
    b = rand(ks[1], (B, S, W), scale=0.1)
    h0 = rand(ks[2], (B, W))
    h, hf = rglru_scan(a, b, h0, bw=bw, bt=bt)
    hr, hfr = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hfr), rtol=1e-4,
                               atol=1e-5)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("BH,S,D,bt", [(4, 32, 16, 16), (2, 64, 32, 32),
                                       (8, 16, 64, 16)])
def test_rwkv6_scan_sweep(BH, S, D, bt):
    ks = jax.random.split(jax.random.PRNGKey(BH + S + D), 5)
    r = rand(ks[0], (BH, S, D))
    k = rand(ks[1], (BH, S, D))
    v = rand(ks[2], (BH, S, D))
    lw = -jax.nn.softplus(rand(ks[3], (BH, S, D)))
    u = rand(ks[4], (BH, D), scale=0.1)
    y, Sf = rwkv6_scan(r, k, v, lw, u, bt=bt)
    yr, Sfr = ref.rwkv6_scan_ref(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(Sf), np.asarray(Sfr), rtol=2e-4,
                               atol=2e-4)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("R,C", [(8, 128), (16, 384), (32, 1000)])
@pytest.mark.parametrize("bits", [8])
def test_quantize_sweep(R, C, bits):
    x = rand(jax.random.PRNGKey(R + C), (R, C), scale=3.0)
    q, sc = quantize_rows(x, bits=bits, br=min(8, R))
    qr, scr = ref.quantize_rows_ref(x, bits)
    np.testing.assert_allclose(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(scr), rtol=1e-6)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("R,C", [(8, 128), (16, 384), (4, 1000)])
def test_quantize_pack4_roundtrip(R, C):
    """In-kernel nibble packing: two int4 per byte, exact unpack."""
    x = rand(jax.random.PRNGKey(R + C), (R, C), scale=3.0)
    packed, sc = quantize_pack4_rows(x, br=min(4, R))
    assert packed.dtype == jnp.uint8 and packed.shape == (R, C // 2)
    qr, scr = ref.quantize_rows_ref(x, 4)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(scr), rtol=1e-6)
    unpacked = compression.unpack_int4(packed, C)
    np.testing.assert_array_equal(np.asarray(unpacked), np.asarray(qr))
    # jnp pack of the reference payload gives bit-identical bytes
    np.testing.assert_array_equal(
        np.asarray(compression.pack_int4(qr)), np.asarray(packed))


def test_pack_int4_odd_dim_roundtrip():
    q = jnp.asarray(np.random.default_rng(0).integers(-7, 8, (5, 9)),
                    jnp.int8)
    packed = compression.pack_int4(q)
    assert packed.shape == (5, 5)
    np.testing.assert_array_equal(
        np.asarray(compression.unpack_int4(packed, 9)), np.asarray(q))


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("R,C,k", [(8, 128, 8), (16, 500, 16), (4, 64, 1)])
@pytest.mark.parametrize("bits", [8, 4])
def test_topk_quantize_sweep(R, C, k, bits):
    """Fused top-k+int row kernel == lax.top_k + symmetric quantization."""
    x = rand(jax.random.PRNGKey(R + C + k), (R, C), scale=3.0)
    q, idx, sc = topk_quantize_rows(x, k=k, bits=bits, br=min(4, R))
    qr, idxr, scr = ref.topk_quantize_rows_ref(x, k, bits)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idxr))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(scr), rtol=1e-6)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n,cap,expect", [
    (151936, 2048, 128),      # qwen-style vocab: aligned divisor, not V
    (32768, 2048, 2048),      # power of two: the cap itself
    (512, 384, 256),          # aligned divisor under the cap
    (1000, 512, 500),         # no aligned divisor: largest plain divisor
    (77, 2048, 77),           # small classification head: whole dim
    (8191, 2048, 8191),       # prime: whole dim, never a width-1 grid
    (50257, 2048, 1733),      # gpt2 vocab: best plain divisor, not 1
])
def test_fit_block_aligned_divisors(n, cap, expect):
    got = ops.fit_block(n, cap)
    assert got == expect and n % got == 0


@pytest.mark.parametrize("B,H,KV,S,D,dtype,expect", [
    (16, 12, 12, 80, 64, jnp.float32, 16),   # GPT-2 at batch 16 x 80
    (16, 12, 12, 80, 64, jnp.bfloat16, 16),
    (16, 12, 2, 80, 64, jnp.float32, 24),    # GQA, G = 6: whole groups
    (2, 12, 2, 80, 128, jnp.float32, 24),    # Qwen2-1.5B at batch 2 x 80
    (16, 12, 2, 80, 128, jnp.float32, 24),   # and its evaluation, 16 x 80
    (3, 7, 7, 8, 64, jnp.float32, 21),       # B*H = 21: all, under budget
    (1, 12, 12, 2048, 64, jnp.float32, 6),   # long: full 128 x 128 blocks
    (1, 12, 2, 2048, 128, jnp.float32, 6),
    (1, 8, 8, 4096, 256, jnp.float32, 4),    # wide heads: the VMEM budget
])
def test_pick_heads_per_step(B, H, KV, S, D, dtype, expect):
    G = H // KV
    blk = ops.fit_block(S, 128)
    itemsize = jnp.dtype(dtype).itemsize
    hb = _fa.pick_heads_per_step(B * H, G, blk, blk, D, itemsize)
    assert hb == expect
    assert (B * H) % hb == 0 and (hb % G == 0 or G % hb == 0)
    assert _fa.step_vmem_bytes(hb, G, blk, blk, D, itemsize) \
        <= _fa._STEP_VMEM_BYTES
    # the next larger valid choice would exceed the target or the budget
    assert hb * blk * blk >= _fa._STEP_SCORES or hb == B * H or \
        _fa.step_vmem_bytes(2 * hb, G, blk, blk, D, itemsize) \
        > _fa._STEP_VMEM_BYTES


def test_kd_loss_nondivisible_vocab_streams_chunks():
    """V % bv != 0 must NOT fall back to a single whole-vocab block."""
    R, V = 16, 1000                               # bv=256 -> fit 250? no:
    bv = ops.fit_block(V, 256)                    # largest divisor <= 256
    assert bv < V and V % bv == 0
    t = rand(jax.random.PRNGKey(0), (R, V), scale=3.0)
    s = rand(jax.random.PRNGKey(1), (R, V), scale=3.0)
    loss = ops.kd_loss(t, s, temperature=2.0, br=16, bv=256)
    expect = jnp.mean(ref.kd_loss_rows_ref(t, s, 2.0))
    np.testing.assert_allclose(np.asarray(loss), np.asarray(expect),
                               rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------- #
def test_ops_wrappers_match_model_layouts():
    """ops.* handle model-native layouts (B,S,H,D) and padding."""
    B, S, H, KV, D = 2, 96, 4, 2, 32          # S=96 pads to 128-tile
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = rand(ks[0], (B, S, H * D))
    w = rand(ks[1], (H * D, 64), scale=0.1)
    a = rand(ks[2], (H * D, 4), scale=0.1)
    b = jnp.zeros((4, 64))
    out = ops.lora_matmul(x, w, a, b)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.lora_matmul_ref(
            x.reshape(-1, H * D), w, a, b)).reshape(B, S, 64),
        rtol=2e-4, atol=2e-4)
