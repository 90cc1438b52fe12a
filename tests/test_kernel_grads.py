"""Gradient-parity sweeps for the differentiable Pallas kernels:
``jax.grad`` through the ops-layer wrappers (custom_vjp backward
kernels, interpret mode) must match ``jax.grad`` through the pure-jnp
oracles in kernels/ref.py within fp32 tolerance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention

TOL = dict(rtol=2e-3, atol=2e-4)


def rand(key, shape, scale=1.0):
    return jax.random.normal(key, shape, jnp.float32) * scale


def assert_grads_close(f_kernel, f_ref, args, names):
    np.testing.assert_allclose(np.asarray(f_kernel(*args)),
                               np.asarray(f_ref(*args)), **TOL)
    argnums = tuple(range(len(args)))
    gk = jax.grad(f_kernel, argnums=argnums)(*args)
    gr = jax.grad(f_ref, argnums=argnums)(*args)
    for name, a, b in zip(names, gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL,
                                   err_msg=name)


# --------------------------------------------------------------------------- #
# LoRA matmul: dx / dW / dA / dB, ranks {4, 8, 16}
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("r", [4, 8, 16])
def test_lora_matmul_grad_parity(r):
    B, S, K, N = 2, 48, 96, 64                 # B*S=96 pads to the 96-tile
    ks = jax.random.split(jax.random.PRNGKey(r), 5)
    x = rand(ks[0], (B, S, K))
    w = rand(ks[1], (K, N), 0.05)
    a = rand(ks[2], (K, r), 0.05)
    b = rand(ks[3], (r, N), 0.05)
    probe = rand(ks[4], (B, S, N))

    def f_kernel(x, w, a, b):
        return jnp.sum(ops.lora_matmul(x, w, a, b) * probe)

    def f_ref(x, w, a, b):
        y = ref.lora_matmul_ref(x.reshape(-1, K), w, a, b)
        return jnp.sum(y.reshape(B, S, N) * probe)

    assert_grads_close(f_kernel, f_ref, (x, w, a, b), "x w a b".split())


# --------------------------------------------------------------------------- #
# KD loss: masked rows, temperatures
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("T", [1.0, 2.0])
@pytest.mark.parametrize("masked", [False, True])
def test_kd_loss_grad_parity(T, masked):
    B, S, V = 2, 24, 384
    ks = jax.random.split(jax.random.PRNGKey(int(T) + masked), 3)
    t = rand(ks[0], (B, S, V), 3.0)
    s = rand(ks[1], (B, S, V), 3.0)
    mask = (jax.random.uniform(ks[2], (B, S)) > 0.3).astype(jnp.float32) \
        if masked else None

    def f_kernel(t, s):
        return ops.kd_loss(t, s, temperature=T, mask=mask, br=16, bv=128)

    def f_ref(t, s):
        rows = ref.kd_loss_rows_ref(t.reshape(-1, V), s.reshape(-1, V),
                                    T)[:, 0]
        if mask is None:
            return jnp.mean(rows)
        m = mask.reshape(-1)
        return jnp.sum(rows * m) / jnp.maximum(jnp.sum(m), 1.0)

    assert_grads_close(f_kernel, f_ref, (t, s), ("teacher", "student"))


def test_kd_loss_grad_chunk_fallback_nondivisible_vocab():
    """V % bv != 0 must stream aligned chunks, not one whole-vocab block,
    and the backward must agree with the reference either way."""
    R, V = 16, 384 + 128                        # 512 = 4 x 128, bv=384
    assert V % 384 != 0
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    t, s = rand(ks[0], (R, V), 2.0), rand(ks[1], (R, V), 2.0)

    def f_kernel(t, s):
        return ops.kd_loss(t, s, temperature=2.0, br=16, bv=384)

    def f_ref(t, s):
        return jnp.mean(ref.kd_loss_rows_ref(t, s, 2.0))

    assert_grads_close(f_kernel, f_ref, (t, s), ("teacher", "student"))


# --------------------------------------------------------------------------- #
# Flash attention: causal / windowed / noncausal, GQA
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32),
                                           (False, 0)])
@pytest.mark.parametrize("H,KV,S,D,blk", [
    pytest.param(4, 4, 64, 32, 32, id="4-4"),
    pytest.param(4, 2, 64, 32, 32, id="4-2"),
    pytest.param(12, 3, 64, 32, 32, id="12-3"),
    # Qwen2-1.5B: GQA 12/2, head dim 128, 80 tokens in one block
    pytest.param(12, 2, 80, 128, 80, id="qwen2-12-2")])
def test_attention_grad_parity(causal, window, H, KV, S, D, blk):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(window + H + KV), 4)
    q = rand(ks[0], (B, S, H, D))
    k = rand(ks[1], (B, S, KV, D))
    v = rand(ks[2], (B, S, KV, D))
    probe = rand(ks[3], (B, S, H, D))

    def f_kernel(q, k, v):
        out = ops.mha_attention(q, k, v, causal=causal, window=window,
                                bq=blk, bkv=blk)
        return jnp.sum(out * probe)

    def f_ref(q, k, v):
        flat = lambda x, n: x.transpose(0, 2, 1, 3).reshape(B * n, S, D)
        out = ref.attention_ref(flat(q, H), flat(k, KV), flat(v, KV),
                                causal=causal, window=window)
        out = out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
        return jnp.sum(out * probe)

    assert_grads_close(f_kernel, f_ref, (q, k, v), "qkv")


@pytest.mark.parametrize("H,KV,S,blk,hb,D", [
    # MHA, two q blocks: every sum in the same order
    pytest.param(6, 6, 32, 16, 3, 32, id="6-6-32-16-3"),
    # GQA, one q block: two kv heads per step
    pytest.param(6, 2, 16, 16, 6, 32, id="6-2-16-16-6"),
    # GQA, one kv head's whole group per step
    pytest.param(6, 2, 32, 16, 3, 32, id="6-2-32-16-3"),
    # GQA, part of a group per step
    pytest.param(6, 1, 32, 16, 2, 32, id="6-1-32-16-2"),
    # Qwen2-1.5B at batch 2 x 80: pick_heads_per_step's 24, four kv heads
    pytest.param(12, 2, 80, 80, 24, 128, id="qwen2-12-2-80-80-24"),
])
def test_attention_heads_per_step_matches_one_head(H, KV, S, blk, hb, D):
    """Blocking heads changes no head's arithmetic: outputs and dq equal
    the one-head-per-step kernel bit for bit; dk/dv too unless several q
    blocks of a GQA group meet in one accumulator in another order."""
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(H + KV + hb), 4)
    q = rand(ks[0], (B * H, S, D))
    k = rand(ks[1], (B * KV, S, D))
    v = rand(ks[2], (B * KV, S, D))
    probe = rand(ks[3], (B * H, S, D))

    def f(q, k, v, n):
        return jnp.sum(probe * flash_attention(
            q, k, v, causal=True, window=24, bq=blk, bkv=blk,
            heads_per_step=n))

    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, bq=blk, bkv=blk,
                                   heads_per_step=hb)),
        np.asarray(flash_attention(q, k, v, bq=blk, bkv=blk,
                                   heads_per_step=1)))
    grads = [jax.grad(f, argnums=(0, 1, 2))(q, k, v, n) for n in (hb, 1)]
    reordered = KV < H and S > blk and hb > 1
    for name, a, b in zip("qkv", *grads):
        if name == "q" or not reordered:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6, err_msg=name)
