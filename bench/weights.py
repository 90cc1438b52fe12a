"""Weights and the initial adapter, made on the device from the seed.

One jitted call makes every base weight in the reference layout
(bench/references/decoder.py), in float32, the type the program keeps
its base weights in.  The program and the reference both receive these
arrays; neither makes weights of its own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.references.decoder import LORA_TARGETS, Arch, weight_shapes

EMBED_STD = 0.02
BIAS_STD = 0.02
NORM_SCALE_STD = 0.05


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, high bits included."""
    if seed < 0:
        raise ValueError("seed must be non-negative")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("embed", "pos"):
        w = z * EMBED_STD
    elif name == "scale":
        w = 1.0 + z * NORM_SCALE_STD
    elif name in ("bias", "bq", "bk", "bv"):
        w = z * BIAS_STD
    else:                               # (L, fan_in, fan_out) matrices
        w = z * shape[-2] ** -0.5
    return w.astype(dtype)


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}/{k}")
    else:
        yield prefix, tree


def _set(tree, path, value):
    keys = path.strip("/").split("/")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, arch: Arch, dtype):
    out = {}
    for i, (path, shape) in enumerate(_paths(weight_shapes(arch))):
        _set(out, path, _leaf(jax.random.fold_in(key, i), path, shape, dtype))
    return out


def make_weights(seed: int, arch: Arch, dtype=jnp.float32):
    """Every base weight, reference layout, from ``seed``."""
    return _make(seed_key(seed), arch, jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_lora(key, arch: Arch, rank: int, dtype):
    L, d = arch.n_layers, arch.d_model
    out_dims = {"wq": arch.n_heads * arch.head_dim,
                "wk": arch.n_kv_heads * arch.head_dim,
                "wv": arch.n_kv_heads * arch.head_dim}
    lora = {}
    for i, t in enumerate(LORA_TARGETS):
        a = jax.random.normal(jax.random.fold_in(key, i), (L, d, rank),
                              jnp.float32) * rank ** -0.5
        lora[t] = {"a": a.astype(dtype),
                   "b": jnp.zeros((L, rank, out_dims[t]), dtype)}
    return lora


def make_lora(seed: int, arch: Arch, rank: int, dtype=jnp.float32):
    """The initial global adapter: A ~ N(0, 1/r), B = 0 (LoRA's own
    initialisation), from a stream of ``seed`` apart from the weights'."""
    return _make_lora(jax.random.fold_in(seed_key(seed), 0x10A), arch,
                      rank, jnp.dtype(dtype))
