"""Plain reference of a dense decoder-only transformer with LoRA on the
query, key and value projections, trained for intent classification.

Written from the published architectures (GPT-2: learned positions,
LayerNorm, tanh-GELU MLP, query/key/value biases; Qwen2: RoPE, RMSNorm,
SwiGLU MLP, grouped-query attention, query/key/value biases), in
straightforward ``jax.numpy``.  It imports nothing of the program.  The
reference precision is float32 with every matrix product at
``Precision.HIGHEST``; the control runs the same code with ``dtype``
bfloat16 and the default precision.

Parameters (all stacked over layers on axis 0):

    {"embed": (V, d), ["pos": (P, d)], "final_norm": {"scale"[, "bias"]},
     "layers": {"norm1": {...}, "norm2": {...}, "wq", "wk", "wv", "wo",
                "bq", "bk", "bv", "w_in", "w_out"[, "w_gate"]}}

LoRA: ``{"wq": {"a": (L, d, r), "b": (L, r, f)}, "wk": ..., "wv": ...}``,
the adapter adding ``(x @ a @ b) * alpha / r`` to the projection.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

N_CLASSES = 77          # class c is answered by vocabulary id 1 + c
LORA_TARGETS = ("wq", "wk", "wv")


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    norm: str             # layernorm | rmsnorm
    norm_eps: float
    mlp: str              # gelu_tanh | swiglu
    positions: str        # learned | rope
    n_positions: int
    rope_theta: float


def arch_from_config(cfg: Dict) -> Arch:
    """Read the published config (Hugging Face key names)."""
    kind = cfg["model_type"]
    if kind == "gpt2":
        d = cfg["n_embd"]
        return Arch(cfg["n_layer"], d, cfg["n_head"], cfg["n_head"],
                    d // cfg["n_head"], cfg.get("n_inner") or 4 * d,
                    cfg["vocab_size"], "layernorm",
                    cfg["layer_norm_epsilon"], "gelu_tanh", "learned",
                    cfg["n_positions"], 0.0)
    if kind == "qwen2":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return Arch(cfg["num_hidden_layers"], d, h,
                    cfg["num_key_value_heads"], d // h,
                    cfg["intermediate_size"], cfg["vocab_size"], "rmsnorm",
                    cfg["rms_norm_eps"], "swiglu", "rope",
                    cfg["max_position_embeddings"], cfg["rope_theta"])
    raise ValueError(f"no reference for model_type {kind!r}")


def weight_shapes(a: Arch) -> Dict:
    """Shapes of every parameter, in the layout above."""
    L, d, f = a.n_layers, a.d_model, a.d_ff
    q, kv = a.n_heads * a.head_dim, a.n_kv_heads * a.head_dim
    norm = {"scale": (L, d)} | ({"bias": (L, d)}
                                if a.norm == "layernorm" else {})
    layers = {"norm1": norm, "norm2": norm,
              "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
              "wo": (L, q, d), "bq": (L, q), "bk": (L, kv), "bv": (L, kv),
              "w_in": (L, d, f), "w_out": (L, f, d)}
    if a.mlp == "swiglu":
        layers["w_gate"] = (L, d, f)
    out = {"embed": (a.vocab_size, d), "layers": layers,
           "final_norm": {k: v[1:] for k, v in norm.items()}}
    if a.positions == "learned":
        out["pos"] = (a.n_positions, d)
    return out


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
def _mm(x, w, precision):
    return jnp.matmul(x, w.astype(x.dtype), precision=precision)


def _norm(a: Arch, p, x):
    if a.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + a.norm_eps) * p["scale"] + p["bias"]
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + a.norm_eps) * p["scale"]


def _rope(a: Arch, x, pos):
    """Rotate-half RoPE on (B, S, H, D)."""
    half = a.head_dim // 2
    inv = 1.0 / a.rope_theta ** (jnp.arange(half, dtype=jnp.float32)
                                 * 2 / a.head_dim)
    ang = pos[:, None].astype(jnp.float32) * inv[None]        # (S, half)
    cos = jnp.cos(ang)[None, :, None].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(a: Arch, scale, precision, x, lp, lo):
    B, S, _ = x.shape
    h = _norm(a, lp["norm1"], x)

    def proj(name):
        y = _mm(h, lp[name], precision) + lp["b" + name[1]]
        ad = lo[name]
        return y + _mm(_mm(h, ad["a"], precision), ad["b"], precision) * scale

    q = proj("wq").reshape(B, S, a.n_heads, a.head_dim)
    k = proj("wk").reshape(B, S, a.n_kv_heads, a.head_dim)
    v = proj("wv").reshape(B, S, a.n_kv_heads, a.head_dim)
    if a.positions == "rope":
        pos = jnp.arange(S)
        q, k = _rope(a, q, pos), _rope(a, k, pos)
    group = a.n_heads // a.n_kv_heads
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision)
    s = s / math.sqrt(a.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=precision)
    x = x + _mm(o.reshape(B, S, -1), lp["wo"], precision)
    h = _norm(a, lp["norm2"], x)
    if a.mlp == "swiglu":
        m = jax.nn.silu(_mm(h, lp["w_gate"], precision)) \
            * _mm(h, lp["w_in"], precision)
    else:
        u = _mm(h, lp["w_in"], precision)
        m = 0.5 * u * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                      * (u + 0.044715 * u ** 3)))
    return x + _mm(m, lp["w_out"], precision)


def class_logits(a: Arch, params, lora, scale, tokens, lengths,
                 precision=jax.lax.Precision.HIGHEST):
    """(B, 77) logits of the answer ids at each row's last token."""
    dt = params["embed"].dtype
    x = params["embed"][tokens]
    if a.positions == "learned":
        x = x + params["pos"][: tokens.shape[1]][None]

    def body(x, layer):
        lp, lo = layer
        return _layer(a, jnp.asarray(scale, dt), precision, x, lp, lo), None

    x, _ = jax.lax.scan(body, x, (params["layers"], lora))
    x = _norm(a, params["final_norm"], x)
    last = x[jnp.arange(x.shape[0]), lengths - 1]               # (B, d)
    head = params["embed"][1:1 + N_CLASSES]                     # tied head
    return jnp.matmul(last, head.T, precision=precision)


def _xent(logits, labels):
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]


# --------------------------------------------------------------------------- #
# Local training (Adam) and evaluation
# --------------------------------------------------------------------------- #
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def make_local_epoch(a: Arch, scale: float, lr: float, precision):
    """jit(fn(params, lora, batches) -> (lora, step_losses, grad_norms)):
    Adam from a fresh state over ``batches`` (tokens (n, B, S), labels
    (n, B), lengths (n, B)).  ``grad_norms`` is each leaf's gradient
    norm averaged over the steps (the rule that leaves out leaves whose
    gradient is nought reads it)."""

    def loss_fn(lora, params, batch):
        lg = class_logits(a, params, lora, scale, batch["tokens"],
                          batch["lengths"], precision)
        return _xent(lg, batch["labels"]).mean()

    grad_fn = jax.value_and_grad(loss_fn)

    def epoch(params, lora, batches):
        dt = jax.tree.leaves(lora)[0].dtype
        zeros = jax.tree.map(jnp.zeros_like, lora)

        def step(carry, batch):
            lo, m, v, t = carry
            loss, g = grad_fn(lo, params, batch)
            t = t + 1
            bc1 = 1.0 - ADAM_B1 ** t
            bc2 = 1.0 - ADAM_B2 ** t
            m = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                             m, g)
            v = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                             v, g)
            lo = jax.tree.map(
                lambda p, m, v: (p - lr * (m / bc1.astype(dt))
                                 / (jnp.sqrt(v / bc2.astype(dt)) + ADAM_EPS)
                                 ).astype(dt), lo, m, v)
            gn = jax.tree.map(lambda g: jnp.sqrt(
                jnp.sum(jnp.square(g.astype(jnp.float32)),
                        axis=tuple(range(1, g.ndim)))), g)
            return (lo, m, v, t), (loss, gn)

        (lora, _, _, _), (losses, gns) = jax.lax.scan(
            step, (lora, zeros, zeros, jnp.zeros((), jnp.float32)), batches)
        return lora, losses, jax.tree.map(lambda x: x.mean(0), gns)

    return jax.jit(epoch)


def make_eval(a: Arch, scale: float, precision):
    """jit(fn(params, lora, batches) -> (summed loss, correct count))
    over (n, B, S) batches."""

    def run(params, lora, batches):
        def step(acc, batch):
            lg = class_logits(a, params, lora, scale, batch["tokens"],
                              batch["lengths"], precision)
            loss = _xent(lg, batch["labels"]).sum()
            hit = (jnp.argmax(lg, -1) == batch["labels"]).sum()
            return (acc[0] + loss, acc[1] + hit), None

        (loss, hits), _ = jax.lax.scan(
            step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            batches)
        return loss, hits

    return jax.jit(run)


def fedavg(trees, weights):
    """Data-weighted mean of the clients' adapters."""
    total = float(sum(weights))
    return jax.tree.map(
        lambda *xs: sum(x.astype(jnp.float32) * (w / total)
                        for x, w in zip(xs, weights)).astype(xs[0].dtype),
        *trees)
