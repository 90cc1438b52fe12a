"""Mixture-of-Experts MLP with sort-based capacity dispatch.

Tokens are routed top-k, assignments sorted by expert id, packed into a
static (E, C, d) buffer (capacity drop beyond C), and processed with an
expert-batched einsum ``ecd,edf->ecf`` — the expert dim shards cleanly on
the ``model`` mesh axis (expert parallelism) and the pack/unpack scatters
lower to the MoE all-to-all under SPMD.  No (T, E, C) one-hot tensor is
ever materialized (GShard-dispatch would be O(T*E*C) memory).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import hint, mm

def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    p = {"router": common.dense_init(ks[0], (d, E), dtype, scale=d ** -0.5)}
    if cfg.activation == "swiglu":
        p["w_gate"] = common.dense_init(ks[1], (E, d, ff), dtype)
        p["w_in"] = common.dense_init(ks[2], (E, d, ff), dtype)
    else:
        p["w_in"] = common.dense_init(ks[2], (E, d, ff), dtype)
    p["w_out"] = common.dense_init(ks[3], (E, ff, d), dtype,
                                   scale=ff ** -0.5)
    return p


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    """Static per-expert buffer size.  Tokens routed beyond it are DROPPED
    (weight 0) — standard train-time capacity semantics; decode (T small)
    never drops.  cfg.moe_capacity_factor tunes the trade-off."""
    cap = math.ceil(n_tokens * cfg.top_k / cfg.n_experts
                    * cfg.moe_capacity_factor)
    # align to 8 lanes only when the buffer is big enough to care; a floor
    # of 8 at decode (S=1, k assignments) wasted 32x expert compute
    # (SSPerf hillclimb 2, iteration C)
    return max(1, cap) if cap <= 8 else -(-cap // 8) * 8


def moe_fwd(params, cfg: ModelConfig, x):
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar).  Dispatch selected by
    cfg.moe_dispatch (SSPerf hillclimb 1):

    - "global":   flat sort across all tokens — simple, but under SPMD the
                  global argsort/gathers force all-gathers/all-reduces of
                  (T*k, d) buffers (829 GB/layer/device on qwen3-moe).
    - "batched":  per-batch-row sort — dispatch indexing is local to each
                  data shard (2.2x better, but GSPMD still all-gathers the
                  (B, E, C, d) buffer over the model axis).
    - "shard_map": explicit schedule.  Activations are replicated over the
                  model axis by the surrounding tensor-parallel layers, so
                  each model shard computes ONLY its expert slice on the
                  locally-packed buffer and a single psum((B,S,d)) merges
                  expert outputs — no dispatch-buffer collectives at all.
                  Falls back to "batched" when no mesh is ambient (CPU).
    """
    if cfg.moe_dispatch == "shard_map":
        mesh = _ambient_mesh()
        if mesh is not None and "model" in (mesh.axis_names or ()):
            return _moe_fwd_shard_map(params, cfg, x, mesh)
        return _moe_fwd_batched(params, cfg, x)
    if cfg.moe_dispatch == "batched":
        return _moe_fwd_batched(params, cfg, x)
    return _moe_fwd_global(params, cfg, x)


def _ambient_mesh():
    """The installed mesh (``jax.set_mesh``), or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _route_and_pack(params, cfg: ModelConfig, x):
    """Shared per-row routing/packing: returns (buf (B,E,C,d), sw, stok,
    keep, slot, aux).  All indexing is within-row -> shard-local when the
    batch dim is sharded."""
    B, S, d = x.shape
    k, E = cfg.top_k, cfg.n_experts
    C = expert_capacity(S, cfg)
    A = S * k

    logits = mm(x, params["router"]).astype(jnp.float32)       # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, k)
    topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=(0, 1))
    flat_e = topk_e.reshape(B, A)
    rows = jnp.arange(B)[:, None]
    dispatch_frac = jnp.zeros((B, E), jnp.float32).at[
        rows, flat_e].add(1.0).mean(0) / (S * k)
    aux = E * jnp.sum(me * dispatch_frac) * cfg.router_aux_coef

    flat_w = topk_p.reshape(B, A).astype(x.dtype)
    flat_tok = jnp.arange(A, dtype=jnp.int32)[None] // k
    order = jnp.argsort(flat_e, axis=1)
    se = flat_e[rows, order]
    stok = jnp.broadcast_to(flat_tok, (B, A))[rows, order]
    sw = flat_w[rows, order]

    counts = jnp.zeros((B, E), jnp.int32).at[rows, se].add(1)
    starts = jnp.cumsum(counts, axis=1) - counts
    pos = jnp.arange(A, dtype=jnp.int32)[None] - starts[rows, se]
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)

    buf = jnp.zeros((B, E * C + 1, d), x.dtype).at[rows, slot].set(
        x[rows, stok])
    return buf[:, :-1].reshape(B, E, C, d), sw, stok, keep, slot, aux


def _moe_fwd_shard_map(params, cfg: ModelConfig, x, mesh):
    """Explicit expert-parallel schedule.

    E >= M: each model rank owns E/M experts (pure expert parallelism).
    E <  M: hybrid expert+ffn parallelism — each expert's ffn dim is split
    across G = M/E ranks (SwiGLU/GELU are elementwise in ff, and w_out is
    row-parallel in ff, so partial outputs simply add); the same single
    psum((B,S,d), "model") merges both expert slices and ff partials.
    (SSPerf hillclimbs 1 & 2, iterations 3/D.)
    """
    E = cfg.n_experts
    M = mesh.shape["model"]
    dp = tuple(a for a in mesh.axis_names if a != "model")
    from jax.sharding import PartitionSpec as P
    import jax.experimental.shard_map as _sm

    wg = params.get("w_gate")
    wi, wo = params["w_in"], params["w_out"]
    if E >= M:
        if E % M != 0:
            return _moe_fwd_batched(params, cfg, x)
        E_loc, G = E // M, 1
    else:
        # hybrid path re-lays-out expert weights (ffn split): amortized
        # over a train/prefill step, but at decode (S==1) the reshard
        # dominates — the per-row batched path wins there (hc2 iter D)
        if M % E != 0 or x.shape[1] == 1:
            return _moe_fwd_batched(params, cfg, x)
        E_loc, G = 1, M // E
        ff = wi.shape[-1]
        if ff % G != 0:
            return _moe_fwd_batched(params, cfg, x)
        ff_loc = ff // G
        # split the ffn dim into G contiguous per-rank slices
        wi = wi.reshape(E, cfg.d_model, G, ff_loc).transpose(
            0, 2, 1, 3).reshape(E * G, cfg.d_model, ff_loc)
        if wg is not None:
            wg = wg.reshape(E, cfg.d_model, G, ff_loc).transpose(
                0, 2, 1, 3).reshape(E * G, cfg.d_model, ff_loc)
        wo = wo.reshape(E, G, ff_loc, cfg.d_model).reshape(
            E * G, ff_loc, cfg.d_model)

    def local_fn(xl, router, wg_l, wi_l, wo_l):
        B, S, d = xl.shape
        C = expert_capacity(S, cfg)
        buf, sw, stok, keep, slot, aux = _route_and_pack(
            {"router": router}, cfg, xl)
        ridx = jax.lax.axis_index("model")
        eidx = ridx * E_loc if G == 1 else ridx // G   # first owned expert
        my = jax.lax.dynamic_slice_in_dim(buf, eidx, E_loc, 1)
        if cfg.activation == "swiglu":
            g = common.silu(jnp.einsum("becd,edf->becf", my,
                                       wg_l.astype(my.dtype)))
            h = jnp.einsum("becd,edf->becf", my, wi_l.astype(my.dtype)) * g
        else:
            h = common.gelu(jnp.einsum("becd,edf->becf", my,
                                       wi_l.astype(my.dtype)))
        ye = jnp.einsum("becf,efd->becd", h, wo_l.astype(h.dtype))
        yf = ye.reshape(B, E_loc * C, d)
        # local unpack: only assignments routed to MY expert(s) contribute
        lo = eidx * C
        local_slot = slot - lo
        mine = keep & (local_slot >= 0) & (local_slot < E_loc * C)
        rows = jnp.arange(B)[:, None]
        gathered = jnp.where(
            mine[..., None],
            yf[rows, jnp.clip(local_slot, 0, E_loc * C - 1)], 0.0)
        out = jnp.zeros((B, S, d), xl.dtype).at[rows, stok].add(
            gathered * sw[..., None])
        out = jax.lax.psum(out, "model")   # experts + ff partials merge
        aux = jax.lax.pmean(aux, dp) if dp else aux
        return out, aux

    in_specs = (P(dp, None, None), P(),
                P("model", None, None) if wg is not None else P(),
                P("model", None, None), P("model", None, None))
    return _sm.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs,
        out_specs=(P(dp, None, None), P()),
        check_rep=False,
    )(x, params["router"], wg, wi, wo)


def _moe_fwd_global(params, cfg: ModelConfig, x):
    B, S, d = x.shape
    T, k, E = B * S, cfg.top_k, cfg.n_experts
    C = expert_capacity(T, cfg)
    xt = x.reshape(T, d)

    logits = mm(xt, params["router"]).astype(jnp.float32)      # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, k)                   # (T,k)
    topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)  # renormalize

    # ---- load-balance auxiliary loss (Switch-style) ----------------------
    me = jnp.mean(probs, axis=0)                                # (E,)
    dispatch_frac = jnp.zeros((E,), jnp.float32).at[topk_e.reshape(-1)].add(
        1.0) / (T * k)
    aux = E * jnp.sum(me * dispatch_frac) * cfg.router_aux_coef

    # ---- sort assignments by expert --------------------------------------
    flat_e = topk_e.reshape(T * k)
    flat_w = topk_p.reshape(T * k).astype(x.dtype)
    flat_tok = jnp.arange(T * k, dtype=jnp.int32) // k
    order = jnp.argsort(flat_e)
    se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]

    # position within each expert's contiguous group
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts                        # exclusive
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
    keep = pos < C                                              # capacity drop
    slot = jnp.where(keep, se * C + pos, E * C)                 # E*C = dropped

    # ---- pack -> expert compute -> unpack --------------------------------
    buf = jnp.zeros((E * C, d), x.dtype).at[slot].set(
        xt[stok], mode="drop")
    xe = buf.reshape(E, C, d)
    xe = hint(xe, "model", None, None)
    if cfg.activation == "swiglu":
        g = common.silu(jnp.einsum("ecd,edf->ecf", xe,
                                   params["w_gate"].astype(xe.dtype)))
        h = jnp.einsum("ecd,edf->ecf", xe,
                       params["w_in"].astype(xe.dtype)) * g
    else:
        h = common.gelu(jnp.einsum("ecd,edf->ecf", xe,
                                   params["w_in"].astype(xe.dtype)))
    h = hint(h, "model", None, None)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(h.dtype))
    yf = ye.reshape(E * C, d)

    gathered = jnp.where(keep[:, None], yf[jnp.minimum(slot, E * C - 1)], 0.0)
    out = jnp.zeros((T, d), x.dtype).at[stok].add(gathered * sw[:, None])
    return out.reshape(B, S, d), aux


def _moe_fwd_batched(params, cfg: ModelConfig, x):
    """Per-row dispatch: every batch row sorts/packs its own S*k
    assignments, so under SPMD with batch sharded on the data axes the
    dispatch indexing is shard-local; the (B, E, C, d) expert buffer is
    then resharded B(data)->E(model) by a single all-to-all."""
    B, S, d = x.shape
    k, E = cfg.top_k, cfg.n_experts
    C = expert_capacity(S, cfg)           # capacity per ROW per expert
    A = S * k                             # assignments per row

    logits = mm(x, params["router"]).astype(jnp.float32)       # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, k)                   # (B,S,k)
    topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=(0, 1))
    flat_e = topk_e.reshape(B, A)
    dispatch_frac = jnp.zeros((B, E), jnp.float32).at[
        jnp.arange(B)[:, None], flat_e].add(1.0).mean(0) / (S * k)
    aux = E * jnp.sum(me * dispatch_frac) * cfg.router_aux_coef

    flat_w = topk_p.reshape(B, A).astype(x.dtype)
    flat_tok = jnp.arange(A, dtype=jnp.int32)[None] // k       # (1,A)
    order = jnp.argsort(flat_e, axis=1)                        # per-row sort
    rows = jnp.arange(B)[:, None]
    se = flat_e[rows, order]
    stok = jnp.broadcast_to(flat_tok, (B, A))[rows, order]
    sw = flat_w[rows, order]

    counts = jnp.zeros((B, E), jnp.int32).at[rows, se].add(1)
    starts = jnp.cumsum(counts, axis=1) - counts
    pos = jnp.arange(A, dtype=jnp.int32)[None] - starts[rows, se]
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)

    buf = jnp.zeros((B, E * C + 1, d), x.dtype).at[rows, slot].set(
        x[rows, stok])
    xe = buf[:, :-1].reshape(B, E, C, d)
    xe = hint(xe, ("pod", "data"), "model", None, None)   # the true a2a
    if cfg.activation == "swiglu":
        g = common.silu(jnp.einsum("becd,edf->becf", xe,
                                   params["w_gate"].astype(xe.dtype)))
        h = jnp.einsum("becd,edf->becf", xe,
                       params["w_in"].astype(xe.dtype)) * g
    else:
        h = common.gelu(jnp.einsum("becd,edf->becf", xe,
                                   params["w_in"].astype(xe.dtype)))
    ye = jnp.einsum("becf,efd->becd", h, params["w_out"].astype(h.dtype))
    ye = hint(ye, ("pod", "data"), "model", None, None)
    yf = ye.reshape(B, E * C, d)

    gathered = jnp.where(keep[..., None],
                         yf[rows, jnp.minimum(slot, E * C - 1)], 0.0)
    out = jnp.zeros((B, S, d), x.dtype).at[rows, stok].add(
        gathered * sw[..., None])
    return out, aux
