"""Model assembly: decoder-only LM covering dense / MoE / hybrid / SSM /
VLM families, plus the encoder-decoder variant (whisper) in encdec.py.

Parameters layout (functional, nested dicts):

    {"embed": (V,d) [, "pos_embed": (P,d)] [, "img_proj": (di,d)],
     "blocks": tuple(block_tree_stacked_over_groups, ...)   # per pattern pos
     "tail":   tuple(block_tree, ...)                       # remainder layers
     "final_norm": ..., ["lm_head": (d,V)]}

The repeated trunk is a ``lax.scan`` over pattern groups (constant-size HLO
-> tractable 512-way SPMD compiles).  A *pattern group* is one repetition of
``cfg.layer_pattern`` (e.g. RecurrentGemma's (rglru, rglru, local_attn));
layers beyond the last full group live unstacked in ``tail``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, LOCAL_ATTN, RGLRU, RWKV6, ModelConfig)
from repro.models import attention, common, mlp, moe, rglru, rwkv6
from repro.models.common import hint


# --------------------------------------------------------------------------- #
# Block init / apply
# --------------------------------------------------------------------------- #
def init_block(key, cfg: ModelConfig, kind: str, cross: bool = False,
               dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": common.init_norm(cfg.norm, d, dtype)}
    if kind in (ATTN, LOCAL_ATTN):
        p["attn"] = attention.init_attention(ks[0], cfg, dtype=dtype)
    elif kind == RGLRU:
        p["attn"] = rglru.init_rglru(ks[0], cfg, dtype=dtype)
    elif kind == RWKV6:
        p["attn"] = rwkv6.init_rwkv6(ks[0], cfg, dtype=dtype)
    else:
        raise ValueError(kind)
    if cross:
        p["xnorm"] = common.init_norm(cfg.norm, d, dtype)
        p["xattn"] = attention.init_attention(ks[2], cfg, cross=True,
                                              dtype=dtype)
    if kind != RWKV6:                       # rwkv6 block embeds channel-mix
        p["norm2"] = common.init_norm(cfg.norm, d, dtype)
        if cfg.is_moe:
            p["mlp"] = moe.init_moe(ks[1], cfg, dtype=dtype)
        else:
            p["mlp"] = mlp.init_mlp(ks[1], cfg, dtype=dtype)
    else:
        p["norm2"] = common.init_norm(cfg.norm, d, dtype)
    return p


def block_fwd(p, cfg: ModelConfig, kind: str, x, positions,
              enc_kv=None, causal: bool = True):
    """Full-sequence block application.  Returns (x, aux).  Its ops carry
    the layer kind in their compiled name: ``attention`` (self and cross
    attention), ``recurrence`` (RG-LRU, RWKV6 time mix) or ``mlp``
    (dense or expert MLP, RWKV6 channel mix)."""
    aux = jnp.zeros((), jnp.float32)
    mixer = "recurrence" if kind in (RGLRU, RWKV6) else "attention"
    with jax.named_scope(mixer):
        h = common.apply_norm(cfg.norm, p["norm1"], x)
        if kind == ATTN:
            window = cfg.sliding_window if causal else 0
            a = attention.attention_fwd(p["attn"], cfg, h, positions,
                                        window=window) if causal else \
                attention.attention_fwd_noncausal(p["attn"], cfg, h,
                                                  positions)
            x = x + a
        elif kind == LOCAL_ATTN:
            x = x + attention.attention_fwd(p["attn"], cfg, h, positions,
                                            window=cfg.local_window)
        elif kind == RGLRU:
            out, _ = rglru.rglru_fwd(p["attn"], cfg, h)
            x = x + out
        elif kind == RWKV6:
            out, _ = rwkv6.timemix_fwd(p["attn"], cfg, h)
            x = x + out
    if kind == RWKV6:
        with jax.named_scope("mlp"):
            h2 = common.apply_norm(cfg.norm, p["norm2"], x)
            cm, _ = rwkv6.channelmix_fwd(p["attn"], cfg, h2)
        return x + cm, aux
    if enc_kv is not None:
        with jax.named_scope("attention"):
            hx = common.apply_norm(cfg.norm, p["xnorm"], x)
            x = x + attention.cross_attention_fwd(p["xattn"], cfg, hx,
                                                  enc_kv)
    with jax.named_scope("mlp"):
        h = common.apply_norm(cfg.norm, p["norm2"], x)
        if cfg.is_moe:
            m, aux = moe.moe_fwd(p["mlp"], cfg, h)
        else:
            m = mlp.mlp_fwd(p["mlp"], cfg, h)
        x = x + m
    if "adapter" in p:                       # bottleneck adapter (PEFT)
        from repro.peft import adapters as _ad
        x = _ad.adapter_fwd(p["adapter"], x)
    return x, aux


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype=jnp.bfloat16):
    if kind == ATTN:
        return attention.init_kv_cache(cfg, batch, max_len,
                                       window=cfg.sliding_window, dtype=dtype)
    if kind == LOCAL_ATTN:
        return attention.init_kv_cache(cfg, batch, max_len,
                                       window=cfg.local_window, dtype=dtype)
    if kind == RGLRU:
        return rglru.init_rglru_cache(cfg, batch)
    if kind == RWKV6:
        return rwkv6.init_rwkv_cache(cfg, batch)
    raise ValueError(kind)


def block_decode(p, cfg: ModelConfig, kind: str, x, cache, pos, enc_kv=None):
    """One-token decode.  x: (B,1,d).  Returns (x, new_cache)."""
    h = common.apply_norm(cfg.norm, p["norm1"], x)
    if kind in (ATTN, LOCAL_ATTN):
        window = cfg.sliding_window if kind == ATTN else cfg.local_window
        a, cache = attention.attention_decode(p["attn"], cfg, h, cache, pos,
                                              window=window)
        x = x + a
    elif kind == RGLRU:
        out, cache = rglru.rglru_decode(p["attn"], cfg, h, cache)
        x = x + out
    elif kind == RWKV6:
        out, (S, x_tm) = rwkv6.timemix_fwd(
            p["attn"], cfg, h, state=cache["S"], x_last=cache["x_tm"])
        x = x + out
        h2 = common.apply_norm(cfg.norm, p["norm2"], x)
        cm, x_cm = rwkv6.channelmix_fwd(p["attn"], cfg, h2,
                                        x_last=cache["x_cm"])
        return x + cm, {"S": S, "x_tm": x_tm.astype(jnp.float32),
                        "x_cm": x_cm.astype(jnp.float32)}
    if enc_kv is not None:
        hx = common.apply_norm(cfg.norm, p["xnorm"], x)
        x = x + attention.cross_attention_fwd(p["xattn"], cfg, hx, enc_kv)
    h = common.apply_norm(cfg.norm, p["norm2"], x)
    if cfg.is_moe:
        m, _ = moe.moe_fwd(p["mlp"], cfg, h)
    else:
        m = mlp.mlp_fwd(p["mlp"], cfg, h)
    return x + m, cache


# --------------------------------------------------------------------------- #
# Model init
# --------------------------------------------------------------------------- #
def _group_split(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int, int]:
    """(pattern, n_full_groups, n_tail_layers)."""
    pat = cfg.layer_pattern or (ATTN,)
    n_groups = cfg.n_layers // len(pat)
    tail = cfg.n_layers - n_groups * len(pat)
    return pat, n_groups, tail


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    pat, n_groups, n_tail = _group_split(cfg)
    keys = jax.random.split(key, 8)
    V, d = cfg.vocab_size, cfg.d_model
    params: Dict[str, Any] = {
        "embed": common.embed_init(keys[0], (V, d), dtype),
        "final_norm": common.init_norm(cfg.norm, d, dtype),
    }
    if not cfg.use_rope:
        params["pos_embed"] = common.embed_init(
            keys[1], (cfg.max_position_embeddings, d), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(keys[2], (d, V), dtype)
    if cfg.n_image_tokens:
        di = cfg.image_embed_dim or d
        params["img_proj"] = common.dense_init(keys[3], (di, d), dtype)

    cross = cfg.is_encoder_decoder
    blocks = []
    if n_groups > 0:
        for pi, kind in enumerate(pat):
            gkeys = jax.random.split(jax.random.fold_in(keys[4], pi),
                                     n_groups)
            init_one = functools.partial(init_block, cfg=cfg, kind=kind,
                                         cross=cross, dtype=dtype)
            blocks.append(jax.vmap(lambda k: init_one(k))(gkeys))
    params["blocks"] = tuple(blocks)
    tail = []
    for ti in range(n_tail):
        kind = pat[ti % len(pat)]
        tail.append(init_block(jax.random.fold_in(keys[5], ti), cfg, kind,
                               cross=cross, dtype=dtype))
    params["tail"] = tuple(tail)
    return params


# --------------------------------------------------------------------------- #
# Embedding & head
# --------------------------------------------------------------------------- #
def embed_tokens(params, cfg: ModelConfig, tokens, img_embeds=None,
                 prefix_embeds=None, pos_offset: int = 0):
    """tokens: (B,S) int32 -> (h (B,S',d), positions (B,S')).  For VLMs the
    projected image embeddings are prepended (S' = n_img + S); soft-prompt
    prefixes (peft/prompt.py) are prepended unprojected."""
    h = params["embed"][tokens]                         # gather, (B,S,d)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    if img_embeds is not None:
        proj = common.mm(img_embeds.astype(h.dtype), params["img_proj"])
        h = jnp.concatenate([proj, h], axis=1)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    B, S = h.shape[:2]
    positions = jnp.arange(S, dtype=jnp.int32)[None] + pos_offset
    positions = jnp.broadcast_to(positions, (B, S))
    if not cfg.use_rope:
        h = h + params["pos_embed"][positions[0]][None]
    return h, positions


def _head_weight(params, cfg: ModelConfig,
                 rows: Optional[Tuple[int, int]] = None):
    """The (d, V) head, or its vocabulary rows ``[lo, hi)``: the tied
    embedding transposed, or ``lm_head`` (a LoRA-bound ``lm_head`` has
    its ``w`` and ``b`` cut alike)."""
    if cfg.tie_embeddings:
        e = params["embed"]
        return (e if rows is None else e[rows[0]:rows[1]]).T
    w = params["lm_head"]
    if rows is None:
        return w

    def cut(x):
        return x[:, rows[0]:rows[1]]
    if isinstance(w, dict):
        return {**w, "w": cut(w["w"]), "b": cut(w["b"])}
    return cut(w)


def lm_logits(params, cfg: ModelConfig, h,
              rows: Optional[Tuple[int, int]] = None):
    """h (..., d) -> logits (..., V), or (..., hi - lo) over the vocabulary
    rows ``rows = (lo, hi)``.  Its ops, and the task's reads of the
    hidden state (core/tasks.py), are named ``lm_head`` in the compiled
    program."""
    with jax.named_scope("lm_head"):
        w = _head_weight(params, cfg, rows)
        h = hint(h, ("pod", "data"), *(None,) * (h.ndim - 1))
        return common.mm(h, w)


# --------------------------------------------------------------------------- #
# Full-sequence trunk (train / prefill)
# --------------------------------------------------------------------------- #
def hidden(params, cfg: ModelConfig, tokens, img_embeds=None,
           prefix_embeds=None, scan_layers: bool = True,
           remat: str = "none"):
    """Returns (final-normed hidden state (B,S',d), aux_loss): the input
    of the head (``lm_logits``)."""
    pat, n_groups, _ = _group_split(cfg)
    with jax.named_scope("embed"):
        h, positions = embed_tokens(params, cfg, tokens, img_embeds,
                                    prefix_embeds)
    h = hint(h, ("pod", "data"), None, None)
    aux0 = jnp.zeros((), jnp.float32)

    def apply_group(h, aux, group_params):
        for pi, kind in enumerate(pat):
            h, a = block_fwd(group_params[pi], cfg, kind, h, positions)
            aux = aux + a
        return h, aux

    if remat == "selective":
        # save matmul outputs, recompute elementwise ops only: cuts the
        # backward recompute traffic that makes full remat memory-bound
        # (SSPerf hillclimb 3)
        apply_group = jax.checkpoint(
            apply_group,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat != "none":
        apply_group = jax.checkpoint(apply_group)

    if scan_layers and n_groups > 0:
        def body(carry, gp):
            h, aux = carry
            h, aux = apply_group(h, aux, gp)
            return (h, aux), None

        (h, aux), _ = jax.lax.scan(body, (h, aux0), params["blocks"])
    else:
        aux = aux0
        for g in range(n_groups):
            gp = jax.tree.map(lambda x: x[g], params["blocks"])
            h, aux = apply_group(h, aux, gp)
    for ti, tp in enumerate(params["tail"]):
        kind = pat[ti % len(pat)]
        h, a = block_fwd(tp, cfg, kind, h, positions)
        aux = aux + a
    return common.apply_norm(cfg.norm, params["final_norm"], h), aux


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    pat, n_groups, n_tail = _group_split(cfg)
    stacked = []
    if n_groups > 0:
        for pi, kind in enumerate(pat):
            one = init_block_cache(cfg, kind, batch, max_len, dtype)
            stacked.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape),
                one))
    tail = tuple(
        init_block_cache(cfg, pat[ti % len(pat)], batch, max_len, dtype)
        for ti in range(n_tail))
    return {"blocks": tuple(stacked), "tail": tail}


def decode_step(params, cfg: ModelConfig, cache, token, pos):
    """token: (B,) int32; pos: scalar int32.  Returns (logits (B,V), cache)."""
    pat, n_groups, _ = _group_split(cfg)
    h = params["embed"][token][:, None]                  # (B,1,d)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    if not cfg.use_rope:
        h = h + params["pos_embed"][pos][None, None]

    def body(h, xs):
        gp, gc = xs
        new_c = []
        for pi, kind in enumerate(pat):
            h, c = block_decode(gp[pi], cfg, kind, h, gc[pi], pos)
            new_c.append(c)
        return h, tuple(new_c)

    if n_groups > 0:
        h, new_blocks = jax.lax.scan(body, h,
                                     (params["blocks"], cache["blocks"]))
    else:
        new_blocks = cache["blocks"]
    new_tail = []
    for ti, tp in enumerate(params["tail"]):
        kind = pat[ti % len(pat)]
        h, c = block_decode(tp, cfg, kind, h, cache["tail"][ti], pos)
        new_tail.append(c)
    h = common.apply_norm(cfg.norm, params["final_norm"], h)
    logits = lm_logits(params, cfg, h)[:, 0]
    return logits, {"blocks": new_blocks, "tail": tuple(new_tail)}


# --------------------------------------------------------------------------- #
# Layer-range application (Split-FedLLM)
# --------------------------------------------------------------------------- #
def n_groups_of(cfg: ModelConfig) -> int:
    _, n_groups, _ = _group_split(cfg)
    return n_groups


def slice_groups(params, start: int, end: int):
    """Sub-model params covering pattern groups [start, end)."""
    out = dict(params)
    out["blocks"] = jax.tree.map(lambda x: x[start:end], params["blocks"])
    if start > 0:
        out.pop("embed", None)
    return out


def forward_groups(params, cfg: ModelConfig, h, positions, start: int,
                   end: int, include_tail: bool = False):
    """Apply pattern groups [start, end) (already-embedded hidden h)."""
    pat, n_groups, _ = _group_split(cfg)
    aux = jnp.zeros((), jnp.float32)
    for g in range(start, end):
        gp = jax.tree.map(lambda x: x[g], params["blocks"])
        for pi, kind in enumerate(pat):
            h, a = block_fwd(gp[pi], cfg, kind, h, positions)
            aux = aux + a
    if include_tail:
        for ti, tp in enumerate(params["tail"]):
            kind = pat[ti % len(pat)]
            h, a = block_fwd(tp, cfg, kind, h, positions)
            aux = aux + a
    return h, aux
