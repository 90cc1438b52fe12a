"""Encoder-decoder assembly (whisper-base backbone [arXiv:2212.04356]).

The mel-spectrogram + conv2 frontend is a STUB per the charter: the
encoder consumes precomputed frame embeddings (B, S_enc, d) delivered by
``input_specs()``.  Encoder: bidirectional attention blocks.  Decoder:
causal self-attention + cross-attention blocks (built by transformer.py
with ``cross=True``); cross-attention K/V are projected once from the
encoder output and reused across decode steps.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ModelConfig
from repro.models import attention, common, transformer
from repro.models.common import mm


def init_encoder(key, cfg: ModelConfig, dtype=jnp.float32):
    keys = jax.random.split(key, 2)
    gkeys = jax.random.split(keys[0], cfg.n_encoder_layers)

    def init_one(k):
        return transformer.init_block(k, cfg, ATTN, cross=False, dtype=dtype)

    stacked = jax.vmap(init_one)(gkeys)
    return {"blocks": stacked,
            "norm": common.init_norm(cfg.norm, cfg.d_model, dtype)}


def init_encdec_params(key, cfg: ModelConfig, dtype=jnp.float32):
    k_enc, k_dec = jax.random.split(key)
    params = transformer.init_params(k_dec, cfg, dtype)
    params["encoder"] = init_encoder(k_enc, cfg, dtype)
    return params


def encode(params, cfg: ModelConfig, enc_embeds):
    """enc_embeds: (B, S_enc, d) stub frontend output -> encoder states."""
    B, Se, d = enc_embeds.shape
    pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))
    h = enc_embeds + common.sinusoidal_positions(Se, d).astype(
        enc_embeds.dtype)[None]

    def body(h, bp):
        hn = common.apply_norm(cfg.norm, bp["norm1"], h)
        h = h + attention.attention_fwd_noncausal(bp["attn"], cfg, hn, pos)
        hn = common.apply_norm(cfg.norm, bp["norm2"], h)
        from repro.models import mlp as _mlp
        h = h + _mlp.mlp_fwd(bp["mlp"], cfg, hn)
        return h, None

    h, _ = jax.lax.scan(body, h, params["encoder"]["blocks"])
    return common.apply_norm(cfg.norm, params["encoder"]["norm"], h)


def _cross_kvs(params, cfg: ModelConfig, enc_out):
    """Precompute per-decoder-layer cross-attn K/V (stacked for scan)."""
    pat, n_groups, _ = transformer._group_split(cfg)
    assert pat == (ATTN,), "enc-dec supports homogeneous attn decoders"

    def proj(xattn_p):
        return attention.encode_cross_kv(xattn_p, cfg, enc_out)

    stacked = jax.vmap(proj, in_axes=(0,))(params["blocks"][0]["xattn"])
    tail = tuple(proj(tp["xattn"]) for tp in params["tail"])
    return stacked, tail


def encdec_hidden(params, cfg: ModelConfig, tokens, enc_embeds,
                  scan_layers: bool = True):
    """Training / scoring trunk.  Returns (final-normed decoder hidden
    state, aux): the input of the head (transformer.lm_logits)."""
    enc_out = encode(params, cfg, enc_embeds)
    return decode_given_enc(params, cfg, tokens, enc_out)


def decode_given_enc(params, cfg: ModelConfig, tokens, enc_out):
    """Decoder stack given precomputed encoder states (the Split-FedLLM
    boundary for encoder-decoder archs: client=encoder, server=decoder).
    Returns (final-normed hidden state, aux)."""
    xkv_stacked, xkv_tail = _cross_kvs(params, cfg, enc_out)
    h, positions = transformer.embed_tokens(params, cfg, tokens)
    aux = jnp.zeros((), jnp.float32)

    def body(carry, xs):
        h, aux = carry
        gp, xkv = xs
        h, a = transformer.block_fwd(gp[0], cfg, ATTN, h, positions,
                                     enc_kv=xkv)
        return (h, aux + a), None

    (h, aux), _ = jax.lax.scan(body, (h, aux),
                               (params["blocks"], xkv_stacked))
    for tp, xkv in zip(params["tail"], xkv_tail):
        h, a = transformer.block_fwd(tp, cfg, ATTN, h, positions, enc_kv=xkv)
        aux = aux + a
    return common.apply_norm(cfg.norm, params["final_norm"], h), aux


def init_encdec_cache(params, cfg: ModelConfig, batch: int, max_len: int,
                      enc_embeds, dtype=jnp.bfloat16):
    """Decode cache = self-attn KV cache + precomputed cross K/V."""
    cache = transformer.init_cache(cfg, batch, max_len, dtype)
    enc_out = encode(params, cfg, enc_embeds)
    cache["xkv"], cache["xkv_tail"] = _cross_kvs(params, cfg, enc_out)
    return cache


def encdec_decode_step(params, cfg: ModelConfig, cache, token, pos):
    h = params["embed"][token][:, None]
    if not cfg.use_rope:
        h = h + params["pos_embed"][pos][None, None]

    def body(h, xs):
        gp, gc, xkv = xs
        h, c = transformer.block_decode(gp[0], cfg, ATTN, h, gc[0], pos,
                                        enc_kv=xkv)
        return h, (c,)

    h, new_blocks = jax.lax.scan(
        body, h, (params["blocks"], cache["blocks"], cache["xkv"]))
    new_tail = []
    for ti, tp in enumerate(params["tail"]):
        h, c = transformer.block_decode(tp, cfg, ATTN, h, cache["tail"][ti],
                                        pos, enc_kv=cache["xkv_tail"][ti])
        new_tail.append(c)
    h = common.apply_norm(cfg.norm, params["final_norm"], h)
    logits = transformer.lm_logits(params, cfg, h)[:, 0]
    new_cache = dict(cache)
    new_cache["blocks"] = new_blocks
    new_cache["tail"] = tuple(new_tail)
    return logits, new_cache
