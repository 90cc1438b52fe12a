"""Model facade: one API over all families.

    model = build_model(cfg)
    params = model.init(key, dtype)
    logits, aux = model.forward(params, batch)      # train / prefill
    h, aux = model.hidden(params, batch)            # ... before the head
    logits = model.head(params, h[, rows])
    cache = model.init_cache(params, batch_size, max_len, batch)
    logits, cache = model.decode_step(params, cache, token, pos)

``batch`` is a dict: {"tokens": (B,S)} plus family extras
("img_embeds" for VLM, "enc_embeds" for audio).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, transformer


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ #
    def init(self, key, dtype=jnp.float32):
        if self.cfg.is_encoder_decoder:
            return encdec.init_encdec_params(key, self.cfg, dtype)
        return transformer.init_params(key, self.cfg, dtype)

    def init_abstract(self, dtype=jnp.float32):
        """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
        key = jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: self.init(k, dtype), key)

    # ------------------------------------------------------------------ #
    def hidden(self, params, batch: Dict[str, Any], scan_layers: bool = True,
               remat: str = "none"):
        """(final-normed hidden state (B, S', d), aux loss): what the head
        (``head``) and the task heads (core/tasks.py) read."""
        from repro.kernels import ops as kernel_ops
        with kernel_ops.policy_scope(self.cfg.kernel_policy):
            if self.cfg.is_encoder_decoder:
                return encdec.encdec_hidden(params, self.cfg,
                                            batch["tokens"],
                                            batch["enc_embeds"],
                                            scan_layers=scan_layers)
            return transformer.hidden(
                params, self.cfg, batch["tokens"],
                img_embeds=batch.get("img_embeds"),
                prefix_embeds=batch.get("prefix_embeds"),
                scan_layers=scan_layers, remat=remat)

    def head(self, params, h, rows: Optional[Tuple[int, int]] = None):
        """h (..., d) -> logits over the whole vocabulary, or over its
        rows ``[lo, hi)``."""
        from repro.kernels import ops as kernel_ops
        with kernel_ops.policy_scope(self.cfg.kernel_policy):
            return transformer.lm_logits(params, self.cfg, h, rows)

    def forward(self, params, batch: Dict[str, Any], scan_layers: bool = True,
                remat: str = "none"):
        """(logits (B, S', V), aux loss)."""
        h, aux = self.hidden(params, batch, scan_layers, remat)
        return self.head(params, h), aux

    # ------------------------------------------------------------------ #
    def init_cache(self, params, batch_size: int, max_len: int,
                   batch: Optional[Dict[str, Any]] = None,
                   dtype=jnp.bfloat16):
        if self.cfg.is_encoder_decoder:
            assert batch is not None and "enc_embeds" in batch
            return encdec.init_encdec_cache(params, self.cfg, batch_size,
                                            max_len, batch["enc_embeds"],
                                            dtype)
        return transformer.init_cache(self.cfg, batch_size, max_len, dtype)

    def decode_step(self, params, cache, token, pos):
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_decode_step(params, self.cfg, cache, token,
                                             pos)
        return transformer.decode_step(params, self.cfg, cache, token, pos)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
