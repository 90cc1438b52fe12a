"""Split-FedLLMs — activation-based updates (paper SSII.C):

    c1 client: forward through the first layers on private data
    c2 client -> server: boundary activations (+ labels)
    c3 server: forward through remaining layers, loss, backprop
    c4 server -> client: activation gradients
    c5 client: backprop through its layers, update tunable params
    cc1-cc4 clients <-> server: LoRA FedAvg of the *client-side* params

Split points (DESIGN SS2): *inter* — a pattern-group boundary index
(initial groups on the client, the rest + head on the server); for
encoder-decoder archs the natural boundary client=encoder/server=decoder;
*intra* — inside a block (attention client-side, FFN server-side), for
homogeneous-attention archs.

Activation/gradient transfers optionally pass through int8/int4
straight-through quantization (paper SSIV.C.2, core/compression.py); wire
bytes are what the quantized payload costs.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig, ModelConfig
from repro.core import compression, tasks
from repro.models import common, transformer
from repro.models.factory import Model
from repro.optim.api import make_optimizer
from repro.peft import lora as lora_lib


# --------------------------------------------------------------------------- #
# LoRA tree partitioning
# --------------------------------------------------------------------------- #
def split_lora(lt, n_client_groups: int):
    """(client_tree, server_tree) from a full-model LoRA tree."""
    client, server = {}, {}
    for k, v in lt.items():
        if k == "blocks":
            client[k] = jax.tree.map(lambda x: x[:n_client_groups], v)
            server[k] = jax.tree.map(lambda x: x[n_client_groups:], v)
        elif k == "encoder":
            client[k] = v
        else:
            server[k] = v
    return client, server


def join_lora(client, server):
    out = {}
    for k in set(client) | set(server):
        if k == "blocks" and k in client and k in server:
            out[k] = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0),
                client[k], server[k])
        elif k in client:
            out[k] = client[k]
        else:
            out[k] = server[k]
    return out


def split_base(base, n_client_groups: int, enc_dec: bool):
    """Slice the frozen base params at the split point."""
    if enc_dec:
        client = {k: v for k, v in base.items() if k == "encoder"}
        server = {k: v for k, v in base.items() if k != "encoder"}
        return client, server
    client = dict(base)
    client["blocks"] = jax.tree.map(lambda x: x[:n_client_groups],
                                    base["blocks"])
    client.pop("tail", None)
    client.pop("final_norm", None)
    client.pop("lm_head", None)
    server = dict(base)
    server["blocks"] = jax.tree.map(lambda x: x[n_client_groups:],
                                    base["blocks"])
    return client, server


# --------------------------------------------------------------------------- #
# Split train step
# --------------------------------------------------------------------------- #
def make_split_fns(model: Model, fed: FedConfig,
                   task: str = "classification"):
    cfg = model.cfg
    task_loss = tasks.get_loss_fn(task)
    opt_init, opt_update = make_optimizer(fed.optimizer)
    n_groups = transformer.n_groups_of(cfg)
    L = min(max(fed.split_layer, 0), n_groups - 1) if not \
        cfg.is_encoder_decoder else 0
    qbits = fed.activation_quant_bits
    priv = fed.privacy

    def _bind(base, lt, rng=None):
        # rank read off the tree: heterogeneous client halves arrive
        # truncated to the client's own rank and need alpha/r_c scaling
        rank = lora_lib.tree_rank(lt, fed.lora_rank)
        return lora_lib.bind(base, lt, fed.lora_alpha, rank,
                             dropout_mask_rng=rng, dropout=fed.lora_dropout)

    def _maybe_q(x):
        if qbits:
            y, _ = compression.quant_roundtrip(x, qbits)
            return y
        return x

    def split_step(base_c, base_s, c_lt, s_lt, c_opt, s_opt, batch, rng,
                   nkey=None):
        """One split training step.  ``nkey`` is the per-(client, round,
        step) privacy noise key (privacy/dp.noise_key) consumed by the
        c2 activation mechanism when ``PrivacyConfig.dp_clip > 0``:
        each boundary token row is L2-clipped to dp_clip and carries
        Gaussian noise of stddev sigma*C *before* quantization — the
        transmitted payload is the protected one.  The c4 gradient
        download (server -> client) is not part of this threat surface.
        Noise keys come from a dedicated fold_in stream, never the
        dropout RNG, so both backends draw identical noise."""
        tokens = batch["tokens"]

        if cfg.is_encoder_decoder:
            from repro.models import encdec

            def client_fwd(cl):
                bound = _bind(base_c, cl, rng)
                return encdec.encode({"encoder": bound["encoder"]}, cfg,
                                     batch["enc_embeds"])

            def server_fwd(sl, h_in):
                bound = _bind(base_s, sl, rng)
                h, aux = encdec.decode_given_enc(bound, cfg, tokens, h_in)
                logits = tasks.task_logits(task, model, bound, h, batch)
                loss, _ = task_loss(logits, batch)
                return loss + aux
        else:
            B, S = tokens.shape
            img = batch.get("img_embeds")

            def client_fwd(cl):
                bound = _bind(base_c, cl, rng)
                h, positions = transformer.embed_tokens(
                    bound, cfg, tokens, img)
                h, _ = transformer.forward_groups(bound, cfg, h, positions,
                                                  0, L)
                return h

            def server_fwd(sl, h_in):
                bound = _bind(base_s, sl, rng)
                Sp = h_in.shape[1]
                positions = jnp.broadcast_to(
                    jnp.arange(Sp, dtype=jnp.int32)[None], (B, Sp))
                h, aux = transformer.forward_groups(
                    bound, cfg, h_in, positions, 0, n_groups - L,
                    include_tail=True)
                h = common.apply_norm(cfg.norm, bound["final_norm"], h)
                logits = tasks.task_logits(task, model, bound, h, batch)
                loss, _ = task_loss(logits, batch)
                return loss + aux

        # c1/c2: client forward, activations "up" (privatized, quantized)
        h, client_vjp = jax.vjp(client_fwd, c_lt)
        if priv.dp_enabled:
            from repro.privacy import dp as dp_mod
            h = dp_mod.privatize_rows(h, nkey, fed)
        h_wire = _maybe_q(h)
        # c3: server forward/backward
        loss, (s_grads, h_grad) = jax.value_and_grad(
            server_fwd, argnums=(0, 1))(s_lt, h_wire)
        # c4/c5: activation grads "down" (quantized), client backward
        (c_grads,) = client_vjp(_maybe_q(h_grad))
        new_c, c_opt2 = opt_update(c_grads, c_opt, c_lt, fed.lr)
        new_s, s_opt2 = opt_update(s_grads, s_opt, s_lt, fed.lr)
        return new_c, new_s, c_opt2, s_opt2, loss

    jitted_split_step = jax.jit(split_step)

    def split_train_step(*args, **kwargs):
        # same depth contract as fedavg.make_fns: the whole step body —
        # both sub-model halves and the quantized boundary — traces
        # under the model's kernel-policy scope even when called
        # directly rather than through core/rounds.run_federated.
        from repro.kernels import ops as kernel_ops
        with kernel_ops.policy_scope(cfg.kernel_policy):
            return jitted_split_step(*args, **kwargs)

    def wire_bytes_per_batch(batch_shape: Tuple[int, int]) -> Tuple[int, int]:
        """(activation_up, grad_down) bytes for one batch (c2/c4).

        int4 payloads are nibble-packed (core/compression.pack_int4):
        two values per byte, per-row ceil — the exact transmittable
        size, not the old ``bits // 8 == 0`` undercount."""
        B, S = batch_shape
        if cfg.is_encoder_decoder:
            S = cfg.encoder_seq_len
        rows, d = B * S, cfg.d_model
        if qbits == 4:
            payload = rows * ((d + 1) // 2)
        elif qbits:
            payload = rows * d * qbits // 8
        else:
            payload = rows * d * 4
        scale = rows * 4 if qbits else 0
        return payload + scale, payload + scale

    return {"split_train_step": split_train_step, "split_step": split_step,
            "opt_init": opt_init, "n_client_groups": L,
            "wire_bytes_per_batch": wire_bytes_per_batch,
            "n_groups": n_groups}


# --------------------------------------------------------------------------- #
# Dynamic split-point selection (SSIV.C.1 — beyond-paper feature)
# --------------------------------------------------------------------------- #
def choose_split_point(cfg: ModelConfig, client_flops_budget: float,
                       n_tokens_per_round: int) -> int:
    """Largest client-side group count whose per-round training FLOPs fit
    the client budget (resource-aware workload distribution)."""
    n_groups = max(1, cfg.n_layers // max(len(cfg.layer_pattern or (1,)), 1))
    per_group = 6.0 * (cfg.active_param_count() / max(cfg.n_layers, 1)) \
        * len(cfg.layer_pattern or (1,)) * n_tokens_per_round
    if per_group <= 0:
        return 1
    k = int(client_flops_budget // per_group)
    return int(min(max(k, 1), n_groups - 1))
