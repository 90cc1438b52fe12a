"""FedLLMs — the paper's foundational framework (SSII.A):

    a1 server -> clients: global tunable (LoRA) parameters
    a2 client: local PEFT fine-tuning on private data
    a3 clients -> server: fine-tuned tunable parameters
    a4 server: aggregation (FedAvg) -> next global parameters

This module also provides the jitted train/eval/logit steps shared by all
three frameworks (they differ in *what* is exchanged, not in how a local
step runs).  The base model is a closed-over constant of the loss, so
gradients exist only for the LoRA tree — the PEFT property (paper fn.1).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import FedConfig, ModelConfig
from repro.core import tasks
from repro.models.factory import Model
from repro.optim.api import make_optimizer
from repro.peft import lora as lora_lib


def make_fns(model: Model, fed: FedConfig, task: str = "classification"):
    """Returns dict of jitted fns: train_step, eval_step, logits_fn,
    kd_step (distill to teacher logits).

    Every returned step enters the model's kernel-policy scope
    (kernels/ops.policy_scope) for its whole body, so parts of a step
    outside Model.forward — e.g. the KD loss in kd_step — dispatch to
    the same kernels as the forward even when the step is called
    directly rather than through core/rounds.run_federated."""
    cfg = model.cfg
    task_loss = tasks.get_loss_fn(task)
    opt_init, opt_update = make_optimizer(fed.optimizer)

    from repro.kernels import ops as kernel_ops

    def _scoped(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with kernel_ops.policy_scope(cfg.kernel_policy):
                return fn(*args, **kwargs)
        return call

    def _bind(base, lt, rng=None):
        rank = lora_lib.tree_rank(lt, fed.lora_rank)
        return lora_lib.bind(base, lt, fed.lora_alpha, rank,
                             dropout_mask_rng=rng, dropout=fed.lora_dropout)

    priv = fed.privacy

    def _forward(bound, batch):
        """(task logits (tasks.task_logits), aux loss)."""
        h, aux = model.hidden(bound, batch)
        return tasks.task_logits(task, model, bound, h, batch), aux

    def train_step_impl(base, lt, opt_state, batch, rng):
        """Raw (un-jitted) local step — also scanned/vmapped by the SPMD
        backend (core/fed_spmd.py), so both backends share ONE loss.

        With ``PrivacyConfig.dp_clip > 0`` this is a DP-SGD step: the
        stacked per-example gradients are clipped to L2 norm C and
        averaged through the fused clip-scale-accumulate kernel
        (privacy/dp.clipped_grad_mean) before the optimizer update —
        deterministic, so the backends stay in parity for free.  The
        seeded payload noise lives at the upload boundary, not here."""
        def loss_fn(l):
            logits, aux = _forward(_bind(base, l, rng), batch)
            loss, _ = task_loss(logits, batch)
            return loss + aux

        if priv.dp_clip > 0.0:
            from repro.privacy import dp as dp_mod

            def example_loss(l, example):
                one = jax.tree.map(lambda x: x[None], example)
                logits, aux = _forward(_bind(base, l, rng), one)
                loss, _ = task_loss(logits, one)
                return loss + aux

            losses, per_ex = jax.vmap(
                jax.value_and_grad(example_loss),
                in_axes=(None, 0))(lt, batch)
            grads = dp_mod.clipped_grad_mean(per_ex, priv.dp_clip)
            loss = losses.mean()
        else:
            loss, grads = jax.value_and_grad(loss_fn)(lt)
        new_lt, new_opt = opt_update(grads, opt_state, lt, fed.lr)
        # metric-only guard: a corrupted/diverged batch must not poison
        # the round's accumulated mean loss (the params still move and
        # the upload-seam validation screens the payload itself)
        loss = jnp.where(jnp.isfinite(loss), loss, 0.0)
        return new_lt, new_opt, loss

    train_step = jax.jit(train_step_impl)

    @jax.jit
    def eval_step(base, lt, batch):
        logits, _ = _forward(_bind(base, lt), batch)
        loss, _ = task_loss(logits, batch)
        if task == "classification":
            return tasks.classification_accuracy(logits, batch), loss
        return -loss, loss

    @jax.jit
    def logits_fn(base, lt, batch):
        """Knowledge representation for KD (paper b2/b6): class logits for
        classification, full LM logits for generative tasks."""
        return _forward(_bind(base, lt), batch)[0]

    @jax.jit
    def kd_step(base, lt, opt_state, batch, teacher_logits, rng):
        """Distill ``teacher_logits`` into the student's LoRA params."""
        from repro.models import loss as losses

        def loss_fn(l):
            student, aux = _forward(_bind(base, l, rng), batch)
            with jax.named_scope("lm_head"):
                kl = losses.kd_kl(student, teacher_logits,
                                  fed.kd_temperature)
            return kl + aux

        loss, grads = jax.value_and_grad(loss_fn)(lt)
        new_lt, new_opt = opt_update(grads, opt_state, lt, fed.lr)
        return new_lt, new_opt, loss

    return {"train_step": _scoped(train_step),
            "train_step_impl": train_step_impl,
            "eval_step": _scoped(eval_step),
            "logits_fn": _scoped(logits_fn),
            "kd_step": _scoped(kd_step), "opt_init": opt_init,
            "opt_update": opt_update, "bind": _bind}


# --------------------------------------------------------------------------- #
# Aggregation (a4)
# --------------------------------------------------------------------------- #
def fedavg(trees: Sequence, weights: Optional[Sequence[float]] = None):
    """Weighted FedAvg of identically-structured pytrees."""
    if weights is None:
        weights = [1.0] * len(trees)
    total = float(sum(weights))
    # fully-dropped cohort: fall back to a uniform mean rather than 0/0
    ws = [w / total for w in weights] if total > 0 \
        else [1.0 / len(trees)] * len(trees)

    def mean(*leaves):
        out = leaves[0].astype(jnp.float32) * ws[0]
        for w, leaf in zip(ws[1:], leaves[1:]):
            out = out + leaf.astype(jnp.float32) * w
        return out.astype(leaves[0].dtype)

    return jax.tree.map(mean, *trees)


def evaluate(fns, base, lt, data: Dict, batch_size: int = 64) -> tuple:
    """Mean accuracy/loss over a dataset: each batch is built and copied
    to the device (``fl.eval.load``), evaluated, and its two numbers read
    back (``fl.eval.read``) before the next is built."""
    from repro.data.loader import epoch_batches, n_batches
    accs, losses_, n = [], [], 0
    batches = epoch_batches(data, batch_size, seed=0)
    for _ in range(n_batches(data, batch_size)):
        with obs.span("fl.eval.load"):
            batch = next(batches)
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
        a, l = fns["eval_step"](base, lt, jb)
        with obs.span("fl.eval.read"):
            accs.append(float(obs.to_host(a)) * len(batch["tokens"]))
            losses_.append(float(obs.to_host(l)) * len(batch["tokens"]))
        n += len(batch["tokens"])
    if n == 0:
        return 0.0, 0.0
    return sum(accs) / n, sum(losses_) / n
