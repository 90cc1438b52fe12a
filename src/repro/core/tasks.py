"""Task heads: intent classification (paper case study) and generative LM.

Classification-as-LM: class c's logit is the LM logit of vocab id 1+c at
the last non-pad position (banking77.py reserves ids [1, 78) as answer
tokens) — matching GPT-2 classification fine-tuning in the paper.  So the
classification head multiplies the final hidden state at that one
position by the head's answer-id rows alone: no other vocabulary logit
is formed, in the forward or the backward pass."""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.data.banking77 import N_CLASSES
from repro.models import loss as losses

ANSWER_ROWS = (1, 1 + N_CLASSES)    # the head's rows of the answer ids


def _head(fn):
    """Name ``fn``'s ops ``lm_head`` in the compiled program, with the
    head product itself (models/transformer.lm_logits)."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        with jax.named_scope("lm_head"):
            return fn(*args, **kwargs)
    return call


@_head
def class_logits(model, params, h: jax.Array, batch: Dict) -> jax.Array:
    """h: final hidden state (B, S', d) -> (B, n_classes): the answer-id
    rows of the head at each row's last non-pad position."""
    offset = h.shape[1] - batch["tokens"].shape[1]        # vlm/prompt prefix
    pos = offset + batch["lengths"].astype(jnp.int32) - 1  # (B,)
    last = jnp.take_along_axis(h, pos[:, None, None], axis=1)[:, 0]
    return model.head(params, last, ANSWER_ROWS)


def task_logits(task: str, model, params, h: jax.Array, batch: Dict):
    """The task's logits from the final hidden state (``Model.hidden``):
    class logits (B, n_classes) for classification, the whole head's
    (B, S', V) for the generative task."""
    if task == "classification":
        return class_logits(model, params, h, batch)
    return model.head(params, h)


@_head
def classification_loss_fn(cl, batch):
    """cl: class logits (B, n_classes)."""
    loss, _ = losses.cross_entropy(cl, batch["labels"])
    return loss, cl


@_head
def classification_accuracy(cl, batch) -> jax.Array:
    """cl: class logits (B, n_classes)."""
    return losses.accuracy(cl, batch["labels"])


@_head
def generative_loss_fn(logits, batch):
    mask = (batch["tokens"] != 0).astype(jnp.float32)
    offset = logits.shape[1] - batch["tokens"].shape[1]
    lg = logits[:, offset:]
    loss, _ = losses.next_token_loss(lg, batch["tokens"], mask)
    return loss, lg


def task_logit_dim(task: str, vocab_size: int) -> int:
    """Paper SSIII.B: classification logits ~ n_classes; generative ~ V."""
    return N_CLASSES if task == "classification" else vocab_size


def get_loss_fn(task: str):
    """(task logits (``task_logits``), batch) -> (loss, logits)."""
    return (classification_loss_fn if task == "classification"
            else generative_loss_fn)
