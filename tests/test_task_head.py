"""The classification head reads one position and the 77 answer-id rows
of the head (core/tasks.class_logits).  Every step that uses it must give
what the whole-vocabulary path gives: every position's logits over the
full vocabulary, then the answer ids at the last non-pad position.  That
path is put back here by patching ``tasks.task_logits``, so both sides
run the same steps around the head."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig, PrivacyConfig
from repro.configs.gpt2_small import gpt2_tiny
from repro.configs.registry import get_config
from repro.core import split, tasks
from repro.core.fedavg import make_fns
from repro.data import banking77
from repro.models.factory import build_model
from repro.peft import lora as lora_lib

B, S = 4, 16


def _full_head_task_logits(task, model, params, h, batch):
    """Whole-vocabulary logits at every position, then the task's read."""
    logits = model.head(params, h)
    if task != "classification":
        return logits
    offset = logits.shape[1] - batch["tokens"].shape[1]
    pos = offset + batch["lengths"].astype(jnp.int32) - 1
    g = jnp.take_along_axis(logits, pos[:, None, None], axis=1)[:, 0]
    return g[:, tasks.ANSWER_ROWS[0]:tasks.ANSWER_ROWS[1]]


def _case(name):
    """(cfg, LoRA targets, batch extras) of a named case."""
    targets = ("wq", "wk", "wv")
    if name == "gpt2":                  # tied head, learned positions
        return gpt2_tiny(), targets, ()
    if name == "qwen2":                 # tied head, GQA, RoPE, SwiGLU
        return get_config("qwen2-1.5b").reduced(d_model=64), targets, ()
    if name == "untied":                # untied head with LoRA on it
        cfg = dataclasses.replace(gpt2_tiny(), tie_embeddings=False)
        return cfg, targets + ("lm_head",), ()
    if name == "prompt":                # soft-prompt prefix: offset 5
        return gpt2_tiny(), targets, ("prefix_embeds",)
    if name == "vlm":                   # image-token prefix
        return get_config("llava-next-34b").reduced(d_model=64), targets, ()
    if name == "encdec":
        return get_config("whisper-base").reduced(d_model=64), targets, ()
    raise ValueError(name)


def _setup(name):
    cfg, targets, extras = _case(name)
    model = build_model(cfg)
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    base = model.init(ks[0])
    lt = lora_lib.init_lora(ks[1], base, targets, 4)
    # B starts at 0; move it so that A has a gradient too
    lt = jax.tree_util.tree_map_with_path(
        lambda p, x: 0.05 * jax.random.normal(ks[2], x.shape)
        if p[-1].key == "b" else x, lt)
    data = banking77.generate(B, cfg.vocab_size, S, seed=3)
    batch = {k: jnp.asarray(v) for k, v in data.items()}
    if "prefix_embeds" in extras:
        batch["prefix_embeds"] = 0.02 * jax.random.normal(
            ks[3], (B, 5, cfg.d_model))
    if cfg.n_image_tokens:
        batch["img_embeds"] = 0.02 * jax.random.normal(
            ks[3], (B, cfg.n_image_tokens, cfg.image_embed_dim))
    if cfg.is_encoder_decoder:
        batch["enc_embeds"] = 0.02 * jax.random.normal(
            ks[3], (B, cfg.encoder_seq_len, cfg.d_model))
    return model, base, lt, batch


def _close(got, want):
    """Each leaf within 1e-5 of the reference, relative to its norm."""
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert g.shape == w.shape
        assert np.linalg.norm(g - w) <= 1e-5 * np.linalg.norm(w), \
            np.linalg.norm(g - w) / np.linalg.norm(w)


@pytest.mark.parametrize("name", ["gpt2", "qwen2", "untied", "prompt",
                                  "vlm", "encdec"])
def test_class_logits_match_full_head(name):
    model, base, lt, batch = _setup(name)
    bound = lora_lib.bind(base, lt, 32.0, 4)
    h, aux = jax.jit(model.hidden)(bound, batch)
    got = jax.jit(lambda p, h, b: tasks.class_logits(model, p, h, b))(
        bound, h, batch)
    assert got.shape == (B, banking77.N_CLASSES)
    _close(got, _full_head_task_logits("classification", model, bound, h,
                                       batch))
    # Model.forward is the trunk, then the whole head
    logits, aux2 = jax.jit(model.forward)(bound, batch)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(model.head(bound, h)))
    np.testing.assert_array_equal(np.asarray(aux), np.asarray(aux2))


def _delta(new, old):
    return jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                        - np.asarray(b, np.float64), new, old)


def _run(step, model, base, lt, batch):
    """One call of ``step`` with fresh (re-traced) functions.  Steps that
    train take plain SGD at lr 1 and give the change of each LoRA tree,
    which is minus its gradient."""
    rng = jax.random.PRNGKey(11)
    if step == "split":
        fed = FedConfig(framework="split", split_layer=1, lora_rank=4,
                        lora_dropout=0.0, optimizer="sgd", lr=1.0)
        sfns = split.make_split_fns(model, fed)
        L = sfns["n_client_groups"]
        c_lt, s_lt = split.split_lora(lt, L)
        base_c, base_s = split.split_base(base, L,
                                          model.cfg.is_encoder_decoder)
        new_c, new_s, _, _, loss = sfns["split_train_step"](
            base_c, base_s, c_lt, s_lt, sfns["opt_init"](c_lt),
            sfns["opt_init"](s_lt), batch, rng)
        return _delta(new_c, c_lt), _delta(new_s, s_lt), loss
    priv = PrivacyConfig(dp_clip=0.5 if step == "train_dp" else 0.0)
    fed = FedConfig(lora_rank=4, lora_dropout=0.1, privacy=priv,
                    optimizer="sgd", lr=1.0)
    fns = make_fns(model, fed)
    opt = fns["opt_init"](lt)
    if step == "eval":
        return fns["eval_step"](base, lt, batch)
    if step == "logits":
        return fns["logits_fn"](base, lt, batch)
    if step == "kd":
        teacher = jax.random.normal(rng, (B, banking77.N_CLASSES))
        new_lt, _, loss = fns["kd_step"](base, lt, opt, batch, teacher, rng)
    else:
        new_lt, _, loss = fns["train_step"](base, lt, opt, batch, rng)
    return _delta(new_lt, lt), loss


@pytest.mark.parametrize("step,name", [
    ("train", "gpt2"), ("train", "qwen2"), ("train", "untied"),
    ("train", "prompt"), ("train_dp", "gpt2"), ("train_dp", "untied"),
    ("eval", "gpt2"), ("eval", "qwen2"), ("eval", "prompt"),
    ("logits", "gpt2"), ("logits", "vlm"), ("kd", "gpt2"), ("kd", "qwen2"),
    ("split", "gpt2"), ("split", "qwen2"), ("split", "encdec"),
])
def test_steps_match_full_head(step, name, monkeypatch):
    """Gradients (the LoRA trees' change), losses, accuracy and class
    logits of each step, against the full head.  Split runs in float64:
    in float32 its server-half gradients on ``gpt2`` lie 4e-4 from the
    float64 result on either path and 2e-5 apart, so a float32 comparison
    there reads rounding, not the head; in float64 the paths agree."""
    with jax.enable_x64(step == "split"):
        model, base, lt, batch = _setup(name)
        if step == "split":
            base, lt, batch = jax.tree.map(
                lambda x: x.astype(jnp.float64)
                if x.dtype == jnp.float32 else x, (base, lt, batch))
        got = _run(step, model, base, lt, batch)
        monkeypatch.setattr(tasks, "task_logits", _full_head_task_logits)
        want = _run(step, model, base, lt, batch)
    _close(got, want)


@pytest.mark.parametrize("step", ["train", "eval"])
def test_generative_path_unchanged(step):
    """The generative task keeps the whole head: its steps give bit for
    bit what the same steps built on Model.forward's logits give."""
    model, base, lt, batch = _setup("gpt2")
    fed = FedConfig(lora_rank=4, lora_dropout=0.0)
    fns = make_fns(model, fed, task="generative")
    opt, rng = fns["opt_init"](lt), jax.random.PRNGKey(0)

    def loss(l, base, batch, rng):
        logits, aux = model.forward(fns["bind"](base, l, rng), batch)
        return tasks.generative_loss_fn(logits, batch)[0] + aux

    # base, batch and key are arguments, as in the steps: a jitted
    # function that closes over them folds them in as constants
    @jax.jit
    def train_step(base, l, o, batch, rng):
        value, g = jax.value_and_grad(loss)(l, base, batch, rng)
        new_l, new_o = fns["opt_update"](g, o, l, fed.lr)
        return new_l, new_o, jnp.where(jnp.isfinite(value), value, 0.0)

    @jax.jit
    def eval_step(base, l, batch):
        logits, _ = model.forward(fns["bind"](base, l), batch)
        acc = -tasks.generative_loss_fn(logits, batch)[0]
        return acc, tasks.generative_loss_fn(logits, batch)[0]

    if step == "eval":
        got = fns["eval_step"](base, lt, batch)
        want = eval_step(base, lt, batch)
    else:
        got = fns["train_step"](base, lt, opt, batch, rng)
        want = train_step(base, lt, opt, batch, rng)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
