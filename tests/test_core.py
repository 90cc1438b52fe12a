"""Core federated-framework unit tests: KD knowledge processing, split
LoRA partitioning, metrics accounting, compression wire sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig, ModelConfig
from repro.core import compression, kd, metrics, split, tasks
from repro.core.fedavg import make_fns
from repro.data import banking77, partition
from repro.models.factory import build_model
from repro.peft import lora as lora_lib

CFG = ModelConfig(name="t", family="dense", n_layers=4, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256,
                  qkv_bias=True, activation="gelu", norm="layernorm",
                  use_rope=False, max_position_embeddings=128)


# --------------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------------- #
def test_ledger_accounting():
    led = metrics.CommLedger()
    led.record(0, 0, "lora_params", metrics.UP, 100)
    led.record(0, 1, "lora_params", metrics.UP, 200)
    led.record(1, 0, "logits", metrics.DOWN, 50)
    assert led.total() == 350
    assert led.total(metrics.UP) == 300
    assert led.per_round() == {0: 300, 1: 50}
    assert led.by_name() == {"lora_params": 300, "logits": 50}
    assert led.mean_client_bytes_per_round() == 350 / 3


def test_flops_orderings():
    """KD does strictly more client work than FedLLM; split strictly
    less (paper Table I row 3)."""
    n_tok, n_lora = 10_000, 1_000
    fed = metrics.train_flops(CFG, n_tok, True, n_lora)
    kd_extra = fed + metrics.fwd_flops(CFG, n_tok) + metrics.train_flops(
        CFG, n_tok, True, n_lora)
    split_ = metrics.train_flops(CFG, n_tok, True, n_lora, frac_layers=0.25)
    assert kd_extra > fed > split_


def test_logit_bytes_classification_vs_generative():
    """Paper SSIII.B: generative logits are ~V/77 x bigger."""
    n = 1000
    cls = metrics.logit_bytes(n, 77)
    gen = metrics.logit_bytes(n, 50_000)
    assert gen / cls == pytest.approx(50_000 / 77, rel=1e-6)
    topk = metrics.logit_bytes(n, 50_000, topk=32)
    assert topk < gen / 100
    q8 = metrics.logit_bytes(n, 50_000, quant_bits=8)
    assert q8 == n * (50_000 + 4)


# --------------------------------------------------------------------------- #
# KD knowledge processing
# --------------------------------------------------------------------------- #
def test_aggregate_knowledge_weighted_mean():
    a = np.ones((10, 5), np.float32)
    b = 3 * np.ones((10, 5), np.float32)
    agg = kd.aggregate_knowledge([a, b], weights=[1, 3])
    np.testing.assert_allclose(agg, 2.5)


def test_aggregate_knowledge_entropy_filter():
    rng = np.random.default_rng(0)
    confident = rng.normal(size=(20, 5)).astype(np.float32) * 10
    noisy = np.zeros((20, 5), np.float32)               # max entropy
    agg = kd.aggregate_knowledge([confident, noisy],
                                 entropy_filter_frac=0.5)
    # high-entropy samples replaced by the confident client's logits
    ent_mean = np.asarray(
        kd._entropy_jnp(jnp.stack([jnp.asarray(confident),
                                   jnp.asarray(noisy)]))).mean(0)
    worst = ent_mean >= np.quantile(ent_mean, 0.5)
    np.testing.assert_allclose(np.asarray(agg)[worst], confident[worst],
                               rtol=1e-5)


def test_align_public_dataset_shifts_distribution():
    pub = banking77.generate(2000, 512, 32, seed=0)
    hist = np.zeros(77)
    hist[:10] = 0.1                                     # clients only use 10
    aligned = kd.align_public_dataset(pub, [hist], 1000, seed=1)
    frac = (aligned["labels"] < 10).mean()
    assert frac > 0.9
    assert len(aligned["tokens"]) == 1000


def test_compress_for_wire_topk_smaller():
    fed_dense = FedConfig(logit_topk=0)
    fed_topk = FedConfig(logit_topk=8)
    logits = np.random.default_rng(0).normal(
        size=(50, 256)).astype(np.float32)
    _, wire_d = kd.compress_for_wire(logits, fed_dense)
    out, wire_t = kd.compress_for_wire(logits, fed_topk)
    assert wire_t < wire_d / 10
    np.testing.assert_array_equal(out.argmax(-1), logits.argmax(-1))


# --------------------------------------------------------------------------- #
# Split-FedLLM internals
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def split_setup():
    model = build_model(CFG)
    base = model.init(jax.random.PRNGKey(0))
    lt = lora_lib.init_lora(jax.random.PRNGKey(1), base,
                            ("wq", "wk", "wv"), 4)
    return model, base, lt


def test_split_join_lora_roundtrip(split_setup):
    model, base, lt = split_setup
    c, s = split.split_lora(lt, 2)
    joined = split.join_lora(c, s)
    for a, b in zip(jax.tree.leaves(lt), jax.tree.leaves(joined)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_split_step_runs_and_learns(split_setup):
    model, base, lt = split_setup
    fed = FedConfig(framework="split", split_layer=2, lora_rank=4,
                    lora_dropout=0.0, lr=5e-3)
    sfns = split.make_split_fns(model, fed, task="classification")
    L = sfns["n_client_groups"]
    c_lt, s_lt = split.split_lora(lt, L)
    base_c, base_s = split.split_base(base, L, False)
    c_opt, s_opt = sfns["opt_init"](c_lt), sfns["opt_init"](s_lt)
    data = banking77.generate(64, CFG.vocab_size, 24, seed=0)
    batch = {k: jnp.asarray(v[:16]) for k, v in data.items()}
    losses = []
    for i in range(8):
        c_lt, s_lt, c_opt, s_opt, loss = sfns["split_train_step"](
            base_c, base_s, c_lt, s_lt, c_opt, s_opt, batch,
            jax.random.PRNGKey(i))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_split_quantized_wire_smaller(split_setup):
    model, _, _ = split_setup
    fed32 = FedConfig(framework="split", split_layer=1)
    fed8 = FedConfig(framework="split", split_layer=1,
                     activation_quant_bits=8)
    s32 = split.make_split_fns(model, fed32)
    s8 = split.make_split_fns(model, fed8)
    up32, down32 = s32["wire_bytes_per_batch"]((16, 24))
    up8, down8 = s8["wire_bytes_per_batch"]((16, 24))
    assert up8 < up32 / 3 and down8 < down32 / 3


def test_choose_split_point_monotone():
    pts = [split.choose_split_point(CFG, b, 10_000)
           for b in (1e6, 1e9, 1e12, 1e15)]
    assert pts == sorted(pts)
    assert 1 <= min(pts) and max(pts) <= CFG.n_layers - 1


# --------------------------------------------------------------------------- #
# tasks
# --------------------------------------------------------------------------- #
def test_class_logits_gather_position():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 5, CFG.d_model))
    batch = {"tokens": jnp.ones((2, 5), jnp.int32),
             "lengths": jnp.asarray([3, 5], jnp.int32)}
    cl = tasks.class_logits(model, params, h, batch)
    logits = model.head(params, h)
    np.testing.assert_allclose(np.asarray(cl[0]),
                               np.asarray(logits[0, 2, 1:78]))
    np.testing.assert_allclose(np.asarray(cl[1]),
                               np.asarray(logits[1, 4, 1:78]))
