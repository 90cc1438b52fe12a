"""Ahead-of-time compiles for a described TPU v5e (no chip attached):
the Pallas kernels of the main path at GPT-2 124M widths, and one whole
FedLLM local-update bucket, compiled by the TPU's own compiler.  Each
test asserts that the kernel reached the compiled program as a Mosaic
``tpu_custom_call`` (not the interpreter, not the XLA reference).

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and every test
worker imports every test file.  All such compiles live in this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import FedConfig
from repro.configs.gpt2_small import gpt2
from repro.configs.qwen2_1_5b import config as qwen2_1_5b
from repro.core import fed_spmd
from repro.core.fedavg import make_fns
from repro.kernels import ops
from repro.models.factory import build_model
from repro.peft import lora as lora_lib

GPT2_LORA_PARAMS = 442_368     # rank-8 LoRA on wq/wk/wv, 12 layers
V5E_BYTES_LIMIT = 16_909_336_064   # memory_stats()["bytes_limit"] of a v5e


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """``compile_for_chip(fn, *shapes)`` -> ``fn`` compiled for v5e,
    with the kernels compiled (not interpreted) and the persistent
    compile cache off (a TPU entry written here could not be read back
    without a chip).  Shapes without a sharding go on one chip."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "INTERPRET", False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        specs = jax.tree.map(
            lambda s: s if s.sharding is not None else jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip), shapes)
        with ops.policy_scope("pallas"):
            return jax.jit(fn).lower(*specs).compile()

    yield run
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_text(compile_for_chip):
    """``compile_text(fn, *shapes)`` -> the compiled HLO text of ``fn``
    for v5e (``compile_for_chip``)."""
    return lambda fn, *shapes: compile_for_chip(fn, *shapes).as_text()


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _grad_all(f, n):
    return jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
                    argnums=tuple(range(n)))


# --------------------------------------------------------------------------- #
# Kernels at GPT-2 widths (batch 16 x 80 tokens)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_fwd_bwd(compile_text, dtype):
    text = compile_text(_grad_all(ops.lora_matmul, 4),
                        _sds((16, 80, 768), dtype), _sds((768, 768), dtype),
                        _sds((768, 8), dtype), _sds((8, 768), dtype))
    assert "tpu_custom_call" in text


def test_flash_attention_fwd_bwd(compile_text):
    qkv = _sds((16, 80, 12, 64))
    text = compile_text(_grad_all(ops.mha_attention, 3), qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,S,H,KV,D", [
    (2, 80, 12, 2, 128),      # Qwen2-1.5B: GQA 12/2, many heads per step
    (1, 2048, 12, 12, 64),    # long sequence: full blocks, one head per step
])
def test_flash_attention_fwd_bwd_shapes(compile_text, B, S, H, KV, D):
    q, kv = _sds((B, S, H, D)), _sds((B, S, KV, D))
    text = compile_text(_grad_all(ops.mha_attention, 3), q, kv, kv)
    assert "tpu_custom_call" in text
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert name in text


@pytest.mark.parametrize("V", [77, 50_257])
def test_kd_loss_fwd_bwd(compile_text, V):
    """77 Banking77 classes, and the full GPT-2 vocabulary (not a
    multiple of 128: the wrapper pads it to aligned blocks)."""
    f = jax.grad(lambda t, s: ops.kd_loss(t, s, temperature=2.0),
                 argnums=(0, 1))
    text = compile_text(f, _sds((16, 80, V)), _sds((16, 80, V)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("R", [20, 500])
def test_topk_quantize(compile_text, R):
    """KD b3 upload: top-8 of 77 class logits, int8; R = 20 is not a
    multiple of the 8-row block."""
    text = compile_text(lambda x: ops.topk_quantize(x, 8), _sds((R, 77)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_activations(compile_text, bits):
    """Split upload of (16, 80, 768) activations: int8 rows, and int4
    packed two per byte."""
    fn = ops.quantize if bits == 8 else ops.quantize_pack4
    text = compile_text(fn, _sds((16, 80, 768), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_dp_clip_mean_rows(compile_text):
    text = compile_text(lambda g: ops.clip_mean_rows(g, 1.0),
                        _sds((16, GPT2_LORA_PARAMS)))
    assert "tpu_custom_call" in text


# --------------------------------------------------------------------------- #
# One whole FedLLM local-update bucket at gpt2() widths
# --------------------------------------------------------------------------- #
def _bucket_update_args(model, fed, C, S=2, B=16, L=80):
    fns = make_fns(model, fed, "classification")

    def build():
        base = model.init(jax.random.PRNGKey(0))
        lt = lora_lib.init_lora(jax.random.PRNGKey(1), base,
                                fed.lora_targets, fed.lora_rank)
        opt = fns["opt_init"](lt)
        return (base, fed_spmd.stack_for_clients(lt, C),
                fed_spmd.stack_for_clients(opt, C))

    base, lt, opt = jax.eval_shape(build)
    batches = {"tokens": _sds((C, S, B, L), jnp.int32),
               "labels": _sds((C, S, B), jnp.int32),
               "lengths": _sds((C, S, B), jnp.int32)}
    return (base, (lt, opt, batches, _sds((C, S, 2), jnp.uint32),
                   _sds((C, S), jnp.bool_)))


@pytest.mark.parametrize("mesh_devices", [0, 4])
def test_fedllm_bucket_update_gpt2(compile_text, topo, mesh_devices):
    """The chip smoke run's local-update program: 2 local steps of batch
    16 x 80 tokens, LoRA rank 8 — 3 clients on one chip, or 4 clients
    with the client axis sharded over a (4, 1) mesh, where each chip
    runs its own client (the compiler cannot partition a Pallas call)."""
    import dataclasses

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    cfg = dataclasses.replace(gpt2(), kernel_policy="pallas")
    model = build_model(cfg)
    fed = FedConfig(lora_rank=8, backend="spmd")
    mesh = None
    if mesh_devices:
        mesh = Mesh(np.array(topo.devices[:mesh_devices]).reshape(-1, 1),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    base, stacked = _bucket_update_args(model, fed, mesh_devices or 3)
    if mesh is not None:
        def place(tree, spec):
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec)), tree)
        base, stacked = place(base, P()), place(stacked, P("data"))
    update = fed_spmd.make_bucket_update(model, fed, "classification",
                                         mesh=mesh)
    text = compile_text(update, base, *stacked)
    assert "tpu_custom_call" in text
    # each kernel call is named <kernel>_<pass>, so a device trace can
    # group its time by kernel
    for name in ("lora_matmul_fwd", "flash_attention_fwd"):
        assert name in text
    # the classification head reads one position and the head's 77
    # answer-id rows: no array ends in the vocabulary (the embedding is
    # [V, d])
    assert f"{cfg.vocab_size}]" not in text


def test_fedllm_bucket_update_qwen2(compile_for_chip):
    """The stacked local-update program at Qwen2-1.5B's published
    widths: 3 clients, 2 local steps of batch 2 x 80 tokens, LoRA rank
    8.  Its temporaries and arguments must fit what one v5e lets a
    program hold: a batch of 2 is what fits."""
    import dataclasses
    cfg = dataclasses.replace(qwen2_1_5b(), kernel_policy="pallas")
    model = build_model(cfg)
    fed = FedConfig(lora_rank=8, backend="spmd")
    base, stacked = _bucket_update_args(model, fed, 3, B=2)
    update = fed_spmd.make_bucket_update(model, fed, "classification")
    compiled = compile_for_chip(update, base, *stacked)
    text = compiled.as_text()
    for name in ("tpu_custom_call", "lora_matmul_fwd", "flash_attention_fwd"):
        assert name in text
    assert f"{cfg.vocab_size}]" not in text      # as on GPT-2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_BYTES_LIMIT
