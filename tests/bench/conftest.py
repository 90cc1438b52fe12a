"""A cell small enough for the CPU: GPT-2's architecture at toy widths,
3 clients of 160 rows (20 local steps of 8).  Only the harness is exercised here; the cells of
BENCHMARK.json run on the chip."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "source": "test", "model_type": "gpt2", "activation_function": "gelu_new",
    "layer_norm_epsilon": 1e-5, "n_embd": 64, "n_head": 4, "n_layer": 2,
    "n_positions": 32, "vocab_size": 512, "reduced": [],
    "program": {"name": "tiny", "family": "dense", "n_layers": 2,
                "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_ff": 256,
                "vocab_size": 512, "qkv_bias": True, "activation": "gelu",
                "norm": "layernorm", "use_rope": False,
                "max_position_embeddings": 32, "tie_embeddings": True,
                "kernel_policy": "auto"}}

TINY_QWEN = {
    "source": "test", "model_type": "qwen2", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2,
    "max_position_embeddings": 64, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": True, "vocab_size": 512,
    "reduced": [],
    "program": {"name": "tinyq", "family": "dense", "n_layers": 2,
                "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
                "d_ff": 128, "vocab_size": 512, "activation": "swiglu",
                "norm": "rmsnorm", "qkv_bias": True, "tie_embeddings": True,
                "rope_theta": 10000.0, "kernel_policy": "auto"}}

TINY_TRAFFIC = {
    "framework": "fedllm",
    "fed": {"n_clients": 3, "lora_rank": 4, "lora_alpha": 8.0,
            "lora_dropout": 0.0, "local_epochs": 1, "lr": 0.001,
            "optimizer": "adam", "backend": "spmd", "aggregation": "sync"},
    "data": {"pad_len": 16, "n_public": 16, "n_train": 480, "n_test": 32},
    "batch_size": 8, "eval_batch": 8, "warmup_rounds": 1, "mesh": None}


def tiny_cell(config=None):
    from bench import spec
    bench = spec.load_benchmark()
    limits = spec.read_limits(bench["workloads"][0]["name"])
    return spec.Cell(name="tiny", chips=1, config_name="tiny",
                     traffic_name="tiny", config=config or TINY_CONFIG,
                     traffic=TINY_TRAFFIC, limits=limits,
                     end_to_end=spec._metrics(bench["end_to_end"]),
                     per_layer=spec._metrics(bench["per_layer"]))


@pytest.fixture
def cell():
    return tiny_cell()
