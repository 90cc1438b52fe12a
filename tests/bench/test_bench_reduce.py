"""The yardstick's arithmetic: the trace reduction on a synthetic event
list, and the FLOP counter against hand arithmetic."""
import pytest

S = 1_000_000_000      # ns per second


def test_trace_reduction_busy_ops_and_gaps():
    from bench import trace
    # window 0..10 s; device 0 busy 1-3 and 2-4 (overlap) and 6-7;
    # device 1 busy 0-5
    devices = {"/device:TPU:0": [(1 * S, 3 * S, "fusion"),
                                 (2 * S, 4 * S, "conv"),
                                 (6 * S, 7 * S, "fusion")],
               "/device:TPU:1": [(0, 5 * S, "conv")]}
    host = [(0, 10 * S, "bench.window"),
            (0, 5 * S, "stage.local_update"),
            (5 * S, 10 * S, "stage.evaluate")]
    out = trace.reduce_events(devices, host)
    assert out["window_s"] == pytest.approx(10.0)
    # device 0: 3 s busy (1-4) + 1 s (6-7); device 1: 5 s -> mean 4.5
    assert out["busy_s"] == pytest.approx(4.5)
    ops = dict(out["device_ops"])
    assert ops["conv"] == pytest.approx((2 + 5) / 2)
    assert ops["fusion"] == pytest.approx(3 / 2)
    gaps = dict(out["idle_gaps"])
    # device 0 gaps: 0-1 (local_update), 4-6 (mid 5: evaluate starts at
    # 5 and is the later span), 7-10 (evaluate); device 1: 5-10
    assert gaps["stage.local_update"] == pytest.approx(1 / 2)
    assert gaps["stage.evaluate"] == pytest.approx((2 + 3 + 5) / 2)
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(10.0)


def test_trace_reduction_without_window_span_uses_device_extent():
    from bench import trace
    out = trace.reduce_events({"/device:TPU:0": [(2 * S, 3 * S, "a"),
                                                 (5 * S, 6 * S, "b")]}, [])
    assert out["window_s"] == pytest.approx(4.0)
    assert out["busy_s"] == pytest.approx(2.0)
    assert dict(out["idle_gaps"]) == {"host.other": pytest.approx(2.0)}


def test_trace_reduction_refuses_an_empty_trace():
    from bench import trace
    with pytest.raises(ValueError):
        trace.reduce_events({"/device:TPU:0": []}, [])


def _arch(name):
    import json

    from bench.references.decoder import arch_from_config
    from conftest import ROOT
    return arch_from_config(json.loads(
        (ROOT / f"bench/configs/{name}.json").read_text()))


def test_flops_gpt2_by_hand():
    from bench import flops
    a = _arch("gpt2")
    d, f, r, L = 768, 3072, 8, 12
    # one row of 10 real tokens
    proj = 2 * (3 * d * d + d * d + 2 * d * f)        # q,k,v,o + MLP
    lora = 2 * r * (3 * d + 3 * d)                     # x@A and (xA)@B
    attn = 4 * 768 * (10 * 11 / 2)                     # per layer
    head = 2 * d * 77
    fwd = 10 * L * (proj + lora) + L * attn + head
    assert flops.forward_flops(a, r, [10]) == pytest.approx(fwd)
    first_dx = 2 * 3 * d * d + 2 * 3 * d * r
    bwd = 10 * (L * (proj + 2 * lora) - first_dx) + 2 * L * attn + head
    assert flops.train_flops(a, r, [10]) == pytest.approx(fwd + bwd)


def test_flops_qwen2_by_hand():
    from bench import flops
    a = _arch("qwen2-1.5b")
    d, f, r, L, q, kv = 1536, 8960, 8, 28, 1536, 256
    proj = 2 * (d * (q + 2 * kv) + q * d + 3 * d * f)
    lora = 2 * r * (3 * d + q + 2 * kv)
    attn = 4 * 12 * 128 * (3 * 4 / 2 + 5 * 6 / 2)     # rows of 3 and 5
    head = 2 * d * 77 * 2
    fwd = 8 * L * (proj + lora) + L * attn + head
    assert flops.forward_flops(a, r, [3, 5]) == pytest.approx(fwd)


def test_round_flops_count_only_valid_steps():
    import numpy as np

    from bench import flops
    a = _arch("gpt2")
    lengths = np.full((2, 3, 4), 20)
    valid = np.array([[True, True, True], [True, False, False]])
    one = flops.train_flops(a, 8, [20] * 4)
    assert flops.train_round_flops(a, 8, lengths, valid) == \
        pytest.approx(4 * one)
