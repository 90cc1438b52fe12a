"""The ``qwen2-fedllm`` cell as BENCHMARK.json lists it: its entries,
its limits file and the steps its traffic gives.  The cell itself runs
on the chip; these checks read its files only."""
import math

import numpy as np
import pytest

CELL = "qwen2-fedllm"
ADAPTER_NUMBERS = ("client_a_median", "client_b_median",
                   "global_delta_median")
EXACT_NUMBERS = ("ledger_bytes_gap", "feed_bad_rows")


@pytest.fixture(scope="module")
def cell():
    from bench import spec
    return spec.load_cell(CELL)


def test_cell_loads_with_its_metrics(cell):
    from bench import spec
    bench = spec.load_benchmark()
    assert cell.chips == 1
    assert cell.config_name == "qwen2-1.5b"
    assert cell.traffic_name == "fedllm-crosssilo-b2"
    assert cell.config["model_type"] == "qwen2"
    assert [m.name for m in cell.end_to_end] == ["setup_s", "round_s"]
    assert [m.name for m in cell.per_layer] == [
        e["name"] for e in bench["per_layer"]]
    assert len(cell.per_layer) == 6


def test_limits_name_the_numbers_judge_reads(cell):
    from bench import compare
    values = dict.fromkeys(ADAPTER_NUMBERS + EXACT_NUMBERS, 0.0)
    checks = compare.judge(values, cell.limits)
    assert set(checks) == set(ADAPTER_NUMBERS + EXACT_NUMBERS)
    for name in EXACT_NUMBERS:
        assert checks[name]["limit"] == 0


@pytest.mark.parametrize("name", ADAPTER_NUMBERS)
def test_adapter_limit_follows_the_rule(cell, name):
    """lower x (upper / lower)^0.6, rounded, strictly between the
    program's largest reading and the smallest control or fault."""
    e = cell.limits[name]
    lower, upper, limit = e["lower"], e["upper"], e["limit"]
    assert lower < limit < upper
    assert math.isclose(limit, lower * (upper / lower) ** 0.6, rel_tol=5e-3)


def test_traffic_gives_83_steps_of_2_and_40_eval_batches(cell):
    from bench import harness
    from bench.control import epoch_feed
    t = cell.traffic
    seed = 2**31 + 17
    _, _, shards, test = harness.make_inputs(cell, seed)
    assert len(shards) == t["fed"]["n_clients"] == 3
    for batches, valid in epoch_feed(shards, t["batch_size"], seed):
        assert batches["tokens"].shape == (83, 2, t["data"]["pad_len"])
        assert bool(np.all(valid))
    assert len(test["tokens"]) // t["eval_batch"] == 40
    assert len(test["tokens"]) % t["eval_batch"] == 0
