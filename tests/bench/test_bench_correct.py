"""The harness end to end on the CPU at a toy size, its chip check
skipped: a sound run is correct, and a run whose timed path is broken
underneath is not, once per fault a one-chip FedLLM cell can have.  The
control (the reference in bfloat16 in the program's place) fails too.
The limits are the gpt2-fedllm cell's own."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_QWEN, tiny_cell

SEED = 2 ** 31 + 12345


def _run(cell):
    from bench import compare, harness
    devs = jax.devices()[:1]
    res = harness.run_cell(cell, SEED, 0.0, False, time.perf_counter(),
                           devs, log=lambda s: None)
    out = harness.result_line(cell, res, devs, False)
    assert out["correct"] == compare.all_pass(res["checks"])
    return out


@pytest.mark.parametrize("config", [None, TINY_QWEN],
                         ids=["gpt2-arch", "qwen2-arch"])
def test_sound_run_is_correct(config):
    out = _run(tiny_cell(config))
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m.name for m in tiny_cell().end_to_end}
    assert list(out)[-1] == "checks"


def _broken_update(monkeypatch, transform):
    from repro.core import fed_spmd
    make = fed_spmd.make_bucket_update

    def broken(*a, **kw):
        update = make(*a, **kw)
        return lambda *args: transform(update, *args)
    monkeypatch.setattr(fed_spmd, "make_bucket_update", broken)


def test_state_left_unchanged_is_not_correct(monkeypatch):
    def unchanged(update, base, lt, opt, batches, keys, valid):
        _, _, loss = update(base, lt, opt, batches, keys, valid)
        return lt, opt, loss
    _broken_update(monkeypatch, unchanged)
    out = _run(tiny_cell())
    assert not out["correct"]
    assert out["checks"]["client_a_median"]["value"] > 0.99


def test_half_batch_left_out_is_not_correct(monkeypatch):
    def half(update, base, lt, opt, batches, keys, valid):
        b = batches["tokens"].shape[2] // 2
        return update(base, lt, opt, {k: v[:, :, :b] for k, v in
                                      batches.items()}, keys, valid)
    _broken_update(monkeypatch, half)
    assert not _run(tiny_cell())["correct"]


def test_answer_altered_where_produced_is_not_correct(monkeypatch):
    def altered(update, *args):
        lt, opt, loss = update(*args)
        lt = jax.tree_util.tree_map_with_path(
            lambda path, x: x * 1.5 if path[-1].key == "b" else x, lt)
        return lt, opt, loss
    _broken_update(monkeypatch, altered)
    assert not _run(tiny_cell())["correct"]


def test_control_in_bfloat16_is_not_correct():
    """The reference in bfloat16 at default precision, standing in for
    the program, fails the cell's limits."""
    from bench import compare, control, spec
    from bench import weights as bweights
    from bench.harness import make_inputs
    cell = tiny_cell()
    reference_round = spec.framework("fedllm").reference_round
    arch, _, shards, test = make_inputs(cell, SEED)
    t = cell.traffic
    w = bweights.make_weights(SEED, arch)
    lora0 = bweights.make_lora(SEED, arch, t["fed"]["lora_rank"])
    feed = control.epoch_feed(shards, t["batch_size"], SEED)
    ref = reference_round(arch, w, lora0, t, feed, shards, test)
    low = reference_round(arch, w, lora0, t, feed, shards, test,
                          dtype=jnp.bfloat16,
                          precision=jax.lax.Precision.DEFAULT)
    values = compare.readings(low, ref, jax.tree.map(np.asarray, lora0),
                              ref["grad_norms"])
    assert not compare.all_pass(compare.judge(values, cell.limits)), values
