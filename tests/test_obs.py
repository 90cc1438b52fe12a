"""The program's spans and counters (repro/obs.py): off by default and
silent when off; when on, every span and counter of a round is recorded,
and the counts of a run follow from its sizes alone."""
import jax
import pytest

from repro import obs
from repro.configs.base import FedConfig, ModelConfig
from repro.core.rounds import run_federated
from repro.data import banking77
from repro.data.population import ClientPopulation

CFG = ModelConfig(name="obs-t", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=192,
                  qkv_bias=True, activation="gelu", norm="layernorm",
                  use_rope=False, max_position_embeddings=64)
ROUNDS, BATCH, EVAL_BATCH = 2, 8, 8


@pytest.fixture(autouse=True)
def clean_registry():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture
def annotations(monkeypatch):
    """Every TraceAnnotation opened, as (name, meta)."""
    seen = []

    class Recorder:
        def __init__(self, name, **meta):
            seen.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return seen


def test_off_records_nothing_and_opens_no_annotation(annotations):
    with obs.span("fl.round", round=0):
        with obs.span("fl.evaluate", round=0):
            pass
    obs.count("steps_stacked", 5)
    assert float(obs.to_host(jax.numpy.ones(()))) == 1.0
    assert annotations == []
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def test_on_counts_spans_and_passes_meta(annotations):
    obs.enable()
    for rnd in range(3):
        with obs.span("fl.round", round=rnd):
            with obs.span("fl.evaluate", round=rnd):
                obs.to_host(jax.numpy.zeros(2))
    obs.count("steps_valid", 4)
    obs.count("steps_valid", 2)
    snap = obs.snapshot()
    assert snap["spans"]["fl.round"][0] == 3
    assert snap["spans"]["fl.evaluate"][0] == 3
    assert snap["spans"]["fl.round"][1] >= snap["spans"]["fl.evaluate"][1]
    assert snap["counters"]["host_syncs"] == 3
    assert snap["counters"]["steps_valid"] == 6
    assert ("fl.round", {"round": 2}) in annotations
    assert ("fl.evaluate", {"round": 1}) in annotations


def test_span_closes_on_an_exception():
    obs.enable()
    with pytest.raises(KeyError):
        with obs.span("fl.round", round=0):
            raise KeyError("stop")
    assert obs.snapshot()["spans"]["fl.round"][0] == 1
    with obs.span("fl.other"):
        pass
    assert obs.snapshot()["spans"]["fl.other"][0] == 1


def test_disable_stops_recording():
    obs.enable()
    obs.count("host_syncs")
    obs.disable()
    obs.count("host_syncs")
    with obs.span("fl.round", round=0):
        pass
    assert obs.snapshot() == {"spans": {}, "counters": {"host_syncs": 1}}


def _tiny_run(test, clients):
    fed = FedConfig(framework="fedllm", n_clients=len(clients),
                    rounds=ROUNDS, lora_rank=4, lora_dropout=0.0, seed=0,
                    backend="spmd")
    pub = banking77.generate(16, CFG.vocab_size, 12, seed=0)
    obs.reset()
    obs.enable()
    res = run_federated(CFG, fed, pub,
                        ClientPopulation.from_clients_data(clients), test,
                        batch_size=BATCH, eval_batch=EVAL_BATCH)
    obs.disable()
    return res, obs.snapshot()


def test_round_counts_follow_from_the_sizes():
    """FedLLM on the SPMD backend: per round, two reads per evaluation
    batch and one per float leaf of each client's upload (the finite
    check at the upload seam); stacked steps are clients x the longest
    client's steps, valid steps each client's own."""
    train = banking77.generate(100, CFG.vocab_size, 12, seed=1)
    # shards of 5, 3 and 4 steps, so that the stack holds padding
    bounds = [(0, 40), (40, 64), (64, 100)]
    clients = [{k: v[a:b] for k, v in train.items()} for a, b in bounds]
    test = banking77.generate(40, CFG.vocab_size, 12, seed=2)
    steps = [len(c["tokens"]) // BATCH for c in clients]
    assert steps == [5, 3, 4]
    res, snap = _tiny_run(test, clients)
    leaves = len(jax.tree.leaves(res.final_lora))
    c = snap["counters"]
    assert c["host_syncs"] == ROUNDS * (
        2 * (len(test["tokens"]) // EVAL_BATCH) + len(clients) * leaves)
    assert c["steps_stacked"] == ROUNDS * len(clients) * max(steps)
    assert c["steps_valid"] == ROUNDS * sum(steps)
    spans = snap["spans"]
    for name in ("fl.round", "fl.broadcast", "fl.local_update",
                 "fl.upload", "fl.aggregate", "fl.evaluate",
                 "fl.exec.update", "fl.exec.stack_batches",
                 "fl.exec.stack_trees", "fl.exec.unstack_tree"):
        assert spans[name][0] == ROUNDS, name
    assert spans["fl.eval.load"][0] == spans["fl.eval.read"][0] == \
        ROUNDS * (len(test["tokens"]) // EVAL_BATCH)
    # the first round traces and compiles the update and the evaluation
    assert c["compile_s.fl.exec.update"] > 0
    assert c["compile_s.fl.evaluate"] > 0

    _, again = _tiny_run(test, clients)
    for name in ("host_syncs", "steps_stacked", "steps_valid"):
        assert again["counters"][name] == c[name]
