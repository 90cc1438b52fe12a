"""Execution-backend parity: ``FedConfig(backend="spmd")`` must agree
with the sequential reference for every framework — final accuracy/loss
within fp32 tolerance (vmapped/batched reductions reorder float ops) and
the communication ledger byte-for-byte (all wire sizes are
shape-derived).  Dropout is 0 here: with dropout the backends draw
different (equally valid) mask streams and bit-level parity is
undefined (see core/rng.py)."""
import dataclasses

import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.configs.gpt2_small import gpt2_tiny
from repro.core.rounds import run_federated
from repro.data import banking77, partition

FRAMEWORKS = ("fedllm", "kd", "split")


@pytest.fixture(scope="module")
def case_study():
    cfg = gpt2_tiny()
    pub, tr, te = banking77.paper_splits(cfg.vocab_size, pad_len=24,
                                         scale=0.04)
    clients = partition.iid_partition(tr, 3)
    return cfg, pub, clients, te


@pytest.fixture(scope="module", params=FRAMEWORKS)
def both_backends(request, case_study):
    cfg, pub, clients, te = case_study
    fed = FedConfig(framework=request.param, n_clients=3, rounds=2,
                    lora_rank=4, lora_dropout=0.0, split_layer=2,
                    kd_epochs=1, seed=0)
    seq = run_federated(cfg, fed, pub, clients, te, batch_size=16,
                        eval_batch=64)
    spmd = run_federated(cfg, dataclasses.replace(fed, backend="spmd"),
                         pub, clients, te, batch_size=16, eval_batch=64)
    return request.param, seq, spmd


def test_accuracy_and_loss_parity(both_backends):
    fw, seq, spmd = both_backends
    assert abs(seq.final_accuracy - spmd.final_accuracy) <= 1e-3, fw
    for hs, hp in zip(seq.history, spmd.history):
        assert abs(hs.loss - hp.loss) <= 1e-3, fw
        assert abs(hs.accuracy - hp.accuracy) <= 1e-3, fw


def test_ledger_bytes_parity_exact(both_backends):
    """Per-round, per-client and per-payload byte totals agree exactly:
    the SPMD backend must not change what the paper's Fig. 4 reports."""
    fw, seq, spmd = both_backends
    assert seq.ledger.per_round() == spmd.ledger.per_round(), fw
    assert seq.ledger.by_name() == spmd.ledger.by_name(), fw
    assert seq.ledger.per_client_round() == spmd.ledger.per_client_round(), fw
    assert seq.ledger.total() == spmd.ledger.total(), fw


def test_client_flops_parity_exact(both_backends):
    fw, seq, spmd = both_backends
    np.testing.assert_array_equal(np.asarray(seq.client_flops),
                                  np.asarray(spmd.client_flops), err_msg=fw)


def test_final_lora_trees_close(both_backends):
    """The aggregated parameters themselves agree within fp32 noise."""
    import jax

    fw, seq, spmd = both_backends
    ls, lp = jax.tree.leaves(seq.final_lora), jax.tree.leaves(spmd.final_lora)
    assert len(ls) == len(lp), fw
    for a, b in zip(ls, lp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4, err_msg=fw)


# --------------------------------------------------------------------------- #
# Heterogeneous LoRA ranks: bucketed SPMD vs sequential
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def hetero_case(case_study):
    cfg, pub, _, te = case_study
    _, tr, _ = banking77.paper_splits(cfg.vocab_size, pad_len=24,
                                      scale=0.04)
    return cfg, pub, partition.iid_partition(tr, 4), te


@pytest.fixture(scope="module", params=FRAMEWORKS)
def hetero_both_backends(request, hetero_case):
    cfg, pub, clients, te = hetero_case
    fed = FedConfig(framework=request.param, n_clients=4, rounds=1,
                    lora_rank=16, client_ranks=(4, 8, 8, 16),
                    lora_dropout=0.0, split_layer=2, kd_epochs=1, seed=0)
    seq = run_federated(cfg, fed, pub, clients, te, batch_size=16,
                        eval_batch=64)
    spmd = run_federated(cfg, dataclasses.replace(fed, backend="spmd"),
                         pub, clients, te, batch_size=16, eval_batch=64)
    return request.param, seq, spmd


def test_hetero_ledger_and_flops_parity_exact(hetero_both_backends):
    """Per-rank bucketing must report the same rank-dependent wire bytes
    and client FLOPs as the sequential backend — Fig. 4 extends to the
    heterogeneous setting without a backend-dependent story."""
    fw, seq, spmd = hetero_both_backends
    assert seq.ledger.per_round() == spmd.ledger.per_round(), fw
    assert seq.ledger.by_name() == spmd.ledger.by_name(), fw
    assert seq.ledger.per_client_round() == spmd.ledger.per_client_round(), fw
    np.testing.assert_array_equal(np.asarray(seq.client_flops),
                                  np.asarray(spmd.client_flops), err_msg=fw)


def test_hetero_accuracy_parity(hetero_both_backends):
    fw, seq, spmd = hetero_both_backends
    for hs, hp in zip(seq.history, spmd.history):
        assert abs(hs.loss - hp.loss) <= 1e-3, fw
        assert abs(hs.accuracy - hp.accuracy) <= 1e-3, fw


def test_hetero_weak_clients_move_fewer_bytes(hetero_both_backends):
    """The whole point of rank truncation: a rank-4 client's param
    exchange costs ~1/4 of the rank-16 client's."""
    fw, _, spmd = hetero_both_backends
    if fw == "kd":
        pytest.skip("KD exchanges logits, not params — rank-independent")
    pcr = spmd.ledger.per_client_round()
    assert pcr[(0, 0)] < pcr[(0, 3)], fw


def test_hetero_svd_aggregation_spmd(hetero_case):
    """The svd harmonization path runs under bucketing too."""
    cfg, pub, clients, te = hetero_case
    fed = FedConfig(framework="fedllm", n_clients=4, rounds=1,
                    lora_rank=16, client_ranks=(4, 8, 8, 16),
                    hetero_agg="svd", lora_dropout=0.0, seed=0,
                    backend="spmd")
    res = run_federated(cfg, fed, pub, clients, te, batch_size=16,
                        eval_batch=64)
    assert np.isfinite(res.history[-1].loss)


def test_client_ranks_validation(case_study):
    cfg, pub, clients, te = case_study
    bad_len = FedConfig(framework="fedllm", client_ranks=(4, 8))
    with pytest.raises(ValueError, match="entries"):
        run_federated(cfg, bad_len, pub, clients, te, batch_size=16)
    too_big = FedConfig(framework="fedllm", lora_rank=8,
                        client_ranks=(4, 8, 16))
    with pytest.raises(ValueError, match="lora_rank"):
        run_federated(cfg, too_big, pub, clients, te, batch_size=16)


# --------------------------------------------------------------------------- #
# Cross-engine golden-parity matrix (unified RoundProgram pipeline):
# sequential vs spmd vs async(max_staleness=0), per framework, from one
# shared fixture — identical CommLedger bytes, fp32-tolerant metrics.
# --------------------------------------------------------------------------- #
def test_engine_matrix_golden_parity(both_backends, case_study):
    fw, seq, spmd = both_backends
    cfg, pub, clients, te = case_study
    fed = FedConfig(framework=fw, n_clients=3, rounds=2, lora_rank=4,
                    lora_dropout=0.0, split_layer=2, kd_epochs=1, seed=0,
                    aggregation="async", max_staleness=0)
    engines = {
        "async-seq": run_federated(cfg, fed, pub, clients, te,
                                   batch_size=16, eval_batch=64),
        "async-spmd": run_federated(
            cfg, dataclasses.replace(fed, backend="spmd"), pub, clients,
            te, batch_size=16, eval_batch=64),
    }
    for name, res in engines.items():
        key = (fw, name)
        # one pipeline -> one ledger, byte-for-byte
        assert res.ledger.per_round() == seq.ledger.per_round(), key
        assert res.ledger.by_name() == seq.ledger.by_name(), key
        assert res.ledger.per_client_round() == \
            seq.ledger.per_client_round(), key
        np.testing.assert_array_equal(np.asarray(res.client_flops),
                                      np.asarray(seq.client_flops),
                                      err_msg=str(key))
        for ha, hs in zip(res.history, seq.history):
            assert abs(ha.loss - hs.loss) <= 1e-3, key
            assert abs(ha.accuracy - hs.accuracy) <= 1e-3, key
    # the sequential async(0) engine collapses onto sync EXACTLY
    for ha, hs in zip(engines["async-seq"].history, seq.history):
        assert ha.loss == hs.loss, fw
        assert ha.accuracy == hs.accuracy, fw
    # spmd sync agrees with spmd async(0) within fp32 tolerance too
    for ha, hp in zip(engines["async-spmd"].history, spmd.history):
        assert abs(ha.loss - hp.loss) <= 1e-3, fw


def test_unknown_backend_rejected(case_study):
    cfg, pub, clients, te = case_study
    fed = FedConfig(framework="fedllm", backend="async")
    with pytest.raises(ValueError, match="backend"):
        run_federated(cfg, fed, pub, clients, te, batch_size=16)


def test_spmd_handles_ragged_client_data(case_study):
    """Clients with unequal batch counts run via the padded/masked scan
    and still produce the sequential backend's exact ledger."""
    cfg, pub, clients, te = case_study
    ragged = [
        {k: v[: 16 + 16 * ci] for k, v in c.items()}
        for ci, c in enumerate(clients)
    ]
    fed = FedConfig(framework="fedllm", n_clients=3, rounds=1, lora_rank=4,
                    lora_dropout=0.0, seed=0)
    seq = run_federated(cfg, fed, pub, ragged, te, batch_size=16,
                        eval_batch=64)
    spmd = run_federated(cfg, dataclasses.replace(fed, backend="spmd"),
                         pub, ragged, te, batch_size=16, eval_batch=64)
    assert seq.ledger.per_client_round() == spmd.ledger.per_client_round()
    assert seq.client_flops == spmd.client_flops
    assert abs(seq.final_accuracy - spmd.final_accuracy) <= 1e-3
