"""Unit tests for the composable round pipeline (core/round_program.py):
schedule semantics, subsampling-rate reporting, and the mesh-sharded
SPMD executor path (client-axis NamedShardings from launch/sharding)."""
import pathlib
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs.base import FedConfig, ModelConfig
from repro.core import round_program as rp
from repro.core.rounds import run_federated
from repro.data import banking77, partition

CFG = ModelConfig(name="rp-t", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=192,
                  qkv_bias=True, activation="gelu", norm="layernorm",
                  use_rope=False, max_position_embeddings=64)


@pytest.fixture(scope="module")
def small_case():
    pub = banking77.generate(24, CFG.vocab_size, 12, seed=0)
    tr = banking77.generate(96, CFG.vocab_size, 12, seed=1)
    te = banking77.generate(16, CFG.vocab_size, 12, seed=2)
    return pub, partition.iid_partition(tr, 3, seed=0), te


def _fed(**kw):
    base = dict(framework="fedllm", n_clients=3, rounds=1, lora_rank=4,
                lora_dropout=0.0, split_layer=1, kd_epochs=1, seed=0)
    base.update(kw)
    return FedConfig(**base)


# --------------------------------------------------------------------------- #
# Schedules
# --------------------------------------------------------------------------- #
def test_sync_schedule_everyone_starts_and_arrives_same_round():
    s = rp.SyncSchedule(_fed(), 3)
    assert s.starters(0) == [0, 1, 2]
    for ci in (2, 0, 1):
        s.submit(0, ci, f"p{ci}")
    jobs = s.pop_arrivals(0)
    assert [j.client for j in jobs] == [0, 1, 2]       # visit order
    assert all(j.start == j.arrival == 0 for j in jobs)
    assert s.pop_arrivals(1) == []


def test_async_schedule_in_flight_clients_do_not_restart():
    fed = _fed(aggregation="async", max_staleness=4, seed=1)
    s = rp.AsyncSchedule(fed, 4)
    assert s.starters(0) == [0, 1, 2, 3]
    for ci in s.starters(0):
        s.submit(0, ci, None)
    arrived = {j.client for j in s.pop_arrivals(0)}
    # whoever is still in flight cannot start round 1
    assert set(s.starters(1)) == arrived
    # zero max_staleness degenerates to the sync schedule
    s0 = rp.AsyncSchedule(_fed(aggregation="async", max_staleness=0), 3)
    for ci in s0.starters(0):
        s0.submit(0, ci, None)
    assert [j.client for j in s0.pop_arrivals(0)] == [0, 1, 2]


def test_make_schedule_dispatch():
    assert isinstance(rp.make_schedule(_fed(), 3), rp.SyncSchedule)
    assert isinstance(rp.make_schedule(_fed(aggregation="async"), 3),
                      rp.AsyncSchedule)


# --------------------------------------------------------------------------- #
# Subsampling-rate reporting (accountant wiring)
# --------------------------------------------------------------------------- #
def test_sample_rate_worst_case_over_clients():
    clients = [{"tokens": np.zeros((32, 4))}, {"tokens": np.zeros((8, 4))}]
    assert rp.sample_rate(clients, 8) == 1.0        # 8/8 clamps at 1
    clients = [{"tokens": np.zeros((32, 4))}, {"tokens": np.zeros((16, 4))}]
    assert rp.sample_rate(clients, 8) == 0.5        # max(8/32, 8/16)


def test_make_accountant_threads_sample_rate():
    from repro.configs.base import PrivacyConfig
    fed = _fed(privacy=PrivacyConfig(dp_clip=1.0, dp_noise_multiplier=1.0))
    a = rp.make_accountant(fed, sample_rate=0.25)
    assert a.sample_rate == 0.25
    assert rp.make_accountant(_fed()) is None       # DP off -> no claim


# --------------------------------------------------------------------------- #
# Stage-spec sourcing: the launch layer compiles the SAME specs
# --------------------------------------------------------------------------- #
def test_launch_builds_from_stage_specs():
    import inspect

    from repro.launch import steps
    src = inspect.getsource(steps)
    for sym in ("FedLLMProgram.spmd_round", "KDProgram.spmd_round",
                "SplitProgram.spmd_round"):
        assert f"round_program.{sym}" in src, sym


# --------------------------------------------------------------------------- #
# Mesh-sharded SPMD executor (client axis on the mesh)
# --------------------------------------------------------------------------- #
def _one_device_mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_client_sharding_helpers():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import client_axes, client_axis_size
    from repro.launch.sharding import client_spec, shard_client_tree

    mesh = _one_device_mesh()
    assert client_axes(mesh) == ("data",)
    assert client_axis_size(mesh) == 1
    assert client_spec(mesh, 3) == P(("data",), None, None)
    tree = {"a": jax.numpy.ones((2, 3)), "b": jax.numpy.zeros((2,))}
    out = shard_client_tree(mesh, tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(tree[k]))
        assert out[k].sharding.spec == client_spec(mesh, tree[k].ndim)


@pytest.mark.parametrize("framework", ["fedllm", "kd"])
def test_spmd_runtime_with_mesh_matches_unsharded(small_case, framework):
    """run_federated(..., mesh=...) drives the SPMD executor through
    explicit client-axis NamedShardings and reproduces the unsharded
    run: the mesh is a placement concern, never a numerics one."""
    pub, clients, te = small_case
    fed = _fed(framework=framework, backend="spmd")
    plain = run_federated(CFG, fed, pub, clients, te, batch_size=8,
                          eval_batch=8)
    sharded = run_federated(CFG, fed, pub, clients, te, batch_size=8,
                            eval_batch=8, mesh=_one_device_mesh())
    assert plain.ledger.per_client_round() == \
        sharded.ledger.per_client_round()
    assert plain.ledger.by_name() == sharded.ledger.by_name()
    for hp, hs in zip(plain.history, sharded.history):
        assert abs(hp.loss - hs.loss) <= 1e-5, framework
    for a, b in zip(jax.tree.leaves(plain.final_lora),
                    jax.tree.leaves(sharded.final_lora)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)


MESH4_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src"); sys.path.insert(0, "tests")
    import jax, numpy as np
    from test_round_program import CFG, _fed
    from repro.core.rounds import run_federated
    from repro.data import banking77, partition
    from repro.launch import sharding

    placed = []
    orig = sharding.shard_client_tree
    def spy(mesh, tree):
        out = orig(mesh, tree)
        placed.extend(jax.tree.leaves(out))
        return out
    sharding.shard_client_tree = spy

    pub = banking77.generate(24, CFG.vocab_size, 12, seed=0)
    tr = banking77.generate(128, CFG.vocab_size, 12, seed=1)
    te = banking77.generate(16, CFG.vocab_size, 12, seed=2)
    clients = partition.iid_partition(tr, 4, seed=0)
    mesh = jax.make_mesh((4, 1), ("data", "model"))
    for fw in ("fedllm", "kd"):
        fed = _fed(framework=fw, backend="spmd", n_clients=4, rounds=2)
        plain = run_federated(CFG, fed, pub, clients, te, batch_size=8,
                              eval_batch=8)
        placed.clear()
        sharded = run_federated(CFG, fed, pub, clients, te, batch_size=8,
                                eval_batch=8, mesh=mesh)
        assert placed and all(len(x.sharding.device_set) == 4
                              for x in placed), fw
        # the server's state comes back to one device: its programs
        # (evaluation, aggregation) must never see mesh-placed operands
        assert all(len(x.sharding.device_set) == 1
                   for x in jax.tree.leaves(sharded.final_lora)), fw
        assert plain.ledger.by_name() == sharded.ledger.by_name(), fw
        assert plain.ledger.per_client_round() == \\
            sharded.ledger.per_client_round(), fw
        for hp, hs in zip(plain.history, sharded.history):
            assert abs(hp.loss - hs.loss) <= 1e-5, (fw, hp.loss, hs.loss)
        for a, b in zip(jax.tree.leaves(plain.final_lora),
                        jax.tree.leaves(sharded.final_lora)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-5)
    print("MESH4_PARITY_OK")
""")


def test_spmd_runtime_on_four_device_mesh_matches_unsharded():
    """The client axis sharded over 4 (virtual) devices — every stacked
    leaf spans all four — reproduces the unsharded run.  A subprocess
    keeps the forced device count out of the other tests."""
    root = pathlib.Path(__file__).resolve().parents[1]
    out = subprocess.run([sys.executable, "-c", MESH4_SUBPROC], cwd=root,
                         capture_output=True, text=True, timeout=480)
    assert "MESH4_PARITY_OK" in out.stdout, out.stderr[-3000:]


def test_adapters_contain_no_per_driver_threading():
    """The acceptance clause: core/rounds*.py are adapters only — no
    privacy/hetero/async code paths left behind."""
    import inspect

    from repro.core import rounds, rounds_spmd

    for mod in (rounds, rounds_spmd):
        src = inspect.getsource(mod)
        for banned in ("privatize", "SecureAggSession", "secagg.",
                       "stale_weighted_avg", "rank_buckets",
                       "rank_segments", "harmonize_buckets",
                       "ParticipationSchedule"):
            assert banned not in src, (mod.__name__, banned)
