"""Bring-up smoke run on a TPU: the paper's case study (GPT-2 124M,
Banking77-shaped data, 3 IID clients, LoRA rank 8) through the library
entry point ``repro.core.rounds.run_federated`` at full model width.

    python chip_smoke.py             # one chip: fedllm, kd, split + check
    python chip_smoke.py --chips 4   # four chips: the client axis sharded
                                     # over a (4, 1) mesh vs unsharded

Weights are random (seeded) and the data is synthetic, so accuracy is
not meaningful; the run checks that every phase completes with finite
metrics, that the Pallas kernels ran compiled (``tpu_custom_call`` in
the local-update program), and that the fused path agrees with the XLA
reference on one batch.  Each phase prints its compile and non-compile
seconds on its own line; the last line of stdout is one JSON object
naming the device.  Exits non-zero, printing no result, when JAX finds
no TPU or any phase fails.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

# Data scale of banking77.paper_splits: 500 public / 500 train (3 x 166,
# 10 local steps of 16 per round) / 616 test samples — the cold run,
# compiles included, stays within a few minutes on one v5e chip.
SCALE = 0.1
PAD_LEN = 80
BATCH = 16
# KD distills 3 clients at once on public batches of EVAL_BATCH through
# the full 50,257-entry LM head: at 64 that step needs 21 GiB of HBM on
# a 16 GiB v5e (ahead-of-time compile), at 16 about 8 GiB
EVAL_BATCH = 16
ROUNDS = 2
# pallas vs xla forward on one batch: both paths run f32 matmuls at the
# TPU's default precision (bf16 passes, f32 accumulation) in different
# orders, so they agree to about 2^-8 per product, not to f32 epsilon;
# an indexing or masking bug in a kernel shows up as an O(1) error.
REF_LOGIT_TOL = 2e-2      # max |diff| over max |logit|
REF_LOSS_TOL = 1e-2       # |diff| over max(1, |loss|)
# sharded vs unsharded round loss: the same per-client math, but XLA
# tiles a 1-client and a 4-client stack differently, so f32 reduction
# order differs and drifts through the local SGD steps
MESH_LOSS_RTOL = 2e-3


# --------------------------------------------------------------------------- #
# Compile accounting: JAX reports every trace, lowering and backend compile
# --------------------------------------------------------------------------- #
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")
_STATS = {"compile_s": 0.0, "trace_s": 0.0, "cache_hits": 0,
          "cache_misses": 0}


def _on_duration(event, duration, **_):
    if event == _BACKEND_COMPILE:
        _STATS["compile_s"] += duration
    elif event in _TRACE:
        _STATS["trace_s"] += duration


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _STATS["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _STATS["cache_misses"] += 1


@contextlib.contextmanager
def phase(name: str):
    """Time one phase and print, on one line: ``compile_s``, the seconds
    of backend (XLA + Mosaic) compiles or persistent-cache loads;
    ``steady_s``, the rest of the wall time (host work, device work and
    tracing); ``trace_s``, JAX's tracing and lowering seconds (a jit
    traced inside another is counted in both); and the persistent
    compile-cache hits and misses."""
    before = dict(_STATS)
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    d = {k: _STATS[k] - before[k] for k in _STATS}
    print(f"phase={name} compile_s={d['compile_s']:.3f} "
          f"steady_s={wall - d['compile_s']:.3f} wall_s={wall:.3f} "
          f"trace_s={d['trace_s']:.3f} cache_hits={d['cache_hits']} "
          f"cache_misses={d['cache_misses']}", flush=True)


# --------------------------------------------------------------------------- #
# Reading back the local-update program that ran
# --------------------------------------------------------------------------- #
class _Recorder:
    """Wraps a stage program and keeps the abstract arguments of its
    first call, so the same program can be lowered and compiled again
    and its HLO read."""

    def __init__(self, fn):
        self.fn, self.specs = fn, None

    def __call__(self, *args):
        if self.specs is None:
            self.specs = jax.tree.map(_spec, args)
        return self.fn(*args)

    def compiled_text(self) -> str:
        fn = self.fn if hasattr(self.fn, "lower") else jax.jit(self.fn)
        return fn.lower(*self.specs).compile().as_text()


def _spec(x):
    sharding = getattr(x, "sharding", None) if isinstance(x, jax.Array) \
        else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


@contextlib.contextmanager
def record_local_update(framework: str):
    """Route the SPMD executor's local-update builder for ``framework``
    through a _Recorder; yields a one-element list holding it."""
    from repro.core import fed_spmd
    name = {"fedllm": "make_bucket_update", "kd": "make_kd_spmd_fns",
            "split": "make_split_spmd_segment"}[framework]
    orig, got = getattr(fed_spmd, name), []

    def build(*a, **kw):
        out = orig(*a, **kw)
        if framework == "kd":
            rec = _Recorder(out["client_update"])
            out = dict(out, client_update=rec)
        else:
            rec = _Recorder(out)
            out = rec
        got.append(rec)
        return out

    setattr(fed_spmd, name, build)
    try:
        yield got
    finally:
        setattr(fed_spmd, name, orig)


# --------------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------------- #
def case_study(cfg, n_clients: int, scale: float = SCALE):
    from repro.data import banking77, partition
    from repro.data.population import ClientPopulation
    pub, tr, te = banking77.paper_splits(cfg.vocab_size, pad_len=PAD_LEN,
                                         scale=scale)
    clients = ClientPopulation.from_clients_data(
        partition.iid_partition(tr, n_clients, seed=0))
    return pub, clients, te


def fed_config(framework: str, n_clients: int):
    from repro.configs.base import FedConfig
    return FedConfig(framework=framework, n_clients=n_clients,
                     rounds=ROUNDS, lora_rank=8, backend="spmd", seed=0)


def check_result(name: str, res) -> None:
    for h in res.history:
        print(f"  {name} round={h.round} loss={h.loss:.6f} "
              f"acc={h.accuracy:.4f}", flush=True)
        assert math.isfinite(h.loss) and math.isfinite(h.accuracy), \
            (name, h)
    assert len(res.history) == ROUNDS, (name, len(res.history))
    by_name = res.ledger.by_name()
    print(f"  {name} ledger={json.dumps(by_name, sort_keys=True)}",
          flush=True)
    assert by_name and res.ledger.total() > 0, (name, by_name)


def run_framework(cfg, framework: str, data, expect_kernels: bool = True):
    """Two rounds of ``framework`` on the spmd backend; asserts finite
    metrics, a non-empty CommLedger and Pallas kernels compiled into the
    local-update program."""
    from repro.core.rounds import run_federated
    from repro.kernels import ops
    pub, clients, te = data
    fed = fed_config(framework, len(clients))
    with phase(framework), record_local_update(framework) as recs:
        res = run_federated(cfg, fed, pub, clients, te, batch_size=BATCH,
                            eval_batch=EVAL_BATCH)
    check_result(framework, res)
    assert len(recs) == 1 and recs[0].specs is not None, framework
    with phase(f"{framework}_hlo"), ops.policy_scope(cfg.kernel_policy):
        text = recs[0].compiled_text()
    n = text.count("tpu_custom_call")
    print(f"  {framework} local_update tpu_custom_calls={n}", flush=True)
    if expect_kernels:
        assert n > 0, f"{framework}: no Pallas kernel in the local update"


def reference_check(cfg, data):
    """One forward + classification loss on one batch at ``cfg``'s widths
    under kernel_policy="pallas" and "xla"; asserts agreement."""
    import jax.numpy as jnp

    from repro.configs.base import FedConfig
    from repro.core.fedavg import make_fns
    from repro.models.factory import build_model
    from repro.peft import lora as lora_lib
    pub = data[0]
    batch = {k: jnp.asarray(v[:BATCH]) for k, v in pub.items()}
    fed = FedConfig(lora_rank=8, lora_dropout=0.0)
    key = jax.random.PRNGKey(0)
    base = build_model(cfg).init(key)
    lt = lora_lib.init_lora(jax.random.fold_in(key, 1), base,
                            fed.lora_targets, fed.lora_rank)
    # B = 0 at init; make the LoRA branch carry signal through the kernel.
    # Kept small (std 0.002, alpha/r = 4): at std 0.02 the adapters blow
    # up q/k, the attention softmax saturates, and rounding the weights
    # to bf16 alone moves the logits by 77% of their max — a check that
    # then cannot tell a kernel bug from TPU matmul precision.
    lt = jax.tree_util.tree_map_with_path(
        lambda path, x: 0.002 * jax.random.normal(
            jax.random.fold_in(key, 2), x.shape, x.dtype)
        if getattr(path[-1], "key", None) == "b" else x, lt)
    out = {}
    for policy in ("pallas", "xla"):
        fns = make_fns(build_model(dataclasses.replace(
            cfg, kernel_policy=policy)), fed, "classification")
        logits = fns["logits_fn"](base, lt, batch)
        loss = fns["eval_step"](base, lt, batch)[1]
        out[policy] = (np.asarray(logits, np.float32), float(loss))
        assert np.isfinite(out[policy][0]).all() and math.isfinite(loss), \
            policy
    (lp, sp), (lx, sx) = out["pallas"], out["xla"]
    logit_err = float(np.max(np.abs(lp - lx)) / np.max(np.abs(lx)))
    loss_err = abs(sp - sx) / max(1.0, abs(sx))
    print(f"  reference logits{list(lp.shape)} rel_max_err={logit_err:.3e} "
          f"(tol {REF_LOGIT_TOL}) loss pallas={sp:.6f} xla={sx:.6f} "
          f"rel_err={loss_err:.3e} (tol {REF_LOSS_TOL})", flush=True)
    assert logit_err <= REF_LOGIT_TOL, logit_err
    assert loss_err <= REF_LOSS_TOL, loss_err


def mesh_parity(cfg, data, n_dev: int = 4):
    """FedLLM and KD with one client per device: the client axis sharded
    over a (n_dev, 1) mesh vs the same run unsharded on one device.
    Ledger bytes identical, round losses within MESH_LOSS_RTOL, and
    every stacked leaf spans all n_dev devices."""
    from repro.core.rounds import run_federated
    from repro.launch import sharding
    pub, clients, te = data
    mesh = jax.make_mesh((n_dev, 1), ("data", "model"))
    placed = []
    orig = sharding.shard_client_tree

    def spy(mesh_, tree):
        out = orig(mesh_, tree)
        placed.extend(jax.tree.leaves(out))
        return out

    for framework in ("fedllm", "kd"):
        fed = fed_config(framework, len(clients))
        with phase(f"{framework}_unsharded"):
            plain = run_federated(cfg, fed, pub, clients, te,
                                  batch_size=BATCH, eval_batch=EVAL_BATCH)
        check_result(f"{framework}_unsharded", plain)
        placed.clear()
        sharding.shard_client_tree = spy
        try:
            with phase(f"{framework}_mesh{n_dev}"):
                shard = run_federated(cfg, fed, pub, clients, te,
                                      batch_size=BATCH,
                                      eval_batch=EVAL_BATCH, mesh=mesh)
        finally:
            sharding.shard_client_tree = orig
        check_result(f"{framework}_mesh{n_dev}", shard)
        spans = {len(x.sharding.device_set) for x in placed}
        print(f"  {framework} stacked_leaves={len(placed)} "
              f"device_spans={sorted(spans)}", flush=True)
        assert placed and spans == {n_dev}, (framework, spans)
        assert plain.ledger.by_name() == shard.ledger.by_name(), framework
        assert plain.ledger.per_client_round() == \
            shard.ledger.per_client_round(), framework
        for hp, hs in zip(plain.history, shard.history):
            err = abs(hp.loss - hs.loss) / max(1.0, abs(hp.loss))
            print(f"  {framework} round={hp.round} loss unsharded="
                  f"{hp.loss:.6f} mesh={hs.loss:.6f} rel_err={err:.3e} "
                  f"(tol {MESH_LOSS_RTOL})", flush=True)
            assert err <= MESH_LOSS_RTOL, (framework, hp.round, err)


# --------------------------------------------------------------------------- #
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh-sharded client-axis phase")
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips}: JAX found {len(devices)} devices",
              file=sys.stderr)
        return 1

    from repro.configs.gpt2_small import gpt2
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    cfg = gpt2()
    with phase("device"):
        print(f"  platform={devices[0].platform} kind="
              f"{devices[0].device_kind} count={len(devices)} "
              f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)
        assert ops.resolve(cfg.kernel_policy) == "pallas", cfg.kernel_policy
        assert ops.INTERPRET is False
        print(f"  model={cfg.name} layers={cfg.n_layers} "
              f"d_model={cfg.d_model} vocab={cfg.vocab_size} "
              f"pad_len={PAD_LEN} batch={BATCH} scale={SCALE}", flush=True)

    if args.chips == 4:
        mesh_parity(cfg, case_study(cfg, 4), 4)
    else:
        data = case_study(cfg, 3)
        for framework in ("fedllm", "kd", "split"):
            run_framework(cfg, framework, data)
        with phase("reference"):
            reference_check(cfg, data)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
