"""One run of one cell: set-up, the measured window, the check.

The window drives the program's own entry point,
``repro.core.rounds.run_federated``, once per run.  Its first
``warmup_rounds`` rounds are set-up (they compile or load every program
from the cache); the window is the whole rounds after them, closed at
the first round boundary past ``--seconds``.  Every round ends
synchronised, because evaluation returns Python floats.

The benchmark never edits the program.  It wraps calls into it:

- the framework's program class and functions, as the framework's own
  file ``bench/frameworks/<framework>.py`` says: its stages are timed
  (traced run only, each ending in ``block_until_ready``) and its first
  round's outputs are kept for the check;
- ``fed_spmd.stack_trees`` / ``unstack_tree``, the executor's host
  helpers, timed in the traced run;
- ``rounds.build_model``, so that the program runs on the weights this
  benchmark made from the seed.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, spec
from bench import data as bdata
from bench import program as bprog
from bench import trace as btrace
from bench import weights as bweights
from bench.references import decoder as ref

# jax.monitoring events: tracing, lowering and backend compiles (copied
# from the bring-up accounting in chip_smoke.py)
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised at the first round boundary past the window's length."""


def check_devices(chips: int) -> List:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Seconds of tracing, lowering and compiling, and cache misses,
    from ``jax.monitoring``.  One listener per process, registered on
    first use."""

    _instance = None

    def __init__(self):
        self.seconds = 0.0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _dur(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.misses


@dataclasses.dataclass
class RunRecord:
    """What the per-layer metric readers (bench/metrics/*.py) read."""
    rounds: int                      # whole rounds in the window
    round_s: float
    chips: int
    spans: Dict[str, float]          # stage seconds summed over the window
    compile_s: float                 # tracing + lowering + compiling
    compile_misses: int
    flops: List[float]               # required operations, per window round
    peak_flops: float                # per chip, bf16
    traced: bool
    trace: Optional[Dict] = None     # bench/trace.reduce_trace


def _block(tree):
    jax.block_until_ready(jax.tree.leaves(tree))


class Driver:
    """The wrappers' shared state for one run."""

    def __init__(self, seconds: float, warmup: int, traced: bool):
        self.seconds, self.warmup, self.traced = seconds, warmup, traced
        self.rounds_done = 0
        self.window_rounds = 0
        self.in_window = False
        self.spans = collections.Counter()
        self.first: Dict = {}           # the first round's outputs
        self.fed: Dict[int, list] = collections.defaultdict(list)
        self.lora_init = None
        self.t_setup = self.t_win0 = self.t_win1 = None
        self.compiles0 = None
        self.compiles = (0.0, 0)
        self.trace_dir = None
        self._win_span = None

    # -- spans ---------------------------------------------------------- #
    def timed(self, name, fn, *args, block=None, **kw):
        if not (self.traced and self.in_window):
            return fn(*args, **kw)
        with jax.profiler.TraceAnnotation("stage." + name):
            t = time.perf_counter()
            out = fn(*args, **kw)
            if block is not None:
                _block(block(out))
            self.spans[name] += time.perf_counter() - t
        return out

    # -- round boundary ------------------------------------------------- #
    def round_end(self):
        self.rounds_done += 1
        now = time.perf_counter()
        if self.rounds_done == self.warmup:
            self.t_setup = self.t_win0 = now
            self.compiles0 = CompileCounter.get().snapshot()
            self.in_window = True
            if self.traced:
                self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(self.trace_dir)
                self._win_span = jax.profiler.TraceAnnotation("bench.window")
                self._win_span.__enter__()
        elif self.rounds_done > self.warmup:
            self.window_rounds += 1
            if now - self.t_win0 >= self.seconds:
                self.t_win1 = now
                s, m = CompileCounter.get().snapshot()
                self.compiles = (s - self.compiles0[0], m - self.compiles0[1])
                self.in_window = False
                if self.traced:
                    self._win_span.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                raise WindowClosed()


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def wrap_program(drv: Driver, fw, program_params, framework: str):
    """Install the wrappers for one ``run_federated`` call: the
    framework's (``fw``, bench/frameworks/<framework>.py), the spans of
    the executor's host helpers, and the benchmark's weights."""
    from repro.core import fed_spmd
    from repro.core import round_program as rp
    from repro.core import rounds

    def timed(name, fn):
        # wait for the device outside the span, so that the span holds
        # the helper's own host time (traced run only)
        def call(*a, **kw):
            if drv.traced and drv.in_window:
                jax.block_until_ready(a)
            return drv.timed(name, fn, *a, **kw)
        return call

    def build_model(cfg):
        model = build(cfg)
        params = bprog.params_for(model, program_params)

        class Seeded(type(model)):
            def init(self, key, dtype=jnp.float32):
                return params
        return Seeded(cfg)

    build = rounds.build_model
    programs = dict(rp.PROGRAMS)
    programs[framework] = fw.program_class(rp.PROGRAMS[framework], drv)
    with contextlib.ExitStack() as stack:
        for obj, name, value in [
                (fed_spmd, "stack_trees",
                 timed("stack_trees", fed_spmd.stack_trees)),
                (fed_spmd, "unstack_tree",
                 timed("unstack_tree", fed_spmd.unstack_tree)),
                (rp, "PROGRAMS", programs),
                (rounds, "build_model", build_model)] + fw.patches(drv):
            stack.enter_context(_patched(obj, name, value))
        yield


# --------------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------------- #
def make_inputs(cell: spec.Cell, seed: int):
    """(arch, data splits, client shards) from the seed."""
    arch = ref.arch_from_config(cell.config)
    d = cell.traffic["data"]
    public, train, test = bdata.splits(seed, arch.vocab_size, d["pad_len"],
                                       d["n_public"], d["n_train"],
                                       d["n_test"])
    shards = bdata.iid_partition(train, cell.traffic["fed"]["n_clients"],
                                 seed + 1)
    return arch, public, shards, test


def memory_peak(devices, log=print) -> int:
    """``peak_bytes_in_use`` of the fullest chip.  Every key of each
    chip's memory statistics is logged (PERF.md, section 2, says what
    the peak does not see)."""
    stats = [d.memory_stats() or {} for d in devices]
    log("memory_stats " + json.dumps(stats))
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def fed_config(traffic: Dict, seed: int):
    from repro.configs.base import FedConfig
    return FedConfig(framework=traffic["framework"], rounds=1_000_000,
                     seed=seed & 0x7FFFFFFF, **traffic["fed"])


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, devices, log=print) -> Dict:
    """Set up, measure, check.  Returns the result record."""
    from repro.core.rounds import run_federated
    from repro.data.population import ClientPopulation

    CompileCounter.get()
    t = cell.traffic
    fw = spec.framework(t["framework"])
    arch, public, shards, test = make_inputs(cell, seed)
    weights = bweights.make_weights(seed, arch)
    lora0 = bweights.make_lora(seed, arch, t["fed"]["lora_rank"])
    drv = Driver(seconds, t["warmup_rounds"], traced)
    drv.lora_init = lora0
    mcfg = bprog.model_config(cell.config)
    mesh = None
    if t.get("mesh"):
        from jax.sharding import AxisType, Mesh
        mesh = Mesh(np.array(devices).reshape(t["mesh"]), ("data", "model"),
                    axis_types=(AxisType.Auto,) * 2)
    with wrap_program(drv, fw, weights, t["framework"]):
        try:
            run_federated(mcfg, fed_config(t, seed), public,
                          ClientPopulation.from_clients_data(shards), test,
                          batch_size=t["batch_size"],
                          eval_batch=t["eval_batch"], mesh=mesh)
            raise RuntimeError("run_federated returned before the window "
                               "closed")
        except WindowClosed:
            pass
    window = drv.t_win1 - drv.t_win0
    setup_s = drv.t_setup - t_start
    log(f"window rounds={drv.window_rounds} seconds={window:.3f} "
        f"asked={seconds} overrun={window - seconds:.3f} "
        f"setup_s={setup_s:.3f} compile_s_in_window={drv.compiles[0]:.3f} "
        f"cache_misses_in_window={drv.compiles[1]}")
    peak = memory_peak(devices, log)

    # the program's state is gone with the call; keep only what the
    # check and the metrics read
    window_rounds = range(t["warmup_rounds"],
                          t["warmup_rounds"] + drv.window_rounds)
    flops = [fw.round_flops(arch, t, drv.fed[r], test)
             for r in window_rounds]
    gc.collect()

    trace = None
    if traced:
        trace = btrace.reduce_trace(drv.trace_dir)
        shutil.rmtree(drv.trace_dir, ignore_errors=True)
    values = fw.readings(drv.first, {
        "arch": arch, "weights": weights, "lora0": lora0, "traffic": t,
        "shards": shards, "test": test}, log)
    log("readings " + json.dumps(values))
    checks = compare.judge(values, cell.limits)
    run = RunRecord(rounds=drv.window_rounds,
                    round_s=window / drv.window_rounds, chips=cell.chips,
                    spans=dict(drv.spans), compile_s=drv.compiles[0],
                    compile_misses=drv.compiles[1], flops=flops,
                    peak_flops=0.0, traced=traced, trace=trace)
    return {"run": run, "setup_s": setup_s, "peak": peak, "checks": checks}


def result_line(cell: spec.Cell, res: Dict, devices, traced: bool) -> Dict:
    """The last line of standard output."""
    run: RunRecord = res["run"]
    kind = devices[0].device_kind
    metrics = {}
    if traced:
        run.peak_flops = float(spec.read_peaks(kind)["bf16_flops_per_s"])
        for m in cell.per_layer:
            v = spec.metric_reader(m.name)(run)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
    else:
        e2e = {"setup_s": res["setup_s"], "round_s": run.round_s}
        for m in cell.end_to_end:
            metrics[m.name] = {"value": e2e[m.name], "unit": m.unit}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(res["peak"])}
    out = {"correct": compare.all_pass(res["checks"]),
           "attempted": run.rounds, "failed": 0, "metrics": metrics,
           "device": device}
    if traced:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = res["checks"]
    return out


def main(args, t_start: float) -> int:
    cell = spec.load_cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = check_devices(cell.chips)
        spec.read_peaks(devices[0].device_kind)
    except (NoChip, KeyError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start,
                   devices, log=lambda s: print(s, flush=True))
    out = result_line(cell, res, devices, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
