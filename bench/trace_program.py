"""Run one benchmark cell once, traced, with the program's own spans and
counters on, and read what they show.

    python3 bench/trace_program.py --workload <name> --seed <n> --seconds <s>

The run is bench/run.py's traced run (``--trace 1``) with three
additions: the program's spans and counters (repro/obs.py) are on
through the window, the trace is also reduced by bench/scopes.py, and
the metrics that read them (``PROGRAM_METRICS``, each read by
bench/metrics/<name>.py) are reported beside the cell's own.  So that
bench/scopes.py finds each device op's layer scope, XLA dumps every
compiled module's HLO to a temporary directory, and the persistent
compile cache is off: every program compiles in set-up.  Prints
a ``program_trace`` line (the program's spans, counters and compile
seconds over the window) and a ``device_scopes`` line (device seconds
by kernel and by layer scope, idle seconds by span), then as the last
line of standard output bench/run.py's result object.  Exits 3,
printing no result, when JAX finds no TPU or too few chips.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Optional  # noqa: E402
from unittest import mock  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
HLO_DIR = ""
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # set before JAX starts its backend, which reads XLA_FLAGS once
    HLO_DIR = tempfile.mkdtemp(prefix="bench-hlo-")
    os.environ["XLA_FLAGS"] = " ".join(
        [os.environ.get("XLA_FLAGS", ""), f"--xla_dump_to={HLO_DIR}",
         "--xla_dump_hlo_as_text"]).strip()

from bench import harness, scopes, spec  # noqa: E402
from bench import trace as btrace  # noqa: E402

# metric -> unit
PROGRAM_METRICS = {"stage_s.evaluate": "s", "idle_s.evaluate": "s",
                   "host_syncs": "count", "pad_step_share": "%",
                   "kernel_s.lora_matmul": "s",
                   "kernel_s.flash_attention": "s",
                   "device_s.lm_head": "s"}


@dataclasses.dataclass
class ProgramRun(harness.RunRecord):
    program: Optional[Dict] = None   # repro.obs.snapshot() of the window
    scopes: Optional[Dict] = None    # bench/scopes.reduce_trace


def traced_run(cell: spec.Cell, seed: int, seconds: float, devices,
               t_start: float, hlo_dir: str = "", log=print) -> Dict:
    """harness.run_cell, traced, with the program's spans on in the
    window; its record is a ProgramRun.  ``hlo_dir`` holds the HLO that
    XLA dumped (bench/scopes.reduce_trace)."""
    from repro import obs
    kept: Dict = {}
    reduce_trace = btrace.reduce_trace

    class ProgramDriver(harness.Driver):
        def round_end(self):
            # what the window recorded, taken before the profiler stops
            if self.in_window:
                kept["program"] = obs.snapshot()
            try:
                super().round_end()
            except harness.WindowClosed:
                obs.disable()
                raise
            if self.in_window and self.rounds_done == self.warmup:
                obs.reset()
                obs.enable()

    def reduce_both(trace_dir):
        kept["scopes"] = scopes.reduce_trace(trace_dir, hlo_dir)
        return reduce_trace(trace_dir)

    with mock.patch.object(harness, "Driver", ProgramDriver), \
            mock.patch.object(btrace, "reduce_trace", reduce_both):
        res = harness.run_cell(cell, seed, seconds, True, t_start, devices,
                               log)
    run = res["run"]
    res["run"] = ProgramRun(
        **{f.name: getattr(run, f.name)
           for f in dataclasses.fields(harness.RunRecord)},
        program=kept.get("program"), scopes=kept.get("scopes"))
    return res


def result(cell: spec.Cell, res: Dict, devices) -> Dict:
    """bench/run.py's traced result, with PROGRAM_METRICS added."""
    out = harness.result_line(cell, res, devices, traced=True)
    for name, unit in PROGRAM_METRICS.items():
        value = spec.metric_reader(name)(res["run"])
        if value is not None:
            out["metrics"][name] = {"value": value, "unit": unit}
    return out


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    return p.parse_args(argv)


def main(args, hlo_dir: str) -> int:
    import jax
    cell = spec.load_cell(args.workload)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        devices = harness.check_devices(cell.chips)
        spec.read_peaks(devices[0].device_kind)
    except (harness.NoChip, KeyError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 3

    def log(line):
        print(line, flush=True)
    res = traced_run(cell, args.seed, args.seconds, devices, T_START,
                     hlo_dir, log)
    log("program_trace " + json.dumps(res["run"].program))
    log("device_scopes " + json.dumps(res["run"].scopes))
    print(json.dumps(result(cell, res, devices)), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main(parse(sys.argv[1:]), HLO_DIR))
    finally:
        shutil.rmtree(HLO_DIR, ignore_errors=True)
