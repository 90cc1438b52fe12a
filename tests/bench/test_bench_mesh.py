"""The harness on a 4-device mesh (four virtual CPU devices, in a child
process), its chip check skipped: a sound run is correct, and a run in
which the exchange between chips is left out (the server gets chip 0's
client results in place of the others') is not."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CHILD = r"""
import copy, json, sys, time
sys.path[:0] = [{tests!r}, {root!r}, {src!r}]
import jax
import numpy as np
from conftest import TINY_TRAFFIC, tiny_cell
from bench import harness

if {broken!r}:
    from repro.core import round_program as rp
    home = rp.SpmdExecutor._home

    def chip0_only(self, *trees):
        out = home(self, *trees)
        def tile(x):
            q = x.shape[0] // 4
            return jax.numpy.concatenate([x[:q]] * 4)
        out = jax.tree.map(tile, out)
        return out
    rp.SpmdExecutor._home = chip0_only

t = copy.deepcopy(TINY_TRAFFIC)
t["fed"]["n_clients"] = 8
t["data"]["n_train"] = 8 * 24
t["mesh"] = [4, 1]
cell = tiny_cell()
cell.traffic, cell.chips = t, 4
devs = jax.devices()[:4]
res = harness.run_cell(cell, 2 ** 31 + 99, 0.0, False, time.perf_counter(),
                       devs, log=lambda s: None)
print(json.dumps(harness.result_line(cell, res, devs, False)))
"""


@pytest.mark.parametrize("broken", [False, True],
                         ids=["sound", "exchange-left-out"])
def test_mesh_run(broken):
    code = CHILD.format(tests=str(ROOT / "tests" / "bench"), root=str(ROOT),
                        src=str(ROOT / "src"), broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (not broken), line["checks"]
