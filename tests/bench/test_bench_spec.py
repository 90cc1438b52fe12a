"""Discovery of a cell's files by name, the name and unit rules, and
the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT


def test_every_cell_of_the_benchmark_loads():
    from bench import spec
    bench = spec.load_benchmark()
    assert [w["name"] for w in bench["workloads"]][0] == "gpt2-fedllm"
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["source"].startswith("https://")
        assert {m.name for m in cell.end_to_end} >= {"setup_s", "round_s"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m.name))
            assert m.moves == "round_s"


@pytest.mark.parametrize("name,ok", [
    ("gpt2-fedllm", True), ("stage_s.local_update", True), ("_x", True),
    ("a" * 64, True), ("a" * 65, False), ("has space", False),
    ("a/b", False), ("a,b", False), (".hidden", False), ("-x", False),
    ("µs", False), ("", False)])
def test_name_rule(name, ok):
    from bench import spec
    if ok:
        assert spec.check_name(name) == name
    else:
        with pytest.raises(ValueError):
            spec.check_name(name)


@pytest.mark.parametrize("unit,ok", [
    ("s", True), ("GiB", True), ("%", True), ("tokens/s", True),
    ("tokens per second", False), ("a" * 17, False), ("µs", False)])
def test_unit_rule(unit, ok):
    from bench import spec
    if ok:
        assert spec.check_unit(unit) == unit
    else:
        with pytest.raises(ValueError):
            spec.check_unit(unit)


def test_benchmark_json_names_and_units_follow_the_rules():
    from bench import spec
    bench = spec.load_benchmark()
    for entry in (bench["configs"] + bench["workloads"]
                  + bench["end_to_end"] + bench["per_layer"]):
        spec.check_name(entry["name"])
        if "unit" in entry:
            spec.check_unit(entry["unit"])


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric
    added as new files and BENCHMARK.json entries, with no code edited,
    load and read."""
    from bench import spec
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = spec.load_benchmark()
    cfg = json.loads((ROOT / "bench/configs/gpt2.json").read_text())
    cfg["n_layer"] = 6
    (tmp_path / "bench/configs/gpt2-half.json").write_text(json.dumps(cfg))
    tr = json.loads((ROOT / "bench/traffic/fedllm-paper.json").read_text())
    tr["fed"]["n_clients"] = 5
    (tmp_path / "bench/traffic/fedllm-five.json").write_text(json.dumps(tr))
    shutil.copy(ROOT / "bench/limits/gpt2-fedllm.json",
                tmp_path / "bench/limits/gpt2h-five.json")
    (tmp_path / "bench/metrics/rounds_seen.py").write_text(
        "def read(run):\n    return float(run.rounds)\n")
    bench["configs"].append({"name": "gpt2-half", "source": "x",
                             "file": "bench/configs/gpt2-half.json",
                             "reduced": ["n_layer"], "why": "x"})
    bench["workloads"].append({"name": "gpt2h-five", "config": "gpt2-half",
                               "traffic": "fedllm-five", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "rounds_seen", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "round driver", "moves": "round_s",
                               "workloads": ["gpt2h-five"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("gpt2h-five", tmp_path)
    assert cell.config["n_layer"] == 6
    assert cell.traffic["fed"]["n_clients"] == 5
    assert "rounds_seen" in [m.name for m in cell.per_layer]
    assert "rounds_seen" not in [
        m.name for m in spec.load_cell("gpt2-fedllm", tmp_path).per_layer]

    class Run:
        rounds = 4
    assert spec.metric_reader("rounds_seen", tmp_path)(Run) == 4.0


def test_unknown_device_kind_has_no_peaks():
    from bench import spec
    assert spec.read_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.read_peaks("TPU v99")


def test_traffic_must_evaluate_whole_batches(tmp_path):
    from bench import spec
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    tr = json.loads((ROOT / "bench/traffic/fedllm-paper.json").read_text())
    tr["data"]["n_test"] = 6161
    (tmp_path / "bench/traffic/odd.json").write_text(json.dumps(tr))
    with pytest.raises(ValueError):
        spec.read_traffic("odd", tmp_path)


def _run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2-fedllm",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = _run_bench(ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _run_bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


def test_a_framework_without_a_file_is_refused():
    """A traffic mix whose framework has no bench/frameworks/<name>.py
    is refused by name, not run against another framework's check."""
    from bench import spec
    assert callable(spec.framework("fedllm").reference_round)
    with pytest.raises(FileNotFoundError, match="kd"):
        spec.framework("kd")
