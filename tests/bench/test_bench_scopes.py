"""Device time by kernel and layer scope, idle time by the program's
spans (bench/scopes.py), on synthetic event lists; the metrics that read
them; and bench/trace_program.py's window on the CPU at a toy size."""
import time

import jax
import pytest

from conftest import tiny_cell

S = 1_000_000_000      # ns per second
FWD = "jit(local_update)/while/body/attention/jit(lora_matmul)/pallas_call"
HEAD_BWD = "jit(local_update)/while/body/transpose(jvp(lm_head))/dot_general"


def _device(*ops):
    return {"/device:TPU:0": list(ops)}


def test_a_loop_counts_only_the_kernels_inside_it():
    from bench import scopes
    # a while over 1-9 s holding two kernels; a fusion after it
    out = scopes.reduce_events(_device(
        (1 * S, 9 * S, "while.241", "jit(local_update)/while"),
        (2 * S, 4 * S, "lora_matmul_fwd.3", FWD),
        (5 * S, 8 * S, "flash_attention_fwd.31", FWD),
        (9 * S, 10 * S, "fusion.2", HEAD_BWD)),
        [(0, 10 * S, "bench.window")])
    assert out["kernels"] == {"flash_attention_fwd": pytest.approx(3.0),
                              "lora_matmul_fwd": pytest.approx(2.0),
                              "fusion": pytest.approx(1.0)}
    assert "while" not in out["kernels"]
    assert out["leaf_s"] == pytest.approx(6.0)


def test_nested_loops_and_equal_intervals():
    from bench import scopes
    ops = [(0, 10, "while.1", ""), (0, 10, "while.2", ""),
           (1, 4, "call.3", ""), (1, 2, "a.4", ""), (3, 4, "b.5", ""),
           (6, 8, "c.6", "")]
    assert [n for _, _, n, _ in scopes.leaf_ops(ops)] == ["a.4", "b.5",
                                                          "c.6"]
    # an op that starts inside another and ends after it holds nothing
    assert len(scopes.leaf_ops([(0, 5, "x", ""), (3, 8, "y", "")])) == 2


def test_kernels_are_grouped_by_name_prefix():
    from bench import scopes, spec
    red = scopes.reduce_events(_device(
        (0, 2 * S, "lora_matmul_fwd.1", FWD),
        (2 * S, 3 * S, "lora_matmul_dx.7", FWD),
        (3 * S, 4 * S, "lora_matmul_panel_grad.2", FWD),
        (4 * S, 8 * S, "flash_attention_dkv.5", FWD),
        (8 * S, 10 * S, "multiply_reduce_fusion.22", HEAD_BWD)),
        [(0, 10 * S, "bench.window")])
    run = _run(scopes=red, rounds=2)
    assert spec.metric_reader("kernel_s.lora_matmul")(run) == \
        pytest.approx(4.0 / 2)
    assert spec.metric_reader("kernel_s.flash_attention")(run) == \
        pytest.approx(4.0 / 2)
    assert spec.metric_reader("device_s.lm_head")(run) == \
        pytest.approx(2.0 / 2)
    no_kernels = scopes.reduce_events(_device((0, S, "fusion.1", "")), [])
    assert spec.metric_reader("kernel_s.lora_matmul")(
        _run(scopes=no_kernels)) is None


@pytest.mark.parametrize("path,scope", [
    (FWD, "attention"),
    (HEAD_BWD, "lm_head"),
    ("jit(eval_step)/lm_head/lm_head/slice", "lm_head"),
    ("jit(local_update)/while/body/embed/gather", "embed"),
    ("jit(u)/while/body/transpose(jvp(mlp))/dot_general", "mlp"),
    ("jit(u)/while/body/attention/mlp_like_name/add", "attention"),
    ("jit(_fold_add)/add", "other"),
    ("", "other")])
def test_scope_is_the_innermost_layer_name(path, scope):
    from bench import scopes
    assert scopes.scope_of(path) == scope


def test_idle_goes_to_the_innermost_program_span():
    from bench import scopes
    # device busy 0-2 and 6-7; fl.round 0-10 holds fl.evaluate 4-10,
    # which holds fl.eval.load 4-5 and fl.eval.read 8-9
    host = [(0, 10 * S, "bench.window"), (0, 10 * S, "fl.round"),
            (4 * S, 10 * S, "fl.evaluate"), (4 * S, 5 * S, "fl.eval.load"),
            (8 * S, 9 * S, "fl.eval.read"), (1 * S, 3 * S, "stage.other")]
    out = scopes.reduce_events(_device((0, 2 * S, "fusion.1", ""),
                                       (6 * S, 7 * S, "fusion.2", "")),
                               host)
    assert out["idle_innermost"] == {"fl.round": pytest.approx(2.0),
                                     "fl.evaluate": pytest.approx(3.0),
                                     "fl.eval.load": pytest.approx(1.0),
                                     "fl.eval.read": pytest.approx(1.0)}
    assert out["idle_within"]["fl.evaluate"] == pytest.approx(5.0)
    assert out["idle_within"]["fl.round"] == pytest.approx(7.0)
    from bench import spec
    assert spec.metric_reader("idle_s.evaluate")(
        _run(scopes=out, rounds=2)) == pytest.approx(2.5)


def test_existing_reduction_is_unchanged_by_program_spans():
    from bench import trace
    devices = {"/device:TPU:0": [(1 * S, 3 * S, "fusion"),
                                 (6 * S, 7 * S, "conv")]}
    host = [(0, 10 * S, "bench.window"), (0, 5 * S, "stage.local_update"),
            (5 * S, 10 * S, "stage.evaluate")]
    program = [(0, 10 * S, "fl.round"), (5 * S, 10 * S, "fl.evaluate"),
               (5 * S, 6 * S, "fl.eval.load")]
    assert trace.reduce_events(devices, host + program) == \
        trace.reduce_events(devices, host)


def test_program_counters_give_syncs_and_padding():
    from bench import spec
    run = _run(program={"spans": {"fl.evaluate": [2, 7.0]},
                        "counters": {"host_syncs": 1576,
                                     "steps_stacked": 40,
                                     "steps_valid": 30}}, rounds=2)
    assert spec.metric_reader("host_syncs")(run) == 788
    assert spec.metric_reader("pad_step_share")(run) == pytest.approx(25.0)
    assert spec.metric_reader("stage_s.evaluate")(run) == 3.5
    for name in ("host_syncs", "pad_step_share", "stage_s.evaluate",
                 "idle_s.evaluate", "kernel_s.lora_matmul",
                 "kernel_s.flash_attention", "device_s.lm_head"):
        # a record without the program's spans (bench/run.py's) reads none
        assert spec.metric_reader(name)(_run(program=None)) is None, name


def _run(program=None, scopes=None, rounds=1):
    from bench.trace_program import ProgramRun
    return ProgramRun(rounds=rounds, round_s=1.0, chips=1, spans={},
                      compile_s=0.0, compile_misses=0, flops=[],
                      peak_flops=1.0, traced=True, program=program,
                      scopes=scopes)


def test_program_spans_cover_the_window_rounds(monkeypatch):
    """bench/trace_program.py on the CPU: the program's counters hold the
    window's rounds only (the trace reductions, which need a TPU, are
    stubbed, and the CPU is given a chip's peak)."""
    from bench import scopes, spec, trace, trace_program
    monkeypatch.setattr(spec, "read_peaks",
                        lambda kind: {"bf16_flops_per_s": 197e12})
    monkeypatch.setattr(trace, "reduce_trace", lambda d: {
        "busy_s": 0.0, "window_s": 1.0, "device_ops": [], "idle_gaps": [],
        "n_devices": 1})
    monkeypatch.setattr(scopes, "reduce_trace", lambda d, h: (
        scopes.reduce_events(_device((0, S, "fusion.1", "")), [])))
    cell = tiny_cell()
    res = trace_program.traced_run(cell, 2 ** 31 + 99, 0.0,
                                   jax.devices()[:1], time.perf_counter(),
                                   log=lambda s: None)
    run = res["run"]
    t = cell.traffic
    n_clients = t["fed"]["n_clients"]
    steps = t["data"]["n_train"] // n_clients // t["batch_size"]
    leaves = 6          # LoRA a and b on wq, wk, wv
    assert run.rounds == 1
    assert run.program["counters"]["host_syncs"] == \
        2 * t["data"]["n_test"] // t["eval_batch"] + n_clients * leaves
    assert run.program["counters"]["steps_stacked"] == n_clients * steps
    assert run.program["spans"]["fl.evaluate"][0] == 1
    out = trace_program.result(cell, res, jax.devices()[:1])
    assert out["metrics"]["pad_step_share"]["value"] == 0.0
    assert out["metrics"]["host_syncs"]["unit"] == "count"


def test_op_paths_come_from_the_dumped_hlo_by_module(tmp_path):
    """The op paths of a compiled program's HLO text, found by module
    and instruction name; an instruction name of another module (the
    same name in two programs) reads that module's path."""
    from bench import scopes

    def program(x, w):
        with jax.named_scope("attention"):
            h = jax.numpy.tanh(x @ w)
        with jax.named_scope("lm_head"):
            return jax.numpy.sum(h @ w.T)
    sds = jax.ShapeDtypeStruct((8, 16), jax.numpy.float32)
    text = jax.jit(program).lower(sds, jax.ShapeDtypeStruct(
        (16, 16), jax.numpy.float32)).compile().as_text()
    (tmp_path / "module_0001.jit_program.cpu_after_optimizations.txt"
     ).write_text(text)
    (tmp_path / "module_0002.jit_other.cpu_after_optimizations.txt"
     ).write_text('HloModule jit_other, is_scheduled=true\n\nENTRY %main '
                  '{\n  ROOT %dot.1 = f32[2]{0} dot(), metadata={op_name='
                  '"jit(other)/embed/dot_general"}\n}\n')
    paths = scopes.hlo_op_paths(str(tmp_path))
    assert set(paths) == {"jit_program", "jit_other"}
    found = {scopes.scope_of(p) for p in paths["jit_program"].values()}
    assert {"attention", "lm_head"} <= found
    instr = next(k for k, p in paths["jit_program"].items()
                 if scopes.scope_of(p) == "lm_head")
    ops = [(1, 2, instr), (11, 12, instr), (21, 22, "dot.1"),
           (31, 32, "dot.1")]
    modules = [(0, 10, "jit_program"), (10, 20, "jit_other"),
               (20, 30, "jit_other")]
    got = [scopes.scope_of(p) for *_, p in
           scopes.op_paths(ops, modules, paths)]
    # the last op lies in no module event
    assert got == ["lm_head", "other", "embed", "other"]
    assert scopes.module_name("jit_program(12221243617530893432)") == \
        "jit_program"
