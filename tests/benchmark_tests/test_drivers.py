"""Every cell's driver end to end at toy widths on the CPU mesh, through the
same ``run.measure`` a chip run goes through after its look for a chip: with
``--trace 0``, with the recorded fixture standing in for the trace, with the
timed path broken underneath (``correct`` must come out false), and with the
reference computed in the next lower precision in the program's place (the
control must fail a limit that the sound program passes).

No number a CPU run gives is a device metric: these tests look at ``correct``,
at the line's shape and at counts only.
"""
import json
import os

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import line as line_mod
from benchmark.harness import registry, runtime, trace
from benchmark.references import dense_decoder as ref

from . import toy

CELLS = ["gpt2m-train-dp1", "sc2-3b-serve-batchgen", "gpt2m-train-dp4"]
FIXTURE = os.path.join(registry.BENCH_DIR, "fixtures",
                       "v5e_train_serve_probe.xspace.txt")


@pytest.fixture(autouse=True)
def cpu_has_no_memory_counter(monkeypatch):
    monkeypatch.setattr(runtime, "memory_peak_bytes", lambda devices: 1 << 20)


def measure(name, trace_flag=0, seed=2 ** 31 + 3, workload_edit=None):
    import jax

    bench, cell, workload, config = toy.toy_cell(name)
    if workload_edit:
        workload_edit(workload)
    code, text = bench_run.measure(
        toy.toy_args(seed=seed, seconds=1.0, trace=trace_flag), bench, cell,
        workload, config, jax.devices()[:cell["chips"]],
        registry.load_peaks("TPU v5 lite"))
    return code, (json.loads(text) if text else None), bench


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_untraced(cell):
    code, line, bench = measure(cell)
    assert code == 0 and line["correct"] is True
    declared = line_mod.declared(bench, cell, 0)
    line_mod.check(line, declared, False)
    assert set(line["metrics"]) == {m["name"] for m in declared}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["count"] == (4 if cell.endswith("dp4") else 1)


@pytest.fixture
def fixture_for_trace(monkeypatch, tmp_path):
    """The profiler stays off; the recorded fixture is reduced in its place,
    against the run's own host spans."""
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path / "fixture.xplane.pb"
    path.write_bytes(blob)
    import time

    def start(self):
        self._trace_t0 = time.perf_counter()

    def stop(self):
        self.spans.rows.append((trace.WINDOW_ANNOTATION, self._trace_t0,
                                time.perf_counter()))

    def reduce(self):
        self.trace = trace.reduce_file(str(path), host_spans=self.spans.rows,
                                       n_devices=1)
        return self.trace

    monkeypatch.setattr(runtime.Run, "trace_start", start)
    monkeypatch.setattr(runtime.Run, "trace_stop", stop)
    monkeypatch.setattr(runtime.Run, "reduce_trace", reduce)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_with_the_fixture_as_its_trace(
        cell, fixture_for_trace):
    code, line, bench = measure(cell, trace_flag=1)
    assert code == 0, "no line was built"
    declared = line_mod.declared(bench, cell, 1)
    line_mod.check(line, declared, True)
    assert set(line["metrics"]) == {m["name"] for m in declared}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"] and len(
        line["breakdown"]["device_ops"]) <= 10


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.training import step as step_mod

    real_factory = step_mod.make_data_parallel_train_step

    def broken_factory(*a, **kw):
        real = real_factory(*a, **kw)

        def step(state, *batch):
            keep = jax.tree_util.tree_map(jnp.copy, state)
            _, metrics = real(state, *batch)
            return keep, metrics

        step._cache_size = real._cache_size
        return step

    monkeypatch.setattr(step_mod, "make_data_parallel_train_step",
                        broken_factory)
    code, line, _ = measure("gpt2m-train-dp1")
    assert code == 0 and line["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from chainermn_tpu.serving import engine as engine_mod

    real_emit = engine_mod.Engine._emit

    def emit(self, req, token):
        return real_emit(self, req, (int(token) + 7) % 512)

    monkeypatch.setattr(engine_mod.Engine, "_emit", emit)
    code, line, _ = measure("sc2-3b-serve-batchgen")
    assert code == 0 and line["correct"] is False


def test_a_refused_request_counts_as_failed():
    def too_long(workload):
        workload["traffic"]["prompt_len"].update(max=200, median=60)

    code, line, _ = measure("sc2-3b-serve-batchgen", workload_edit=too_long)
    # prompts over the largest bucket (128) are refused at submit
    assert code == 0 and line["failed"] > 0
    assert line["failed"] <= line["attempted"]


def _toy_run(name, seed):
    import jax

    bench, cell, workload, config = toy.toy_cell(name)
    return runtime.Run(
        t_process=0.0, args=toy.toy_args(seed=seed), cell=cell,
        workload=workload, config=config,
        peaks=registry.load_peaks("TPU v5 lite"),
        devices=jax.devices()[:cell["chips"]],
        scratch=os.path.join(registry.ROOT, ".bench_scratch"))


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9, 77])
def test_training_control_in_fp8_fails_a_limit_the_program_passes(seed):
    """The control: the reference with every matrix multiplication's operands
    rounded to fp8's 4 significant bits, put in the program's place."""
    drv = registry.load_module("drivers", "train_dp")
    run = _toy_run("gpt2m-train-dp1", seed)
    limits = run.workload["check"]["limits"]
    b = drv.build(run)
    want = drv.reference_readings(run, b)
    control = drv.compare(drv.reference_readings(run, b, ref.fake_fp8), want)
    _, got = drv.program_readings(run, b, 3)
    sound = drv.compare(got, want)
    print("sound", sound, "control", control)
    assert all(sound[k] <= limits[k] for k in limits), sound
    assert any(control[k] > limits[k] for k in limits), control
    assert control["grad_norm_gap"] > 3 * sound["grad_norm_gap"]


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9, 77])
def test_serving_control_in_fp8_fails_the_limit_the_program_passes(seed):
    drv = registry.load_module("drivers", "serve_closed")
    run = _toy_run("sc2-3b-serve-batchgen", seed)
    limit = run.workload["check"]["limits"]["served_logit_gap"]
    engine, spec = drv.build_engine(run)
    loop = drv.ClosedLoop(engine, drv.Traffic(
        seed, run.workload["traffic"], run.config["as_run"]["vocab"]),
        run.spans)
    drv.warm_up(run, engine, loop)
    win = drv.window(run, loop)
    sample = drv.pick_sample(seed, win["completed"], 3)
    gaps = drv.reference_gaps(run, spec, sample, quant=ref.fake_fp8)
    print(gaps)
    assert gaps["served_gap"] <= limit < gaps["control_gap"], gaps
    assert gaps["control_gap"] > 3 * gaps["served_gap"]


def test_fake_fp8_keeps_four_significant_bits():
    import jax.numpy as jnp

    x = jnp.asarray([1.0, 1.03, 1.07, 3.3, -0.3, 1e-3, 0.0])
    q = np.asarray(ref.fake_fp8(x))
    assert q.tolist() == pytest.approx([1.0, 1.0, 1.125, 3.25, -0.3125,
                                        0.0009765625, 0.0])
