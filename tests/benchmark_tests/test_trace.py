"""The trace reduction: on a trimmed copy of a trace recorded on the v5e
(benchmark/fixtures/, PR 23) and on hand-made planes."""
import os

import pytest

from benchmark.harness import trace

FIXTURE = os.path.join(os.path.dirname(trace.__file__), "..", "fixtures",
                       "v5e_train_serve_probe.xspace.txt")
OPS, ASYNC, MODS = trace.OP_LINE, trace.ASYNC_LINE, trace.MODULE_LINE
MS = 1e6


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("trace") / "probe.xplane.pb"
    path.write_bytes(blob)
    return trace.read_planes(str(path))


def test_recorded_trace_planes_and_lines_are_picked_by_rule(recorded):
    assert sorted(recorded["devices"]) == [0]
    assert set(recorded["devices"][0]) == {OPS, ASYNC, MODS}
    assert [h[0] for h in recorded["host"]] == [trace.WINDOW_ANNOTATION]


def test_recorded_trace_reduces_to_sane_numbers(recorded):
    spans = [(trace.WINDOW_ANNOTATION, 10.0, 10.0882),
             ("engine.step", 10.0740, 10.0785)]
    r = trace.reduce_planes(recorded, host_spans=spans)
    assert r["window_s"] == pytest.approx(0.0882, rel=1e-6)
    assert 0 < r["busy_s"] <= r["window_s"]
    # three 2.9 ms train steps, two prefills, two decode dispatches of 1 ms
    assert len(r["module_runs_s"]["jit_local_step"]) == 3
    assert r["module_runs_s"]["jit_local_step"][0] == pytest.approx(
        2.9e-3, rel=0.01)
    assert len(r["module_runs_s"]["jit__decode_k"]) == 2
    assert len(r["module_runs_s"]["jit__pf"]) == 2
    assert len(r["module_gaps_s"]["jit_local_step"]) == 2
    assert max(r["module_gaps_s"]["jit_local_step"]) < 50e-6
    # the modules' time bounds the operations' union from above
    modules = sum(sum(v) for v in r["module_runs_s"].values())
    assert r["busy_s"] <= modules * 1.02
    names = [n for n, _ in r["device_ops"]]
    assert "while" not in names and len(names) <= 10
    assert any("flash" in k for k in r["op_family_s"])
    assert any("fused_ce_fwd" in k for k in r["op_family_s"])
    # the idle time is attributed and adds up to window - busy
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-3)
    assert r["span_uncovered_s"]["engine.step"][0] > 0


def planes(devices, host=()):
    return {"devices": devices, "host": list(host)}


def test_no_device_events_raises():
    with pytest.raises(trace.TraceError):
        trace.reduce_planes(planes({}))
    with pytest.raises(trace.TraceError):
        trace.reduce_planes(planes({0: {OPS: [], MODS: []}}))


def test_overlapping_lines_and_nested_ops_do_not_exceed_the_window():
    dev = {OPS: [("%while.1 = (s32[]) while(x)", 0, 10 * MS),
                 ("%fusion.1 = f32[] fusion(x)", 1 * MS, 4 * MS),
                 ("%fusion.2 = f32[] fusion(x)", 3 * MS, 6 * MS)],
           MODS: [("jit_f(1)", 0, 10 * MS)],
           ASYNC: [("%copy-start.1 = f32[] copy-start(x)", 0, 10 * MS)]}
    r = trace.reduce_planes(planes({0: dev}))
    assert r["busy_s"] == pytest.approx(r["window_s"]) == pytest.approx(0.01)
    # the container is not a family of its own; the fusions' time is summed
    assert r["op_family_s"] == {"fusion": pytest.approx(0.006)}
    assert r["op_family_calls"] == {"fusion": 2}


def test_busy_is_the_mean_of_four_devices_not_their_sum():
    devs = {}
    for d, busy_ms in enumerate([2, 4, 6, 8]):
        devs[d] = {OPS: [("%a.1 = f32[] fusion(x)", 0, busy_ms * MS),
                         ("%b.1 = f32[] fusion(x)", 9 * MS, 10 * MS)],
                   MODS: []}
    r = trace.reduce_planes(planes(devs))
    assert r["n_devices"] == 4
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx((3 + 5 + 7 + 9) / 4 * 1e-3)
    assert trace.reduce_planes(planes(devs), n_devices=1)["busy_s"] == (
        pytest.approx(3e-3))


def test_exposed_collective_is_the_part_no_compute_covers():
    dev = {OPS: [("%fusion.1 = f32[] fusion(x)", 0, 5 * MS),
                 ("%all-reduce-done.1 = f32[] all-reduce-done(x)",
                  4 * MS, 8 * MS),
                 ("%fusion.2 = f32[] fusion(x)", 8 * MS, 10 * MS)],
           ASYNC: [("%all-reduce-start.1 = f32[] all-reduce-start(x)",
                    2 * MS, 8 * MS)], MODS: []}
    r = trace.reduce_planes(planes({0: dev}))
    # collective in flight 2..8 ms, compute covers 2..5: 3 ms exposed
    assert r["collective_exposed_s"] == pytest.approx(3e-3)
    assert r["busy_s"] == pytest.approx(10e-3)


def test_window_and_idle_gaps_follow_the_benchmarks_annotation():
    dev = {OPS: [("%fusion.1 = f32[] fusion(x)", 102 * MS, 104 * MS),
                 ("%fusion.2 = f32[] fusion(x)", 107 * MS, 109 * MS)],
           MODS: [("jit_step(9)", 102 * MS, 104 * MS),
                  ("jit_step(9)", 107 * MS, 109 * MS)]}
    host = [(trace.WINDOW_ANNOTATION, 100 * MS, 110 * MS)]
    spans = [(trace.WINDOW_ANNOTATION, 5.000, 5.010),
             ("engine.step", 5.0045, 5.0095), ("submit", 5.0046, 5.0050)]
    r = trace.reduce_planes(planes({0: dev}, host), host_spans=spans)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.004)
    assert r["module_gaps_s"] == {"jit_step": [pytest.approx(3e-3)]}
    gaps = dict(r["idle_gaps"])
    assert gaps["engine.step"] == pytest.approx(4e-3)   # 104..107, 109..110
    assert gaps["(no span)"] == pytest.approx(2e-3)     # 100..102
    # engine.step lasted 5 ms, 2 ms of it (107..109) under device work
    assert r["span_uncovered_s"]["engine.step"] == [pytest.approx(3e-3)]


@pytest.mark.parametrize("name,fam", [
    ("%fusion.123 = f32[8]{0} fusion(%p)", "fusion"),
    ("%transpose_jvp_fused_ce_dw__.1 = bf16[512,8192] custom-call(x)",
     "transpose_jvp_fused_ce_dw"),
    ("%flash_bwd_fused.4 = (bf16[32,512,64]) custom-call(x)",
     "flash_bwd_fused"),
    ("%copy-done.11 = bf16[2] copy-done(x)", "copy-done"),
])
def test_instruction_families(name, fam):
    assert trace.family(name) == fam


def test_interval_helpers():
    merged = trace.union([(0, 2), (1, 3), (5, 6)])
    assert merged == [[0, 3], [5, 6]] and trace.total(merged) == 4
    assert trace.overlap(merged, [[2, 5.5]]) == pytest.approx(1.5)
    assert trace.gaps(merged, 0, 8) == [(3, 5), (6, 8)]
