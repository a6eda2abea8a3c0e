"""tools/scope_table.py on a hand-made trace: device seconds by program
scope and instruction family from the ``tf_op`` stat of the event metadata,
the program's rows against their annotations, and the decode program's runs
against the spans that enqueue and wait for them."""
import pytest

from benchmark.tools import scope_table
from chainermn_tpu.tracing import Row

MS = 1_000_000_000      # picoseconds in a millisecond

# device: fusion.1 (optimizer_update) 2 ms, fusion.2 (optimizer_update/
# grad_reduce) 1 ms, all-reduce.1 (grad_reduce) 3 ms, copy.7 (no tf_op) 1 ms,
# multiply_reduce_fusion.3 (attend_cache) 4 ms half outside the window, a
# `while` container that must not count; modules: two runs of jit__decode_k.
TRACE = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events {{ metadata_id: 1 offset_ps: {10 * MS} duration_ps: {2 * MS} }}
    events {{ metadata_id: 2 offset_ps: {12 * MS} duration_ps: {1 * MS} }}
    events {{ metadata_id: 3 offset_ps: {13 * MS} duration_ps: {3 * MS} }}
    events {{ metadata_id: 4 offset_ps: {16 * MS} duration_ps: {1 * MS} }}
    events {{ metadata_id: 5 offset_ps: {98 * MS} duration_ps: {4 * MS} }}
    events {{ metadata_id: -6 offset_ps: {10 * MS} duration_ps: {50 * MS} }}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events {{ metadata_id: 7 offset_ps: {22 * MS} duration_ps: {10 * MS} }}
    events {{ metadata_id: 7 offset_ps: {60 * MS} duration_ps: {30 * MS} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8]{{0}} fusion(f32[8] %state_1__0__mu__w), kind=kLoop" stats {{ metadata_id: 1 str_value: "jit(local_step)/jvp(M)/optimizer_update/mul:" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.2 = f32[8]{{0}} fusion(f32[8] %g, f32[8] %x), kind=kOutput" stats {{ metadata_id: 1 ref_value: 2 }} }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%all-reduce.1 = f32[8]{{0}} all-reduce(f32[8] %g)" stats {{ metadata_id: 1 str_value: "jit(local_step)/optimizer_update/grad_reduce/psum:" }} }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%copy.7 = f32[8]{{0}} copy(f32[8] %x)" stats {{ metadata_id: 3 int64_value: 64 }} }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%multiply_reduce_fusion.3 = f32[8]{{0}} fusion(f32[8] %k)" stats {{ metadata_id: 1 str_value: "jit(_decode_k)/while/body/block_0/attend_cache/dot_general:" }} }} }}
  event_metadata {{ key: -6 value {{ id: -6 name: "%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "jit__decode_k(123)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "jit(local_step)/optimizer_update/grad_reduce/div:" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "bytes_accessed" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {100 * MS} }}
    events {{ metadata_id: 2 offset_ps: {20 * MS} duration_ps: {3 * MS} }}
    events {{ metadata_id: 3 offset_ps: {23 * MS} duration_ps: {10 * MS} }}
    events {{ metadata_id: 2 offset_ps: {50 * MS} duration_ps: {3 * MS} }}
    events {{ metadata_id: 3 offset_ps: {53 * MS} duration_ps: {20 * MS} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.trace_window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "engine.decode.enqueue" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "engine.decode.wait" }} }}
}}
"""


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("scope") / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    return str(path)


def program_rows(offset_s, skew_ms=0.0):
    """The rows the program would hold for the host plane's spans, on a
    clock ``offset_s`` behind the trace's; the second wait is ``skew_ms``
    late."""
    at = lambda ms: 0.001 + ms / 1e3 - offset_s     # noqa: E731
    return [Row(1, None, "engine.decode.enqueue", at(20), at(23), {}),
            Row(2, None, "engine.decode.wait", at(23), at(33), {}),
            Row(3, None, "engine.decode.enqueue", at(50), at(53), {}),
            Row(4, None, "engine.decode.wait", at(53 + skew_ms),
                at(73 + skew_ms), {})]


def test_wire_decoding_agrees_with_profiledata(xplane):
    from jax.profiler import ProfileData

    planes = scope_table.read_space(xplane)
    ref = ProfileData.from_file(xplane)
    assert [p["name"] for p in planes] == [p.name for p in ref.planes]
    for mine, theirs in zip(planes, ref.planes):
        for ml, tl in zip(mine["lines"], theirs.lines):
            assert ml["name"] == tl.name
            got = [(mine["events"][mid]["name"], s, e)
                   for mid, s, e in ml["events"]]
            want = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in tl.events]
            assert [g[0] for g in got] == [w[0] for w in want]
            for g, w in zip(got, want):
                assert g[1:] == pytest.approx(w[1:])


def test_device_seconds_by_scope_and_family(xplane):
    out = scope_table.analyse(xplane, operand="state_1__", n_devices=1)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["by_family_kind_s"]["fusion"] == pytest.approx({
        "kLoop": 0.002, "kOutput": 0.001})
    assert out["by_family_kind_s"]["copy"] == pytest.approx({"-": 0.001})
    assert out["reads_operand_s"] == pytest.approx({"fusion": 0.002})
    assert out["device_ops"] == 5 and out["ops_with_tf_op"] == 4
    assert out["metadata_stats_seen"] == ["bytes_accessed", "tf_op"]
    assert out["by_scope_s"] == pytest.approx({
        "optimizer_update/grad_reduce": 0.004, "optimizer_update": 0.002,
        "attend_cache": 0.002, "(none)": 0.001})
    assert out["by_family_s"]["fusion"] == pytest.approx({
        "optimizer_update": 0.002, "optimizer_update/grad_reduce": 0.001})
    assert out["by_family_s"]["collective"] == pytest.approx({
        "optimizer_update/grad_reduce": 0.003})
    assert out["by_family_s"]["multiply_reduce_fusion"] == pytest.approx({
        "attend_cache": 0.002})         # the half inside the window
    assert "while" not in out["by_family_s"]
    assert "clocks" not in out          # no rows given: nothing to compare


@pytest.mark.parametrize("skew_ms", [0.0, 0.4])
def test_rows_against_annotations_and_module_runs(xplane, skew_ms):
    offset_s = 1234.5
    out = scope_table.analyse(
        xplane, program_rows(offset_s, skew_ms), (0.001 - offset_s, 0.0),
        module="jit__decode_k", enqueue="engine.decode.enqueue",
        wait="engine.decode.wait", n_devices=1)
    clocks = out["clocks"]
    assert clocks["clock_offset_ns"] == pytest.approx(offset_s * 1e9)
    assert clocks["spans_matched"] == 4
    assert clocks["rows_vs_annotations_unmatched"] == {}
    assert clocks["largest_start_difference_ms"] == pytest.approx(
        skew_ms, abs=1e-3)
    assert clocks["largest_duration_difference_ms"] == pytest.approx(
        0.0, abs=1e-3)
    # the first run (22-32 ms) lies between its enqueue's start (20) and its
    # wait's end (33); the second (60-90) outlasts its wait's end (73)
    assert clocks["module_runs_in_window"] == 2
    assert clocks["module_runs_between_enqueue_and_wait_end"] == 1


def test_a_row_with_no_annotation_is_reported_not_matched(xplane):
    rows = program_rows(0.0) + [Row(9, None, "engine.emit", 0.08, 0.081, {})]
    out = scope_table.analyse(xplane, rows, (0.001, 0.101), n_devices=1)
    assert out["clocks"]["rows_vs_annotations_unmatched"] == {
        "engine.emit": [1, 0]}
    assert out["clocks"]["spans_matched"] == 4


@pytest.mark.parametrize("op_name,want", [
    ("jit(local_step)/jvp(M)/optimizer_update/mul:", "optimizer_update"),
    ("jit(s)/optimizer_update/grad_reduce/psum:",
     "optimizer_update/grad_reduce"),
    ("jit(_decode_k)/while/body/block_3/attend_cache/dot_general:",
     "attend_cache"),
    ("jit(_decode_k)/while/body/sample/sort:", "sample"),
    ("jit(local_step)/transpose(jvp(M))/block_0/mlp_out/dot_general:",
     "(none)"),
    (None, "(none)")])
def test_scope_of_an_operation_name(op_name, want):
    assert scope_table.scope_of(op_name) == want


def test_the_run_reads_the_trace_before_the_reducer_deletes_it(
        xplane, tmp_path, monkeypatch, capsys):
    import json
    import os
    import shutil

    from benchmark.harness import registry
    from chainermn_tpu import tracing

    from . import toy

    bench, cell, workload, config = toy.toy_cell("sc2-3b-serve-batchgen")
    monkeypatch.setattr(scope_table.ScopeRun, "dest", str(tmp_path / "out"))
    monkeypatch.setattr(scope_table.ScopeRun, "tool", dict(
        module="jit__decode_k", enqueue="engine.decode.enqueue",
        wait="engine.decode.wait"))
    run = scope_table.ScopeRun(
        t_process=0.0, args=toy.toy_args(trace=1), cell=cell,
        workload=workload, config=config,
        peaks=registry.load_peaks("TPU v5 lite"), devices=[object()],
        scratch=str(tmp_path / "scratch"))
    where = os.path.join(run._trace_dir, "plugins", "profile", "t")
    os.makedirs(where)
    shutil.copy(xplane, os.path.join(where, "hand.xplane.pb"))
    run.spans.rows.append(("bench.trace_window", 0.001, 0.101))
    tracing.clear()
    tracing._rows.extend(program_rows(0.0))
    try:
        reduced = run.reduce_trace()
    finally:
        tracing.clear()
    assert reduced["busy_s"] > 0 and not os.path.exists(run._trace_dir)
    out = json.load(open(tmp_path / "out" / "sc2-3b-serve-batchgen.json"))
    assert out["clocks"]["spans_matched"] == 4
    assert out["by_scope_s"]["attend_cache"] == pytest.approx(0.002)
    assert os.path.exists(tmp_path / "out" / "sc2-3b-serve-batchgen.xplane.pb")
    said = capsys.readouterr().out
    assert "scope_table family fusion" in said and "scope_table clocks" in said
