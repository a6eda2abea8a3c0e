"""The readers of set-up (``harness/startup.py`` and the six ``setup_*``
metrics) on a hand-made compile log and lifecycle rows: what ended inside a
``setup.*`` span counts, the reference between the two spans and everything
after the window do not, a compile inside the window is flagged on the
``compile_table`` line, and a program from before the compile log reads a
stated value, said on that line."""
import collections
import json

import pytest

from benchmark.harness import line as line_mod
from benchmark.harness import registry, runtime, startup
from chainermn_tpu import tracing
from chainermn_tpu.tracing import CompileRow, Row

METRICS = ["setup_programs_lowered", "setup_cache_misses",
           "setup_trace_lower_s", "setup_backend_compile_s",
           "setup_first_run_s", "setup_build_s"]
WINDOW_S = 30.0


def spans():
    """setup.build 10-30, the reference 30-50 (no span: its time is taken
    off ``setup_s``), setup.warm_up_and_ramp 50-80, then the window's
    iterations from 80.5 on."""
    sp = runtime.Spans()
    sp.rows += [("setup.build", 10.0, 30.0),
                ("engine.step", 51.0, 60.0),
                ("setup.warm_up_and_ramp", 50.0, 80.0),
                ("engine.step", 80.5, 81.0), ("engine.step", 81.0, 82.0),
                (runtime.trace_mod.WINDOW_ANNOTATION, 89.5, 91.5)]
    return sp


def compile_rows():
    def c(name, t_end, trace, lower, backend, cache="hit", retrieval=0.0):
        return CompileRow(f"jit({name})", t_end, trace, lower, backend,
                          cache, retrieval)

    return [
        c("import_time", 5.0, 0.1, 0.1, 0.1, "none"),        # before set-up
        c("make_tree", 12.0, 0.5, 0.25, 1.0, "hit", 0.5),    # the benchmark's
        c("broadcast_in_dim", 21.0, 0.0, 0.125, 0.375, "none"),  # in the build
        c("reference_layer", 40.0, 1.0, 1.0, 8.0, "miss"),   # the reference
        c("_pf", 55.0, 2.0, 1.0, 0.5, "hit", 0.25),
        c("convert_element_type", 55.5, 0.0, 0.0, 0.25, "none"),
        c("_decode_k", 63.0, 1.5, 0.5, 4.0, "miss"),
        c("_pf", 95.0, 2.0, 1.0, 0.5, "hit", 0.25),          # in the window
        c("reference_after", 120.0, 1.0, 1.0, 1.0, "none"),  # after it
    ]


def lifecycle():
    return [
        Row(1, None, "engine.build", 20.0, 24.0,
            {"n_slots": 64, "capacity": 2048, "page_bytes": 1 << 32}),
        Row(2, None, "program.first_call", 51.0, 56.0,
            {"program": "prefill_sampled", "key": "2x1024"}),
        Row(3, None, "program.first_call", 60.0, 68.0,
            {"program": "decode_k", "key": "4"}),
        Row(4, None, "program.first_call", 91.0, 96.0,
            {"program": "prefill_sampled", "key": "2x2048"}),
    ]


#: what the rows above say of set-up: make_tree, broadcast_in_dim, _pf,
#: convert_element_type, _decode_k
WANT = {
    "setup_programs_lowered": 5,
    "setup_cache_misses": 3,
    "setup_trace_lower_s": 0.75 + 0.125 + 3.0 + 0.0 + 2.0,
    "setup_backend_compile_s": 1.0 + 0.375 + 0.5 + 0.25 + 4.0,
    "setup_first_run_s": (5.0 - 3.5 - 0.25) + (8.0 - 6.0),
    "setup_build_s": 4.0 - 0.5,
}


@pytest.fixture()
def program(monkeypatch):
    """The program's records for the length of a test: the hand-made rows in
    the module's own deques, so that its ``compile_table`` joins them."""
    monkeypatch.setattr(tracing, "_compiles",
                        collections.deque(compile_rows()))
    monkeypatch.setattr(tracing, "_lifecycle",
                        collections.deque(lifecycle()))


def facts():
    return {"spans": spans(), "window_s": WINDOW_S}


def read(name, f):
    return registry.load_module("metrics", name).read(f)


@pytest.mark.parametrize("name", METRICS)
def test_each_reader_gives_its_number_of_set_up_alone(program, name, capsys):
    f = facts()
    assert read(name, f) == pytest.approx(WANT[name])
    assert type(read(name, f)) is type(WANT[name])
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and out[0].startswith("compile_table {")  # once


@pytest.mark.parametrize("name", METRICS)
def test_rows_outside_the_set_up_spans_are_left_out(program, name):
    """Without the reference's rows, the window's and the rest, every
    number is the same; without set-up's spans every number is 0."""
    f = facts()
    want = read(name, f)
    kept = [c for c in compile_rows() if 10.0 <= c.t_end <= 30.0
            or 50.0 <= c.t_end <= 80.0]
    assert len(kept) == 5
    tracing._compiles.clear()
    tracing._compiles.extend(kept)
    assert read(name, facts()) == pytest.approx(want)
    bare = runtime.Spans()
    bare.rows += [r for r in spans().rows if not r[0].startswith("setup.")]
    assert read(name, {"spans": bare, "window_s": WINDOW_S}) == 0


def test_the_parts_add_up_to_less_than_the_set_up_spans(program):
    found = startup.startup(facts())
    parts = sum(found["setup"][k] for k in (
        "trace_lower_s", "backend_compile_s", "first_run_s", "build_s"))
    assert found["setup_spans_s"] == 50.0
    assert 0 < parts <= found["setup_spans_s"]
    assert found["elsewhere"] == 3      # import_time, the two references


def test_the_window_opens_where_the_first_span_after_set_up_starts():
    assert startup.window_of(spans(), WINDOW_S) == (80.5, 110.5)
    late = spans()
    late.rows = [r for r in late.rows if r[1] < 80.0]
    assert startup.window_of(late, WINDOW_S) == (80.0, 110.0)
    assert startup.window_of(runtime.Spans(), WINDOW_S) is None


def test_a_compile_inside_the_window_is_flagged_on_the_line(program, capsys):
    startup.startup(facts())
    text = capsys.readouterr().out.strip()
    table = json.loads(text.split(" ", 1)[1])
    assert table["in_window"] == [{"fun_name": "jit(_pf)",
                                   "program": "prefill_sampled",
                                   "key": "2x2048"}]
    calls = {(c["program"], c["key"]): c for c in table["first_calls"]}
    assert set(calls) == {("prefill_sampled", "2x1024"), ("decode_k", "4"),
                          ("prefill_sampled", "2x2048")}
    assert calls["prefill_sampled", "2x2048"]["in_window"] is True
    assert "in_window" not in calls["decode_k", "4"]
    first = calls["prefill_sampled", "2x1024"]
    assert (first["fun_name"], first["compiles"], first["cache"]) == (
        "jit(_pf)", 2, "hit")
    assert first["first_run_s"] == pytest.approx(1.25)
    assert calls["decode_k", "4"]["cache"] == "miss"
    assert table["setup"]["cache_misses"] == 3
    assert table["builds"] == [{"n_slots": 64, "capacity": 2048,
                                "page_bytes": 1 << 32,
                                "span": "engine.build", "span_s": 4.0}]
    # the benchmark's own jit is outside the program's spans; the page's
    # zeros were compiled inside the build
    outside = {(o["fun_name"], o["span"]): o
               for o in table["outside_first_calls"]}
    assert set(outside) == {("jit(make_tree)", None),
                            ("jit(broadcast_in_dim)", "engine.build")}
    assert outside["jit(make_tree)", None]["cache"] == {"hit": 1}
    assert table["compiles_elsewhere"] == 3
    # a compile in the window with no first call around it is named too
    tracing._lifecycle.pop()
    startup.startup(facts())
    table = json.loads(capsys.readouterr().out.strip().split(" ", 1)[1])
    assert table["in_window"] == [{"fun_name": "jit(_pf)", "program": None,
                                   "key": None}]


@pytest.mark.parametrize("name", METRICS)
def test_a_program_from_before_the_compile_log_reads_the_stated_value(
        monkeypatch, capsys, name):
    monkeypatch.delattr(tracing, "compiles")
    f = facts()
    assert read(name, f) == startup.NOT_INSTRUMENTED == 0.0
    assert f["startup"] is None
    out = capsys.readouterr().out.strip()
    assert out.startswith("compile_table: the program has no "
                          "chainermn_tpu.tracing.compiles")
    assert "not measured" in out


@pytest.mark.parametrize("cell", [
    "gpt2m-train-dp1", "gpt2m-train-dp4", "sc2-3b-serve-batchgen",
    "ling3-flash-serve-reasongen", "xing4-serve-longdoc",
    "dsv3-serve-mtp-reasongen", "laguna-xs2-serve-mixedctx"])
def test_every_cell_declares_the_six_in_a_traced_run_only(cell):
    bench = registry.load_benchmark()
    traced = [m["name"] for m in line_mod.declared(bench, cell, 1)]
    assert [n for n in traced if n in METRICS] == METRICS
    assert not set(METRICS) & {m["name"] for m in
                               line_mod.declared(bench, cell, 0)}
    # the block is found by its layer, wherever later entries stand
    block = [m for m in bench["per_layer"] if m["layer"] == "start-up"]
    assert [m["name"] for m in block] == METRICS
    for m in block:
        assert (m["moves"], m["better"]) == ("setup_s", "lower") \
            and "workloads" not in m
