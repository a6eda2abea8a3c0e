"""The readers of the program's own spans (``chainermn_tpu.tracing``) on
hand-made rows: an iteration that admitted nothing, a sub-window with no
prefill at all, one with no iteration (raises, naming the span), and a
program from before the spans (a stated value, said on an earlier line)."""
import json

import pytest

from benchmark.harness import line as line_mod
from benchmark.harness import phases, registry
from chainermn_tpu.tracing import Row

SERVE = ["serve_admit_host_ms", "serve_decode_enqueue_host_ms",
         "serve_emit_host_ms", "serve_queue_age_s", "serve_admitted_per_iter",
         "serve_prefill_pad_pct"]
TRAIN = ["train_input_host_ms", "train_dispatch_host_ms"]


def read(name, rows):
    return registry.load_module("metrics", name).read({"program_rows": rows})


def serve_rows(admitting=(True, False, True)):
    """One engine.step a second; an admitting iteration runs admit 4 ms,
    wait, emit 1 ms, then every iteration decode.enqueue 2 ms, wait,
    emit 3 ms. Ids count up; children name their step."""
    rows, ids = [], iter(range(1, 1000))
    for i, admits in enumerate(admitting):
        t, step = float(i), next(ids)

        def child(name, t0, ms, **attrs):
            rows.append(Row(next(ids), step, name, t + t0, t + t0 + ms / 1e3,
                            attrs))

        if admits:
            child("engine.admit", 0.00, 4, bucket=256, admitted=2, rows=2,
                  prompt_tokens=300, padded_tokens=212)
            child("engine.prefill.wait", 0.01, 20)
            child("engine.emit", 0.04, 1, tokens=2, retired=0)
        child("engine.decode.enqueue", 0.05, 2, live=30)
        child("engine.decode.wait", 0.06, 500)
        child("engine.emit", 0.6, 3, tokens=120, retired=1)
        rows.append(Row(step, None, "engine.step", t, t + 0.7,
                        {"iteration": i, "queued": 28, "active": 30,
                         "oldest_wait_s": 20.0 + 5 * i}))
    return rows


def train_rows(n=3):
    rows, ids = [], iter(range(1, 1000))
    for i in range(n):
        t, step = float(i), next(ids)
        rows.append(Row(next(ids), step, "updater.input", t, t + (i + 1) / 1e4,
                        {"bytes": 65536}))
        rows.append(Row(next(ids), step, "updater.dispatch", t + 0.001,
                        t + 0.001 + 5e-4, {}))
        rows.append(Row(step, None, "updater.update", t, t + 0.002,
                        {"iteration": i}))
    return rows


@pytest.mark.parametrize("name,want", [
    ("serve_admit_host_ms", 4.0),            # iterations: 4, 0, 4
    ("serve_decode_enqueue_host_ms", 2.0),
    ("serve_emit_host_ms", 4.0),             # 1 + 3, 3, 1 + 3
    ("serve_queue_age_s", 25.0),             # 20, 25, 30
    ("serve_admitted_per_iter", 4 / 3),
    ("serve_prefill_pad_pct", 100 * 424 / 1024),
])
def test_serving_readers_with_an_iteration_that_admitted_nothing(name, want):
    assert read(name, serve_rows()) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("serve_admit_host_ms", 0.0), ("serve_admitted_per_iter", 0.0),
    ("serve_prefill_pad_pct", 0.0), ("serve_emit_host_ms", 3.0),
    ("serve_decode_enqueue_host_ms", 2.0)])
def test_serving_readers_with_no_prefill_at_all(name, want):
    assert read(name, serve_rows((False, False))) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [("train_input_host_ms", 0.2),
                                       ("train_dispatch_host_ms", 0.5)])
def test_training_readers(name, want):
    assert read(name, train_rows()) == pytest.approx(want)


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_no_iteration_in_the_sub_window_raises_naming_the_span(name):
    root = "engine.step" if name in SERVE else "updater.update"
    orphans = [r for r in serve_rows() + train_rows() if r.name != root]
    with pytest.raises(LookupError, match=root):
        read(name, orphans)
    with pytest.raises(LookupError, match=root):
        read(name, [])


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_a_program_from_before_the_spans_reads_the_stated_value(name):
    assert read(name, None) == phases.NOT_INSTRUMENTED
    assert "no chainermn_tpu.tracing" in phases.table_line(None)


def test_rows_come_from_the_program_inside_the_trace_window(capsys):
    """The rows are read once a run, from ``tracing.rows`` between the ends
    of the run's ``bench.trace_window`` span, and the table is printed."""
    from benchmark.harness import runtime
    from chainermn_tpu import tracing

    spans = runtime.Spans()
    spans.rows.append((runtime.trace_mod.WINDOW_ANNOTATION, 1.0, 2.9))
    facts = {"spans": spans}
    tracing.clear()
    try:
        tracing._rows.extend(serve_rows())      # steps at 0-0.7, 1-1.7, 2-2.7
        got = phases.rows_in_window(facts)
    finally:
        tracing.clear()
    assert [r.attrs["iteration"] for r in got if r.name == "engine.step"] \
        == [1, 2]
    assert phases.rows_in_window(facts) is got
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and out[0].startswith("phase_table {")
    with pytest.raises(LookupError, match="bench.trace_window"):
        phases.rows_in_window({"spans": runtime.Spans()})


def test_phase_table_counts_medians_totals_and_summed_attributes():
    text = phases.table_line(serve_rows())
    table = json.loads(text.split(" ", 1)[1])
    assert table["engine.emit"]["n"] == 5
    assert table["engine.emit"]["median_ms"] == pytest.approx(3.0)
    assert table["engine.emit"]["total_ms"] == pytest.approx(11.0)
    assert table["engine.emit"]["sum"] == {"tokens": 364, "retired": 3}
    assert table["engine.admit"]["sum"]["padded_tokens"] == 424
    assert table["engine.step"]["sum"]["oldest_wait_s"] == pytest.approx(75.0)


@pytest.mark.parametrize("cell,names", [
    ("sc2-3b-serve-batchgen", SERVE), ("gpt2m-train-dp1", TRAIN),
    ("gpt2m-train-dp4", TRAIN)])
def test_the_new_metrics_are_declared_for_their_cells_alone(cell, names):
    bench = registry.load_benchmark()
    declared = {m["name"] for m in line_mod.declared(bench, cell, 1)}
    assert set(names) <= declared
    assert not (set(SERVE + TRAIN) - set(names)) & declared
    assert not set(SERVE + TRAIN) & {
        m["name"] for m in line_mod.declared(bench, cell, 0)}
