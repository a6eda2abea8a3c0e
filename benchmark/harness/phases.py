"""The program's own spans (``chainermn_tpu.tracing``) inside the traced
sub-window, for the readers under ``metrics/`` that split a scheduler
iteration or a trainer step by phase.

The program records a span only while a profiler session is active, on
``time.perf_counter``: the clock of the run's own ``Spans``, whose
``bench.trace_window`` row bounds the sub-window. The reducer of the device
trace keeps only ``bench.*`` host events, so these readers take the rows from
the program's memory and not from the xplane.

A program from before the spans has no ``chainermn_tpu.tracing``. There the
rows are ``None``, every reader returns ``NOT_INSTRUMENTED`` and the run says
so on an earlier line: ``harness/line.py`` refuses a line that lacks a
declared metric, and the run of such a program must still give its line.
"""
import importlib.util
import json

from benchmark.harness import stats
from benchmark.harness.trace import WINDOW_ANNOTATION

NOT_INSTRUMENTED = 0.0


def rows_in_window(facts):
    """The program's rows whole inside the traced sub-window, oldest first;
    ``None`` for a program without the module. Read once a run (kept in
    ``facts``), and the phase table is printed then."""
    if "program_rows" not in facts:
        facts["program_rows"] = program_rows(facts["spans"])
        print(table_line(facts["program_rows"]), flush=True)
    return facts["program_rows"]


def program_rows(spans):
    """``chainermn_tpu.tracing.rows`` between the ends of the
    ``bench.trace_window`` row of ``spans`` (the run's ``Spans``)."""
    if importlib.util.find_spec("chainermn_tpu.tracing") is None:
        return None
    from chainermn_tpu import tracing

    window = spans.named(WINDOW_ANNOTATION)
    if not window:
        raise LookupError(f"the run has no {WINDOW_ANNOTATION!r} span: "
                          "the program's spans have no sub-window")
    return tracing.rows(*window[-1])


def iterations(rows, root):
    """[(root row, [its child rows])] for every ``root`` span among
    ``rows``. Raises LookupError, naming the span, when there is none: a
    sub-window always holds an iteration, so none means the program's spans
    were off or have been renamed."""
    roots = [r for r in rows if r.name == root]
    if not roots:
        raise LookupError(
            f"no {root!r} span of the program inside {WINDOW_ANNOTATION}: "
            f"{len(rows)} program rows there, names "
            f"{sorted({r.name for r in rows})}")
    children = {r.id: [] for r in roots}
    for r in rows:
        if r.parent_id in children:
            children[r.parent_id].append(r)
    return [(r, children[r.id]) for r in roots]


def per_iteration(facts, root, child, attr=None):
    """One number per ``root`` span of the sub-window: the summed seconds of
    its ``child`` spans or, with ``attr``, their summed attribute; 0 for an
    iteration without such a child. ``None`` for a program without spans."""
    rows = rows_in_window(facts)
    if rows is None:
        return None
    return [sum((c.attrs.get(attr, 0) if attr else c.t1 - c.t0)
                for c in kids if c.name == child)
            for _, kids in iterations(rows, root)]


def median_ms(facts, root, child):
    """Median over the iterations of :func:`per_iteration`, in ms."""
    xs = per_iteration(facts, root, child)
    return NOT_INSTRUMENTED if xs is None else 1e3 * stats.median(xs)


def table_line(rows):
    """The phase table of the sub-window as one printable line: per span
    name its count, median and total in ms, and every numeric attribute
    summed."""
    if rows is None:
        return ("phase_table: the program has no chainermn_tpu.tracing; "
                f"its span metrics read {NOT_INSTRUMENTED} (not measured)")
    table = {}
    for r in rows:
        t = table.setdefault(r.name, {"n": 0, "ms": [], "sum": {}})
        t["n"] += 1
        t["ms"].append(1e3 * (r.t1 - r.t0))
        for k, v in r.attrs.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                t["sum"][k] = t["sum"].get(k, 0) + v
    return "phase_table " + json.dumps({
        name: {"n": t["n"], "median_ms": stats.median(t["ms"]),
               "total_ms": sum(t["ms"]), "sum": t["sum"]}
        for name, t in table.items()})
