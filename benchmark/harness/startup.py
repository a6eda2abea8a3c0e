"""What set-up cost, from inside the program: its compile log and lifecycle
spans (``chainermn_tpu.tracing.compiles``, ``lifecycle_rows``,
``compile_table``), for the ``setup_*`` readers under ``metrics/``.

The program keeps both records from its start, profiler session or not, on
``time.perf_counter``: the clock of the run's own ``Spans``. A record counts
as set-up when its end lies inside a ``setup.*`` row of those spans; the plain
reference compiles its own programs between or after them and is left out.
The measured window opens where the first span after the last ``setup.*`` row
starts (each driver reads the clock and enters its first iteration's span
there) and lasts ``facts["window_s"]``; a compile that ended in it is flagged
on the table's line: ``programs_lowered_in_window`` with a name.

The four parts of set-up are disjoint, so that they add up to at most the
``setup.*`` spans and the remainder is the benchmark's own work (imports,
the weights made on the device, the ramp's iterations): a first call and a
build are each read LESS the compile rows that ended inside them. (One
overlap is left in: a small program compiled while another is being traced
lies inside the outer's ``trace_s``.)

A program from before the compile log has no ``tracing.compiles``. There
:func:`startup` is ``None``, every reader returns ``NOT_INSTRUMENTED`` and the
run says so on the table's line: ``harness/line.py`` refuses a line that lacks
a declared metric, and the run of such a program must still give its line.
"""
import importlib.util
import json

from benchmark.harness.phases import NOT_INSTRUMENTED

BUILDS = ("engine.build", "step.build")
FIRST_CALL = "program.first_call"
METRICS = ("programs_lowered", "cache_misses", "trace_lower_s",
           "backend_compile_s", "first_run_s", "build_s")


def read(facts, metric):
    """One of ``METRICS`` for the run; ``NOT_INSTRUMENTED`` for a program
    without the compile log."""
    found = startup(facts)
    return NOT_INSTRUMENTED if found is None else found["setup"][metric]


def startup(facts):
    """:func:`reduce` of the program's records, read once a run (kept in
    ``facts``); the table's line is printed then. ``None`` for a program
    without the compile log."""
    if "startup" not in facts:
        log = program_log()
        facts["startup"] = log and reduce(
            *log, facts["spans"], facts.get("window_s", 0.0))
        print(table_line(facts["startup"]), flush=True)
    return facts["startup"]


def program_log():
    """(compile rows, lifecycle rows, compile table) of this process, or
    ``None`` where the program keeps none."""
    if importlib.util.find_spec("chainermn_tpu.tracing") is None:
        return None
    from chainermn_tpu import tracing

    if not hasattr(tracing, "compiles"):
        return None
    return tracing.compiles(), tracing.lifecycle_rows(), \
        tracing.compile_table()


def window_of(spans, window_s):
    """(start, end) of the measured window on the spans' clock, or ``None``
    where the run has no ``setup.*`` row."""
    ends = [e for n, _, e in spans.rows if n.startswith("setup.")]
    if not ends:
        return None
    after = [s for _, s, _ in spans.rows if s >= max(ends)]
    start = min(after, default=max(ends))
    return start, start + window_s


def cost(c):
    return c.trace_s + c.lower_s + c.backend_s


def reduce(compiles, lifecycle, table, spans, window_s):
    """The six numbers of set-up and the table's entries worth a line.

    ``setup``: over the compile rows that ended inside a ``setup.*`` row,
    their count, those not from the cache, their summed trace + lower and
    backend seconds; over the ``program.first_call`` rows that ended there
    their time less their compile rows; over the build rows likewise.
    ``programs``: the table's entries of set-up and of the window, the
    latter flagged ``in_window``. ``elsewhere``: the count of compile rows in
    neither (the reference's, and what follows the window)."""
    setups = [(s, e) for n, s, e in spans.rows if n.startswith("setup.")]
    window = window_of(spans, window_s)

    def in_setup(t):
        return any(s <= t <= e for s, e in setups)

    def in_window(t):
        return window is not None and window[0] <= t <= window[1]

    def less_compiles(r):
        return (r.t1 - r.t0) - sum(cost(c) for c in compiles
                                   if r.t0 <= c.t_end <= r.t1)

    rows = [c for c in compiles if in_setup(c.t_end)]
    spans_in = [r for r in lifecycle if in_setup(r.t1)]
    setup = {
        "programs_lowered": len(rows),
        "cache_misses": sum(c.cache != "hit" for c in rows),
        "trace_lower_s": sum(c.trace_s + c.lower_s for c in rows),
        "backend_compile_s": sum(c.backend_s for c in rows),
        "first_run_s": sum(less_compiles(r) for r in spans_in
                           if r.name == FIRST_CALL),
        "build_s": sum(less_compiles(r) for r in spans_in
                       if r.name in BUILDS),
    }
    programs = []
    for e in table:
        flagged = in_window(e["t_end"])
        if flagged or in_setup(e["t_end"]):
            programs.append(dict(e, in_window=True) if flagged else e)
    return {
        "setup": setup, "programs": programs,
        "setup_spans_s": sum(e - s for s, e in setups),
        "builds": [dict(r.attrs, span=r.name, span_s=r.t1 - r.t0)
                   for r in spans_in if r.name in BUILDS],
        "elsewhere": sum(not in_setup(c.t_end) and not in_window(c.t_end)
                         for c in compiles),
    }


def table_line(found):
    """What set-up compiled, as one printable line: the six numbers, every
    first call of a program key, the other compiles summed by name and
    enclosing span, and first the programs that compiled inside the measured
    window (none in a sound run). Seconds to a tenth of a millisecond."""
    if found is None:
        return ("compile_table: the program has no chainermn_tpu.tracing."
                f"compiles; its setup_* metrics read {NOT_INSTRUMENTED} "
                "(not measured)")

    def rounded(d):
        return {k: round(v, 4) if isinstance(v, float) else v
                for k, v in d.items() if k != "t_end"}

    calls, others = [], {}
    for e in found["programs"]:
        if e["span"] == FIRST_CALL:
            calls.append(rounded(e))
            continue
        o = others.setdefault((e["fun_name"], e["span"]), {
            "fun_name": e["fun_name"], "span": e["span"], "compiles": 0,
            "trace_lower_s": 0.0, "backend_s": 0.0, "cache": {}})
        o["compiles"] += e["compiles"]
        o["trace_lower_s"] += e["trace_s"] + e["lower_s"]
        o["backend_s"] += e["backend_s"]
        o["cache"][e["cache"]] = o["cache"].get(e["cache"], 0) + 1
    return "compile_table " + json.dumps({
        "in_window": [{"fun_name": e["fun_name"], "program": e["program"],
                       "key": e["key"]}
                      for e in found["programs"] if e.get("in_window")],
        "setup": rounded(found["setup"]),
        "setup_spans_s": round(found["setup_spans_s"], 4),
        "builds": [rounded(b) for b in found["builds"]],
        "first_calls": calls,
        "outside_first_calls": [rounded(o) for o in sorted(
            others.values(),
            key=lambda o: -o["trace_lower_s"] - o["backend_s"])],
        "compiles_elsewhere": found["elsewhere"]})
