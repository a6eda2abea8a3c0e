"""chainermn_tpu.tracing: off outside a profiler session (one shared no-op,
no clock read, no row), on inside one (rows with parents and attributes, and
the same names as events of ``/host:CPU`` in the written trace)."""
import glob
import os
import threading
import types

import pytest

from chainermn_tpu import tracing


@pytest.fixture(autouse=True)
def no_rows_left_behind():
    tracing.clear()
    yield
    tracing.clear()


def test_off_is_one_shared_noop_that_reads_no_clock(monkeypatch):
    def boom():
        raise AssertionError("the clock was read with tracing off")

    monkeypatch.setattr(tracing, "time",
                        types.SimpleNamespace(perf_counter=boom))
    a = tracing.span("engine.step", iteration=1)
    b = tracing.span("updater.update")
    assert a is b is tracing.OFF and not a
    with a as sp:
        assert sp is tracing.OFF
        sp.set(admitted=2)
        with tracing.span("inner") as inner:
            assert inner is tracing.OFF
    assert tracing.rows() == []


def test_off_propagates_exceptions():
    with pytest.raises(KeyError):
        with tracing.span("x"):
            raise KeyError("through")


@pytest.fixture(scope="module")
def recorded(profiler_session):
    """One session shared by the tests below: what it left in memory and
    the trace it wrote."""
    tracing.clear()
    with profiler_session() as logdir:
        with tracing.span("outer", iteration=7, name="first") as outer:
            assert outer
            with tracing.span("inner.a") as a:
                a.set(admitted=2, padded_tokens=212)
            with tracing.span("inner.b", live=3):
                pass
        with pytest.raises(KeyError):
            with tracing.span("raises"):
                raise KeyError("through")

        def elsewhere():
            with tracing.span("other.thread"):
                pass

        t = threading.Thread(target=elsewhere)
        with tracing.span("main.thread"):
            t.start()
            t.join(timeout=30)
        assert not t.is_alive()
        rows = tracing.rows()
        for i in range(tracing.MAX_ROWS + 10):
            with tracing.span("flood", i=i):
                pass
        flooded = tracing.rows()
    after = tracing.span("after.the.session")
    tracing.clear()
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return {"rows": rows, "flooded": flooded, "after": after,
            "xplane": paths[-1]}


def test_on_rows_carry_parents_and_attributes_from_call_and_set(recorded):
    by_name = {r.name: r for r in recorded["rows"]}
    outer, a, b = by_name["outer"], by_name["inner.a"], by_name["inner.b"]
    assert outer.parent_id is None
    assert a.parent_id == b.parent_id == outer.id
    assert outer.attrs == {"iteration": 7, "name": "first"}
    assert a.attrs == {"admitted": 2, "padded_tokens": 212}
    assert b.attrs == {"live": 3}
    assert outer.t0 <= a.t0 <= a.t1 <= b.t0 <= b.t1 <= outer.t1
    assert len({r.id for r in recorded["rows"]}) == len(recorded["rows"])
    # a row is appended when its span ends: children before their parent
    names = [r.name for r in recorded["rows"]]
    assert names.index("inner.a") < names.index("outer")


def test_on_a_span_that_raises_still_records_and_unwinds(recorded):
    by_name = {r.name: r for r in recorded["rows"]}
    assert by_name["raises"].parent_id is None
    assert by_name["main.thread"].parent_id is None


def test_on_parents_are_per_thread(recorded):
    by_name = {r.name: r for r in recorded["rows"]}
    assert by_name["other.thread"].parent_id is None
    main = by_name["main.thread"]
    assert main.t0 <= by_name["other.thread"].t0 <= main.t1


def test_rows_are_bounded_and_windowed(recorded):
    flooded = recorded["flooded"]
    assert len(flooded) == tracing.MAX_ROWS
    assert flooded[0].name == "flood" and flooded[-1].attrs == {
        "i": tracing.MAX_ROWS + 9}
    rows = recorded["rows"]
    outer = next(r for r in rows if r.name == "outer")
    tracing._rows.extend(rows)
    inside = tracing.rows(outer.t0, outer.t1)
    assert [r.name for r in inside] == ["inner.a", "inner.b", "outer"]
    assert tracing.rows(outer.t0, outer.t1 - 1e-9)[-1].name == "inner.b"
    assert tracing.rows(lo=outer.t1) == [
        r for r in rows if r.t0 >= outer.t1]


def test_off_again_once_the_session_has_stopped(recorded):
    assert recorded["after"] is tracing.OFF
    assert tracing.span("now") is tracing.OFF


def test_spans_are_events_of_the_host_plane_with_their_attributes(recorded):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(recorded["xplane"])
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(e)
    for name in ("outer", "inner.a", "inner.b", "raises", "other.thread"):
        assert name in events, sorted(events)[:20]
    outer, a = events["outer"][0], events["inner.a"][0]
    assert dict(outer.stats)["iteration"] == 7
    assert outer.start_ns <= a.start_ns
    assert a.start_ns + a.duration_ns <= outer.start_ns + outer.duration_ns
    # the annotation and the row time the same piece of work
    row = next(r for r in recorded["rows"] if r.name == "outer")
    assert abs(outer.duration_ns / 1e9 - (row.t1 - row.t0)) < 1e-3


# -- the two records kept without a session ------------------------------
def _fresh(name):
    """A function no other test has jitted: its own name and code."""
    import jax.numpy as jnp

    def f(x):
        return jnp.tanh(x) * 3.0 + 1.0

    f.__name__ = f.__qualname__ = name
    return f


def _named(rows, name):
    return [c for c in rows if c.fun_name == f"jit({name})"]


def test_a_fresh_jit_gives_one_compile_row_and_a_second_call_none():
    import time

    import jax
    import jax.numpy as jnp

    x = jnp.ones((3,))
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    fn = jax.jit(_fresh("compile_log_probe"))
    fn(x)
    first = tracing.compiles(t0)
    (row,) = _named(first, "compile_log_probe")
    assert row.trace_s > 0 and row.lower_s > 0 and row.backend_s > 0
    assert row.cache in ("none", "miss") and row.retrieval_s == 0.0
    assert t0 <= row.t_end <= time.perf_counter()
    assert tracing.compiles(hi=t0) == [
        c for c in tracing.compiles() if c.t_end <= t0]
    fn(x)
    assert tracing.compiles(t0) == first


def test_a_jit_traced_inside_another_gives_no_row_of_its_own():
    import time

    import jax
    import jax.numpy as jnp

    inner = jax.jit(_fresh("compile_log_inner"))

    def outer(x):
        return inner(x) + inner(x * 2.0)

    outer.__name__ = outer.__qualname__ = "compile_log_outer"
    x = jnp.ones((5,))
    jax.block_until_ready(x + x * 2.0)
    t0 = time.perf_counter()
    jax.jit(outer)(x)
    rows = tracing.compiles(t0)
    assert not _named(rows, "compile_log_inner")
    (row,) = _named(rows, "compile_log_outer")
    assert [c.fun_name for c in rows] == ["jit(compile_log_outer)"]
    # the inner traces lie inside the outer's, which is its own and not
    # the last inner one's
    assert row.trace_s > 0


def test_the_persistent_cache_reads_miss_then_hit(tmp_path):
    import time

    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache

    keys = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in keys}
    x = jnp.ones((7,))
    jax.block_until_ready(x)
    try:
        for k, v in keys.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        def compiled_afresh():
            t0 = time.perf_counter()
            jax.jit(_fresh("compile_log_cached"))(x)
            (row,) = _named(tracing.compiles(t0), "compile_log_cached")
            jax.clear_caches()
            return row

        miss, hit = compiled_afresh(), compiled_afresh()
        assert (miss.cache, hit.cache) == ("miss", "hit")
        assert miss.retrieval_s == 0.0
        assert 0.0 < hit.retrieval_s <= hit.backend_s
        assert hit.trace_s > 0 and hit.lower_s > 0   # paid again on a hit
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_the_listeners_are_registered_once_however_often_asked():
    from jax._src import monitoring

    def count():
        return (sum(cb.__module__ == tracing.__name__
                    for cb in monitoring.get_event_duration_listeners()),
                sum(cb.__module__ == tracing.__name__
                    for cb in monitoring.get_event_listeners()))

    assert count() == (1, 1)
    tracing._listen()
    assert count() == (1, 1)


def test_lifecycle_spans_record_without_a_session_and_nest():
    import time

    t0 = time.perf_counter()
    assert tracing.span("hot") is tracing.OFF
    with tracing.lifecycle_span("engine.build", n_slots=4) as build:
        assert build
        with tracing.span("hot.inside") as hot:
            assert hot is tracing.OFF
        with tracing.lifecycle_span("program.first_call", program="decode_k",
                                    key="2") as call:
            pass
        build.set(page_bytes=1 << 20)
    with pytest.raises(KeyError):
        with tracing.lifecycle_span("step.build"):
            raise KeyError("through")
    got = tracing.lifecycle_rows(t0)
    assert [r.name for r in got] == ["program.first_call", "engine.build",
                                     "step.build"]
    first, built, failed = got
    assert first.parent_id == built.id and built.parent_id is None
    assert failed.parent_id is None
    assert built.attrs == {"n_slots": 4, "page_bytes": 1 << 20}
    assert first.attrs == {"program": "decode_k", "key": "2"}
    assert built.t0 <= first.t0 <= first.t1 <= built.t1
    assert tracing.lifecycle_rows(t0, built.t1 - 1e-9) == [first]
    assert tracing.rows() == []         # never among the iteration rows
    assert tracing.span("hot") is tracing.OFF


def test_lifecycle_rows_survive_a_flood_of_iteration_rows(profiler_session):
    import time

    t0 = time.perf_counter()
    with tracing.lifecycle_span("engine.build", capacity=32):
        pass
    with profiler_session():
        with tracing.lifecycle_span("program.first_call", program="prefill",
                                    key="2x8"):
            with tracing.span("engine.admit"):
                pass
        for i in range(70_000):
            with tracing.span("flood", i=i):
                pass
    assert len(tracing.rows()) == tracing.MAX_ROWS
    tracing.clear()                     # drops the iteration rows alone
    assert [r.name for r in tracing.lifecycle_rows(t0)] == [
        "engine.build", "program.first_call"]
    for i in range(tracing.MAX_LIFECYCLE_ROWS + 5):
        with tracing.lifecycle_span("bounded", i=i):
            pass
    assert len(tracing.lifecycle_rows()) == tracing.MAX_LIFECYCLE_ROWS
    assert tracing.lifecycle_rows()[-1].attrs == {
        "i": tracing.MAX_LIFECYCLE_ROWS + 4}


def test_compile_table_gives_a_first_call_its_compiles_and_its_first_run():
    import time

    import jax
    import jax.numpy as jnp

    x = jnp.ones((9,))
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    jax.jit(_fresh("table_outside"))(x)
    fn = jax.jit(_fresh("table_program"))
    with tracing.lifecycle_span("engine.build"):
        jax.jit(_fresh("table_in_build"))(x)
    for _ in range(2):      # a program key dispatched twice: one first call
        with (tracing.lifecycle_span("program.first_call", program="decode_k",
                                     key="4") if not fn._cache_size()
              else tracing.OFF):
            jax.block_until_ready(fn(x))
    table = tracing.compile_table(t0)
    assert [(e["program"], e["key"], e["span"], e["fun_name"])
            for e in table] == [
        ("decode_k", "4", "program.first_call", "jit(table_program)"),
        (None, None, None, "jit(table_outside)"),
        (None, None, "engine.build", "jit(table_in_build)")]
    call, outside, built = table
    (row,) = _named(tracing.compiles(t0), "table_program")
    assert call["compiles"] == 1 and call["cache"] == row.cache
    assert (call["trace_s"], call["lower_s"], call["backend_s"]) == (
        row.trace_s, row.lower_s, row.backend_s)
    cost = row.trace_s + row.lower_s + row.backend_s
    assert call["first_run_s"] == pytest.approx(call["span_s"] - cost)
    assert 0 <= call["first_run_s"] < call["span_s"]
    assert outside["first_run_s"] is None and built["span_s"] is None
    assert all(isinstance(e, dict) for e in table)
    assert tracing.compile_table(call["t_end"] + 1e-6) == []
