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
