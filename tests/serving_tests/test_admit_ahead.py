"""Admission one decode ahead (serving/engine.py): the next iteration's cohort
is composed and its prefill dispatched right after ``decode_k`` has been
enqueued, whenever the decision is fixed by then, and the next ``step()``
settles it.

The contract: the schedule is the late one carried out earlier. Every
request's tokens, the iteration that admitted it and the slot it took are
those of the same engine with the predicate held to ``False`` (a test's patch:
the engine has no such option); each condition of the predicate sends an
iteration down the late path; whatever changes the engine between two steps
settles the cohort in flight first; and the host arrays a dispatch was given
are never written under it."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu import tracing
from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.serving import Engine, EngineConfig, SpeculativeEngine

KINDS = ["dense", "hybrid", "self_draft", "speculative"]


@functools.lru_cache(maxsize=None)
def _dense():
    model = TransformerLM(vocab=43, d_model=32, n_heads=4, n_layers=1,
                          d_ff=48, max_len=64, attention="reference",
                          pos_emb="rope")
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))["params"]


def build(kind, **over):
    """An engine of ``kind`` over two buckets, four slots and cohorts of two,
    with the vocabulary and the (short, long) bucket of its prompts."""
    if kind in ("dense", "speculative"):
        model, params = _dense()
        vocab, buckets = 43, (8, 32)
    elif kind == "hybrid":          # a model with per-slot recurrent state
        from tests.serving_tests.test_state_cache import setup
        model, params = setup()
        vocab, buckets = 256, (16, 64)
    else:                           # its own multi-token-prediction module
        from tests.models_tests.test_mtp_module import SIZES, setup
        model, params = setup()
        vocab, buckets = SIZES["vocab"], (16, 64)
        over = dict(self_draft=True, **over)
    cfg = dict(n_slots=4, capacity=buckets[1], buckets=buckets, decode_k=2,
               prefill_cohort=2, max_new_tokens=8)
    cfg.update(over)
    if cfg.get("prefill_chunk"):
        cfg["buckets"] = (cfg["capacity"],)
    cfg = EngineConfig(**cfg)
    if kind == "speculative":
        return SpeculativeEngine(model, params, model, params, cfg,
                                 spec_k=2), vocab, buckets
    return Engine(model, params, cfg), vocab, buckets


def late(eng):
    """The same engine with every admission at the top of its iteration."""
    eng._admission_is_fixed = lambda: False
    return eng


def prompt(rs, vocab, n):
    return rs.randint(0, vocab, (n,)).astype(np.int32)


# -- equivalence -----------------------------------------------------------
#: (bucket: 0 short / 1 long, max_new_tokens) in order of arrival; the odd
#: ones are sampled. The first six are queued at the start, then one arrives
#: after each of the next iterations.
STREAM = [(0, 5), (0, 8), (1, 3), (0, 7), (0, 1), (0, 6), (1, 4), (1, 8),
          (0, 2), (0, 5), (0, 3), (1, 6)]


def serve_stream(eng, vocab, buckets):
    """Steps ``eng`` through STREAM. Returns per request (tokens, iteration
    that admitted it, slot), what every ``step()`` returned, the report's
    samples, and the number of iterations that ended with a cohort in
    flight."""
    rs = np.random.RandomState(7)
    slots = {}
    install = eng._install

    def recording(req, slot):
        slots[req.request_id] = slot
        install(req, slot)

    eng._install = recording
    kws = []
    for i, (long, n_new) in enumerate(STREAM):
        n = int(rs.randint(buckets[0] + 1, buckets[0] + 6) if long
                else rs.randint(3, buckets[0] + 1))
        kws.append(dict(prompt=prompt(rs, vocab, n), max_new_tokens=n_new,
                        **(dict(temperature=0.8, top_k=8, seed=30 + i)
                           if i % 2 else {})))
    reqs = [eng.submit(**kw) for kw in kws[:6]]
    later = kws[6:]
    admitted_at, returns, n_ahead = {}, [], 0
    while not eng.idle() or later:
        if later:
            reqs.append(eng.submit(**later.pop(0)))
        # step() syncs internally: it pulls the dispatch's token ids
        returns.append(eng.step())  # dlint: disable=DL104
        n_ahead += eng._ahead is not None
        for r in reqs:
            if r.tokens and r.request_id not in admitted_at:
                admitted_at[r.request_id] = eng.iteration
        assert eng.iteration < 200
    assert all(r.state == "done" and len(r.tokens) == n
               for r, (_, n) in zip(reqs, STREAM))
    per_request = [(list(r.tokens), admitted_at[r.request_id],
                    slots[r.request_id]) for r in reqs]
    rep = eng.report
    return (per_request, returns,
            (list(rep.occupancy_samples), list(rep.queue_depth_samples)),
            n_ahead)


@pytest.mark.parametrize("kind", KINDS)
def test_the_schedule_is_the_late_one_carried_out_earlier(kind):
    eng, vocab, buckets = build(kind, n_slots=5)
    got, returns, samples, n_ahead = serve_stream(eng, vocab, buckets)
    want, want_returns, want_samples, never = serve_stream(
        late(build(kind, n_slots=5)[0]), vocab, buckets)
    assert never == 0 and n_ahead >= 3          # both paths were taken
    assert n_ahead < len(returns) - 1
    for i, (a, b) in enumerate(zip(got, want)):
        assert a == b, (i, a, b)        # tokens, admitting iteration, slot
    # and what the caller's loop and the report saw, iteration by iteration:
    # a cohort in flight is still queued, and no row of the grid yet
    assert returns == want_returns
    assert samples == want_samples
    assert sorted(eng.free_slots) == [0, 1, 2, 3, 4] and eng._ahead is None


# -- each condition ----------------------------------------------------------
#: config overrides, prompts queued behind two live rows (all of the short
#: bucket but "b": the long one), max_new_tokens of everything, and whether
#: the iteration then admits ahead
CONDITIONS = {
    "all_hold_full_head": (dict(n_slots=6), "ss", 8, True),
    "all_hold_other_bucket_behind": (dict(n_slots=6), "sb", 8, True),
    "open_head": (dict(n_slots=6), "s", 8, False),
    "fewer_free_slots_than_the_cohort": (dict(n_slots=4), "ss", 8, False),
    "token_budget": (dict(n_slots=6, token_budget=10_000), "ss", 8, False),
    "prefill_chunk": (dict(n_slots=6, prefill_chunk=4), "ss", 8, False),
    "no_live_row": (dict(n_slots=6), "ss", 1, False),
}


@pytest.mark.parametrize("condition", sorted(CONDITIONS))
def test_each_condition_decides_the_path(condition):
    """Two rows decode; behind them the queue holds a cohort for the top of
    this iteration and then the head under test. Where a condition fails
    the iteration is the late path's: nothing is in flight after it."""
    over, queued, n_new, ahead = CONDITIONS[condition]
    eng, vocab, _ = build("dense", max_new_tokens=n_new, **over)
    rs = np.random.RandomState(1)
    reqs = [eng.submit(prompt(rs, vocab, 4)) for _ in range(2)]
    eng.step()
    assert eng._ahead is None           # nothing was queued behind them
    reqs += [eng.submit(prompt(rs, vocab, 12 if c == "b" else 4))
             for c in "ss" + queued]
    out = eng.step()
    assert (eng._ahead is not None) == ahead
    if ahead:
        cohort = eng._in_flight()
        want = 1 if queued.endswith("b") else 2
        assert cohort == reqs[4:4 + want]
        assert all(r.state == "running" and r.slot is not None
                   and not r.tokens and r.slot not in eng.active
                   and r.slot not in eng.free_slots for r in cohort)
        assert out["queued"] == len(eng.queue) + want
    eng.run_until_drained()
    assert all(r.state == "done" and len(r.tokens) == n_new for r in reqs)
    assert sorted(eng.free_slots) == list(range(eng.config.n_slots))


# -- what settles a cohort in flight -----------------------------------------

def _submitted(n_new):
    eng, vocab, _ = build("dense", n_slots=8)
    rs = np.random.RandomState(2)
    return eng, [eng.submit(prompt(rs, vocab, 4 + i % 3), max_new_tokens=n,
                            **(dict(temperature=0.7, top_k=6, seed=i)
                               if i % 2 else {}))
                 for i, n in enumerate(n_new)]


def in_flight(n_new=(8,) * 8):
    """A dense engine of eight slots stopped after the iteration that admits
    requests 0 and 1 at its top and 2 and 3 ahead; 4 to 7 wait."""
    eng, reqs = _submitted(n_new)
    eng.step()
    assert eng._in_flight() == reqs[2:4] and len(eng.queue) == 4
    return eng, reqs


@functools.lru_cache(maxsize=None)
def oracle_tokens(n_new=(8,) * 8):
    eng, reqs = in_flight(n_new)
    eng.run_until_drained()
    return [list(r.tokens) for r in reqs]


def accounted(eng, reqs):
    """No request lost or doubled: each ended once, each slot is free once."""
    s = eng.report.summary()["requests"]
    assert all(r.finished and r.slot is None for r in reqs)
    assert s["completed"] + s["aborted"] == len(reqs) == s["submitted"]
    assert sorted(eng.free_slots) == list(range(eng.config.n_slots))
    assert eng.idle() and eng._ahead is None


def test_an_engine_with_only_a_cohort_in_flight_is_not_idle():
    """Both live rows end in the decode that the cohort was dispatched
    behind: nothing queued, nothing active, and still work to do."""
    n_new = (3, 3, 8, 8)
    eng, vocab, _ = build("dense", n_slots=8)
    rs = np.random.RandomState(3)
    reqs = [eng.submit(prompt(rs, vocab, 5), max_new_tokens=n)
            for n in n_new]
    eng.step()
    assert not eng.queue and not eng.active and not eng.prefilling
    assert eng._in_flight() == reqs[2:] and not eng.idle()
    assert eng.run_until_drained() > 0
    assert [len(r.tokens) for r in reqs] == list(n_new)
    accounted(eng, reqs)


def test_abort_all_requeues_a_cohort_in_flight():
    eng, reqs = in_flight()
    hit = eng.abort_all(requeue=True)
    assert set(map(id, hit)) == set(map(id, reqs[:4]))
    assert all(r.state == "queued" and not r.tokens and r.slot is None
               for r in reqs)
    assert eng._ahead is None and len(eng.queue) == 8
    assert sorted(eng.free_slots) == list(range(8))
    eng.run_until_drained()
    assert [list(r.tokens) for r in reqs] == oracle_tokens()
    # the four that were requeued were submitted once and ended once
    accounted(eng, reqs)


def test_abort_all_aborts_a_cohort_in_flight():
    eng, reqs = in_flight()
    hit = eng.abort_all()
    assert len(hit) == 8 and all(r.state == "aborted" for r in reqs)
    accounted(eng, reqs)


def test_swap_weights_settles_and_refuses_while_the_cohort_lives():
    eng, reqs = in_flight()
    with pytest.raises(RuntimeError, match="4 queued, 4 active"):
        eng.swap_weights(_dense()[1], "v2")
    assert eng._ahead is None and eng.weights_version is None
    eng.run_until_drained()
    assert [list(r.tokens) for r in reqs] == oracle_tokens()
    accounted(eng, reqs)


def test_swap_weights_on_an_engine_whose_last_work_was_in_flight():
    """Every stream ends at its first token or in the first decode: after
    the iteration only the cohort in flight is left, and settling it drains
    the engine, so the swap goes through; its first tokens are the old
    weights'."""
    n_new = (3, 3, 1, 1)
    eng, reqs = _submitted(n_new)
    late_eng, want = _submitted(n_new)
    late(late_eng).run_until_drained()
    eng.step()
    assert eng._in_flight() == reqs[2:]
    other = jax.tree_util.tree_map(lambda a: -a, _dense()[1])
    old, version = eng.swap_weights(other, "v2")
    assert version is None and eng.weights_version == "v2"
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    accounted(eng, reqs)


def test_export_session_of_a_request_in_flight_and_release_under_another():
    """``export_session`` of a request whose cohort is in flight settles it
    and freezes the stream with its first token; a second engine continues
    it bitwise. The source is released while ITS next cohort is in flight."""
    eng, reqs = in_flight()
    moved = reqs[2]
    session = eng.export_session(moved)
    assert eng._ahead is None and moved.state == "held"
    assert session["tokens"] == oracle_tokens()[2][:1]
    assert reqs[3].slot in eng.active           # its cohort mate decodes on
    dest, _, _ = build("dense", n_slots=8)
    adopted = dest.import_session(session, moved.prompt)
    dest.run_until_drained()
    assert list(adopted.tokens) == oracle_tokens()[2]
    eng.step()              # admits 4 and 5 at its top, 6 and 7 ahead
    assert eng._in_flight() == reqs[6:]
    eng.release_held(moved)
    assert eng._ahead is None and moved.state == "done"
    assert all(r.slot in eng.active for r in reqs[6:])
    eng.run_until_drained()
    want = oracle_tokens()
    assert [list(r.tokens) for r in reqs] == want[:2] + [want[2][:1]] + want[3:]
    accounted(eng, reqs)


# -- the host arrays -----------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "speculative"])
def test_the_arrays_a_dispatch_was_given_are_not_written_under_it(kind):
    """``_install`` writes the sampling rows of the slots it binds. Ahead, it
    runs while the decode dispatch that was handed the same arrays may not
    have read them: the arrays that dispatch holds stay as they were when it
    was given them (the new rows go into copies), and its tokens are the late
    path's."""
    eng, vocab, _ = build(kind, n_slots=8)
    rs = np.random.RandomState(4)
    kws = [dict(prompt=prompt(rs, vocab, 5), temperature=0.9, top_k=5,
                seed=50 + i, eos_id=i) for i in range(4)]
    reqs = [eng.submit(**kw) for kw in kws]
    given = []
    names = ("_temps", "_topks", "_eos") + (
        ("_spec_prev",) if kind == "speculative" else ())
    admit_ahead = eng._admit_ahead

    def recording():        # called with the decode dispatch just enqueued
        arrays = [getattr(eng, n) for n in names]
        given.append((arrays, [a.copy() for a in arrays]))
        admit_ahead()

    eng._admit_ahead = recording
    eng.step()
    assert eng._in_flight() == reqs[2:] and len(given) == 1
    arrays, copies = given[0]
    for name, a, c in zip(names, arrays, copies):
        np.testing.assert_array_equal(a, c)
        assert getattr(eng, name) is not a
    # the rows of the cohort in flight were written, elsewhere
    slots = [r.slot for r in reqs[2:]]
    assert (eng._temps[slots] == np.float32(0.9)).all()
    assert (arrays[0][slots] == 0).all()
    assert eng._eos[slots].tolist() == [2, 3]
    eng.run_until_drained()
    ref = late(build(kind, n_slots=8)[0])
    want = [ref.submit(**kw) for kw in kws]
    ref.run_until_drained()
    assert [list(r.tokens) for r in reqs] == [list(r.tokens) for r in want]


# -- spans ---------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "speculative"])
def test_spans_of_both_paths(kind, profiler_session):
    """``engine.admit`` stays a direct child of ``engine.step`` (the
    benchmark's readers group by parent), says which path it took, and is at
    most one of each an iteration; an iteration that settles waits for the
    prefill before it enqueues its decode."""
    eng, vocab, buckets = build(kind, n_slots=5)
    tracing.clear()
    with profiler_session():
        serve_stream(eng, vocab, buckets)
    rows = tracing.rows()
    tracing.clear()
    steps = {r.id: r for r in rows if r.name == "engine.step"}
    admits = [r for r in rows if r.name == "engine.admit"]
    assert len(steps) == eng.iteration
    assert all(a.parent_id in steps for a in admits)
    assert all(a.attrs["ahead"] in (0, 1) for a in admits)
    assert sum(a.attrs["admitted"] for a in admits) == len(STREAM)
    assert 3 <= sum(a.attrs["ahead"] for a in admits) < len(admits)
    expect_settle = False
    for sid, step in sorted(steps.items(), key=lambda kv: kv[1].t0):
        kids = [r for r in rows if r.parent_id == sid]
        names = [k.name for k in kids]
        mine = [a.attrs["ahead"] for a in kids if a.name == "engine.admit"]
        assert mine in ([], [0], [1], [0, 1])
        if expect_settle:
            assert 0 not in mine
            assert names[:3] == ["engine.prefill.wait", "engine.emit",
                                 "engine.decode.enqueue"]
        if 1 in mine:
            at = names.index("engine.admit", 1 if mine == [0, 1] else 0)
            assert names[at - 1:at + 2] == [
                "engine.decode.enqueue", "engine.admit", "engine.decode.wait"]
        expect_settle = 1 in mine
    assert not expect_settle
