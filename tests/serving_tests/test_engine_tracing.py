"""The engine's spans: under a profiler session every iteration gives its
phases in order inside its ``engine.step`` span, with admission counted where
it happens (at the top of its iteration, or one decode ahead: both orders,
and each engine again with every admission held to the late path); without a
session nothing is recorded and the tokens are the same."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu import tracing
from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.serving import SpeculativeEngine
from chainermn_tpu.serving.engine import Engine, EngineConfig

PHASES = ["engine.admit", "engine.prefill.wait", "engine.emit",
          "engine.decode.enqueue", "engine.decode.wait", "engine.emit"]
LENS = (3, 5, 4, 12, 4, 6, 3)
N_NEW = 6


@functools.lru_cache(maxsize=None)
def _setup():
    model = TransformerLM(vocab=43, d_model=32, n_heads=4, n_layers=1,
                          d_ff=48, max_len=64, attention="reference",
                          pos_emb="rope")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _engine(kind):
    """``kind`` names the engine; a ``-late`` suffix holds its admissions to
    the top of their iteration (a test's patch of the predicate)."""
    kind, _, late = kind.partition("-")
    eng = _build(kind)
    if late:
        eng._admission_is_fixed = lambda: False
    return eng


def _build(kind):
    model, params = _setup()
    cfg = EngineConfig(n_slots=4, capacity=32, max_new_tokens=N_NEW,
                       prefill_cohort=2, buckets=[8, 32], decode_k=2,
                       prefill_chunk=4 if kind == "chunked" else None)
    if kind == "speculative":
        return SpeculativeEngine(model, params, model, params, cfg, spec_k=2)
    return Engine(model, params, cfg)


def _drain(eng):
    rs = np.random.RandomState(0)
    reqs = [eng.submit(rs.randint(0, 43, (n,)).astype(np.int32), seed=i,
                       temperature=0.7 if i % 2 else None)
            for i, n in enumerate(LENS)]
    eng.run_until_drained()
    return reqs


def _iterations(rows):
    steps = [r for r in rows if r.name == "engine.step"]
    return [(s, [r for r in rows if r.parent_id == s.id]) for s in steps]


@pytest.mark.parametrize("kind", ["plain", "chunked", "speculative"])
def test_stepped_without_a_session_nothing_is_recorded(kind):
    tracing.clear()
    reqs = _drain(_engine(kind))
    assert all(r.state == "done" and len(r.tokens) == N_NEW for r in reqs)
    assert tracing.rows() == []


@pytest.fixture(scope="module", params=["plain", "plain-late", "chunked",
                                         "speculative", "speculative-late"])
def traced(request, profiler_session):
    """Each kind of engine drained once without and once under a session."""
    kind = request.param
    plain = [list(r.tokens) for r in _drain(_engine(kind))]
    tracing.clear()
    with profiler_session():
        eng = _engine(kind)
        reqs = _drain(eng)
    rows = tracing.rows()
    tracing.clear()
    return {"kind": kind, "rows": rows, "engine": eng, "plain": plain,
            "tokens": [list(r.tokens) for r in reqs]}


def test_a_session_changes_no_token(traced):
    assert traced["tokens"] == traced["plain"]


def test_every_iteration_holds_its_phases_in_order(traced):
    """An iteration is its prefill (an admission at its top: admit, wait,
    emit; or the settling of the cohort the iteration before admitted ahead:
    wait, emit; a chunked engine may run several admissions) and then its
    decode (enqueue, the next iteration's admission where that ran ahead,
    wait, emit)."""
    its = _iterations(traced["rows"])
    assert len(its) == traced["engine"].iteration
    assert [s.attrs["iteration"] for s, _ in its] == list(
        range(1, len(its) + 1))
    assert {r.name for r in traced["rows"]} == set(PHASES) | {"engine.step"}
    in_flight = False           # the iteration before admitted ahead
    orders = set()
    for step, kids in its:
        names = [k.name for k in kids]
        prefills, decode = names, []
        if "engine.decode.enqueue" in names:
            at = names.index("engine.decode.enqueue")
            prefills, decode = names[:at], names[at:]
        ahead = decode[1:2] == ["engine.admit"]
        assert decode in ([], PHASES[3:],
                          PHASES[3:4] + PHASES[:1] + PHASES[4:])
        if in_flight:
            assert prefills == PHASES[1:3]          # settled, not admitted
        else:
            assert len(prefills) % 3 == 0
            for i in range(0, len(prefills), 3):
                assert prefills[i:i + 3] == PHASES[:3]
        admits = [k for k in kids if k.name == "engine.admit"]
        if traced["kind"] != "chunked":
            # one cohort an iteration: this one's at the top unless it was
            # in flight, and the next one's where it ran ahead
            late = bool(prefills) and not in_flight
            assert [a.attrs["ahead"] for a in admits] == (
                [0] * late + [1] * ahead)
            orders |= ({"late"} if late else set()) | (
                {"settled"} if in_flight else set()) | (
                {"ahead"} if ahead else set())
        in_flight = ahead
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
        assert step.t0 <= kids[0].t0 and kids[-1].t1 <= step.t1
        assert sum(k.t1 - k.t0 for k in kids) <= step.t1 - step.t0
    assert not in_flight
    if traced["kind"] in ("plain", "speculative"):
        # the stream takes both paths: (3, 5) finds no row decoding, (4) and
        # (12) are closed heads with a slot free, (4, 6) finds no slot free
        assert {"late", "ahead", "settled"} <= orders
    else:
        assert not {"ahead", "settled"} & orders


def test_admission_is_counted_where_it_happens(traced):
    its = _iterations(traced["rows"])
    admits = [k for _, kids in its for k in kids if k.name == "engine.admit"]
    assert sum(a.attrs["admitted"] for a in admits) == len(LENS)
    assert sum(a.attrs["prompt_tokens"] for a in admits) == sum(LENS)
    for a in admits:
        width = a.attrs["chunk" if traced["kind"] == "chunked" else "bucket"]
        assert ("ahead" in a.attrs) == (traced["kind"] != "chunked")
        assert a.attrs["rows"] == 2
        assert (a.attrs["prompt_tokens"] + a.attrs["padded_tokens"]
                == 2 * width)
    if traced["kind"] != "chunked":
        # FIFO, same-bucket cohorts of two: (3, 5) (4) (12) (4, 6) (3)
        assert [(a.attrs["bucket"], a.attrs["admitted"],
                 a.attrs["padded_tokens"]) for a in admits] == [
            (8, 2, 8), (8, 1, 12), (32, 1, 52), (8, 2, 6), (8, 1, 13)]


def test_a_program_without_latent_attention_names_none(traced):
    """``chunk_attention`` is the chunk program's dispatched attention as it
    was traced — the latent page's (tests/serving_tests/test_state_cache.py)
    or the K/V leaves' (tests/models_tests/test_kv_window_mixers.py): a dense
    ``TransformerLM`` has no such call, chunked or not, so its spans carry
    no such attribute."""
    admits = [r for r in traced["rows"] if r.name == "engine.admit"]
    assert admits and not any("chunk_attention" in a.attrs for a in admits)
    assert traced["engine"].steps.chunk_attention is None


def test_a_decode_program_without_latent_attention_names_none(traced):
    """The same for ``decode_attention`` on ``engine.decode.enqueue``."""
    enq = [r for r in traced["rows"] if r.name == "engine.decode.enqueue"]
    assert enq and not any("decode_attention" in r.attrs for r in enq)
    if not traced["kind"].startswith("speculative"):    # whose round is not
        #                                                   ``decode_k``
        assert traced["engine"].steps.decode_k_traces == 1
    assert traced["engine"].steps.decode_attention is None


def test_the_decode_span_names_the_attention_its_program_traced(
        profiler_session):
    """A model whose decode step reads latent pages in blocks, at widths the
    kernel serves: nothing before a decode program exists; from its trace on
    every ``engine.decode.enqueue`` span says which form the program's
    latent attention took — off the chip the ``jax.numpy`` loop, and why."""
    from tests.models_tests.test_hyper_connections import setup
    from tests.models_tests.test_mla_long import DECODE_ALIGNED

    model, params = setup(**DECODE_ALIGNED)
    eng = Engine(model, params, EngineConfig(
        n_slots=2, capacity=96, buckets=(32, 96), decode_k=2,
        prefill_cohort=2))
    rs = np.random.RandomState(0)
    for n in (9, 20):
        eng.submit(rs.randint(0, 256, (n,)).astype(np.int32),
                   max_new_tokens=5)
    tracing.clear()
    with profiler_session():
        eng._admit(float("inf"))
        assert eng.steps.decode_attention is None       # nothing traced yet
        eng.run_until_drained()
    rows = tracing.rows()
    tracing.clear()
    enq = [r for r in rows if r.name == "engine.decode.enqueue"]
    assert len(enq) >= 2 and eng.steps.decode_k_traces == 1
    assert eng.steps.decode_attention == "loop:not on a TPU"
    assert {r.attrs["decode_attention"] for r in enq} == {"loop:not on a TPU"}


def test_the_decode_span_names_the_dense_models_attention(profiler_session):
    """A dense ``TransformerLM`` that did not ask for the reference: its
    decode step's attention goes through ``models/transformer.py::
    cache_decode_attention``, so from the ``decode_k`` program's trace on
    every ``engine.decode.enqueue`` span says which form it took — off the
    chip, and at a toy row, the whole-page ``jax.numpy`` form, and why (the
    reference models of ``traced`` have no such call and name none)."""
    model = TransformerLM(vocab=43, d_model=32, n_heads=4, n_kv_heads=2,
                          n_layers=2, d_ff=48, max_len=64, attention="flash",
                          pos_emb="rope")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    eng = Engine(model, params, EngineConfig(
        n_slots=4, capacity=32, max_new_tokens=N_NEW, prefill_cohort=2,
        buckets=[8, 32], decode_k=2))
    rs = np.random.RandomState(0)
    for n in (3, 5, 12):
        eng.submit(rs.randint(0, 43, (n,)).astype(np.int32))
    tracing.clear()
    with profiler_session():
        eng._admit(float("inf"))
        assert eng.steps.decode_attention is None       # nothing traced yet
        eng.run_until_drained()
    rows = tracing.rows()
    tracing.clear()
    enq = [r for r in rows if r.name == "engine.decode.enqueue"]
    assert len(enq) >= 2 and eng.steps.decode_k_traces == 1
    assert eng.steps.decode_attention == (
        "xla:a cache row [2, 8] of float32 is not whole tiles of 128 lanes")
    assert {r.attrs["decode_attention"] for r in enq} == {
        eng.steps.decode_attention}


def test_the_decode_span_names_the_state_step_its_program_traced(
        profiler_session):
    """A model with a recurrent layer: from the ``decode_k`` program's trace
    on every ``engine.decode.enqueue`` span carries ``state_step``, the form
    the recurrence's decode step took (``models/hybrid.py::
    kda_decode_step``) — off the chip ``kda_step``, and why; a dense model's
    spans carry none (``traced``' engines hold no such layer)."""
    from chainermn_tpu.models.hybrid import HybridLM

    model = HybridLM(vocab=64, d_model=32, n_heads=2, d_head=16, d_ff=48,
                     max_len=64, pattern=(("kda", "dense"),))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    eng = Engine(model, params, EngineConfig(
        n_slots=2, capacity=64, buckets=(16, 64), decode_k=2,
        prefill_cohort=2))
    rs = np.random.RandomState(0)
    for n in (5, 9):
        eng.submit(rs.randint(0, 64, (n,)).astype(np.int32),
                   max_new_tokens=5)
    tracing.clear()
    with profiler_session():
        eng._admit(float("inf"))
        assert eng.steps.state_step is None             # nothing traced yet
        eng.run_until_drained()
    rows = tracing.rows()
    tracing.clear()
    enq = [r for r in rows if r.name == "engine.decode.enqueue"]
    assert len(enq) >= 2 and eng.steps.decode_k_traces == 1
    assert eng.steps.state_step == "xla:d_k 16, d_v 16 are not both " \
        "multiples of 128"
    assert {r.attrs["state_step"] for r in enq} == {eng.steps.state_step}
    assert not any("decode_attention" in r.attrs for r in enq)


def test_a_decode_program_without_a_recurrence_names_no_state_step(traced):
    enq = [r for r in traced["rows"] if r.name == "engine.decode.enqueue"]
    assert enq and not any("state_step" in r.attrs for r in enq)
    assert traced["engine"].steps.state_step is None


def test_the_step_span_sees_the_queue_it_started_with(traced):
    its = _iterations(traced["rows"])
    first, last = its[0][0], its[-1][0]
    assert first.attrs["queued"] == len(LENS) and first.attrs["active"] == 0
    assert 0.0 <= first.attrs["oldest_wait_s"] < 60.0
    assert last.attrs["queued"] == 0 and last.attrs["oldest_wait_s"] == 0.0
    waits = [s.attrs["oldest_wait_s"] for s, _ in its if s.attrs["queued"]]
    assert waits == sorted(waits)       # submitted together: the head ages


def test_emit_counts_tokens_and_retirements(traced):
    emits = [r for r in traced["rows"] if r.name == "engine.emit"]
    assert sum(e.attrs["tokens"] for e in emits) == len(LENS) * N_NEW
    assert sum(e.attrs["retired"] for e in emits) == len(LENS)
    assert traced["engine"].report.tokens_emitted == len(LENS) * N_NEW


@pytest.mark.parametrize("chunked", [False, True])
def test_the_serving_programs_name_their_attention_and_their_sampling(
        chunked):
    """Device scopes are metadata of the compiled programs: the decode
    branch's attention over the page under ``attend_cache`` (the one-token
    step and the chunk that attends over the whole page), sampling under
    ``sample``."""
    from chainermn_tpu.serving.kv_cache import (decode_apply, init_cache,
                                                prefill_chunk_apply)
    from chainermn_tpu.serving.sampling import init_keys, sample_tokens

    model, params = _setup()
    n = 2
    dm = model.clone(decode=True, chunked_prefill=chunked)
    cache = init_cache(model, n, 16)

    def program(params, cache, keys):
        if chunked:
            logits, cache = prefill_chunk_apply(
                dm, params, cache, jnp.zeros((n, 4), jnp.int32),
                jnp.zeros(n, jnp.int32), jnp.full(n, 4, jnp.int32),
                jnp.arange(n))
        else:
            logits, cache = decode_apply(dm, params, cache,
                                         jnp.zeros(n, jnp.int32))
        return sample_tokens(logits, keys, jnp.zeros(n), jnp.zeros(n, int))

    text = jax.jit(program).lower(params, cache, init_keys(n)).as_text(
        debug_info=True)
    scoped = [l for l in text.splitlines() if "attend_cache/" in l]
    assert any("dot_general" in l for l in scoped), scoped[:5]
    # the selection of the top-k threshold: its counting loop, and no sort
    sampling = [l for l in text.splitlines() if "/sample/" in l]
    assert any("/sample/while" in l for l in sampling), sampling[:5]
    assert not any("sort" in l for l in sampling)


# -- lifecycle spans and the compile log: kept without a session ---------
PROGRAMS = {"_pf", "_prefill", "_pc", "_decode_k", "_decode"}


@pytest.fixture(scope="module", params=["plain", "chunked", "speculative"])
def started(request):
    """Each kind of engine built and drained with no profiler session: what
    the process's lifecycle rows and compile log hold of it."""
    import time

    t0 = time.perf_counter()
    eng = _engine(request.param)
    t_built = time.perf_counter()
    _drain(eng)
    t_drained = time.perf_counter()
    _drain(eng)                 # every key again: no first call among them
    return {"kind": request.param, "engine": eng,
            "rows": tracing.lifecycle_rows(t0, t_drained),
            "later": tracing.lifecycle_rows(t_drained),
            "compiles": tracing.compiles(t0),
            "table": tracing.compile_table(t0), "t_built": t_built}


def test_one_engine_build_with_what_it_allocated(started):
    builds = [r for r in started["rows"] if r.name == "engine.build"]
    assert len(builds) == 1 and builds[0].t1 <= started["t_built"]
    steps = started["engine"].steps
    assert builds[0].attrs == {"n_slots": 4, "capacity": 32,
                               "page_bytes": steps.cache_bytes()}
    assert builds[0].parent_id is None


def test_one_first_call_per_program_key_reached_and_none_later(started):
    calls = [r for r in started["rows"] if r.name == "program.first_call"]
    keys = [(r.attrs["program"], r.attrs["key"]) for r in calls]
    want = ({("prefill_chunk", "2x4"), ("decode_k", "2")}
            if started["kind"] == "chunked" else
            {("prefill_sampled", "2x8"), ("prefill_sampled", "2x32"),
             ("decode_k", "2")})
    if started["kind"] == "speculative":    # its round is not ``decode_k``
        want.discard(("decode_k", "2"))
    assert len(keys) == len(set(keys)) and set(keys) == want
    assert started["later"] == []
    assert {r.name for r in started["rows"]} == {"engine.build",
                                                 "program.first_call"}


def test_every_compile_of_a_serving_program_lies_in_its_first_call(started):
    calls = [r for r in started["rows"] if r.name == "program.first_call"]
    programs = [c for c in started["compiles"]
                if c.fun_name[4:-1] in PROGRAMS]
    assert len(programs) == len(calls)
    for c in programs:
        assert sum(r.t0 <= c.t_end <= r.t1 for r in calls) == 1
    by_key = {(e["program"], e["key"]): e for e in started["table"]
              if e["span"] == "program.first_call"}
    assert len(by_key) == len(calls)
    for (program, _), e in by_key.items():
        assert e["fun_name"] == {
            "prefill_sampled": "jit(_pf)", "prefill_chunk": "jit(_pc)",
            "decode_k": "jit(_decode_k)"}[program]
        assert e["compiles"] >= 1 and 0 <= e["first_run_s"] < e["span_s"]


def test_the_one_token_and_logits_programs_have_first_calls_too():
    import time

    model, params = _setup()
    from chainermn_tpu.serving.kv_cache import ServingStep

    t0 = time.perf_counter()
    steps = ServingStep(model, params, 2, 16)
    for _ in range(2):
        steps.prefill(np.zeros((2, 8), np.int32), [3, 4], [0, 1])
        steps.decode(np.zeros(2, np.int32))
    calls = tracing.lifecycle_rows(t0)
    assert [(r.name, r.attrs) for r in calls] == [
        ("program.first_call", {"program": "prefill", "key": "2x8"}),
        ("program.first_call", {"program": "decode", "key": "2"})]
    assert steps.decode_traces == 1 and steps.prefill_traces == {(2, 8): 1}


def test_a_state_step_gets_its_first_calls_through_the_shared_paths():
    """``StateServingStep`` overrides the programs, not the entry points:
    the spans come with them."""
    import time

    from tests.models_tests.test_hyper_connections import setup
    from tests.models_tests.test_mla_long import DECODE_ALIGNED

    model, params = setup(**DECODE_ALIGNED)
    t0 = time.perf_counter()
    eng = Engine(model, params, EngineConfig(
        n_slots=2, capacity=96, buckets=(32, 96), decode_k=2,
        prefill_cohort=2))
    eng.submit(np.arange(9, dtype=np.int32), max_new_tokens=4)
    eng.run_until_drained()
    rows = tracing.lifecycle_rows(t0)
    assert type(eng.steps).__name__ == "StateServingStep"
    assert [(r.name, r.attrs.get("program"), r.attrs.get("key"))
            for r in rows] == [
        ("engine.build", None, None),
        ("program.first_call", "prefill_sampled", "2x32"),
        ("program.first_call", "decode_k", "2")]
    assert rows[0].attrs["page_bytes"] == eng.steps.cache_bytes() > 0
