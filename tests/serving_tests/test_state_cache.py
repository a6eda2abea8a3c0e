"""A model that declares its own per-slot state (models/hybrid.py: recurrent
state, convolution tail, latent page — no K/V) through ``Engine.submit`` /
``step``: the served tokens against the plain reference, the trace counts,
slot export/import leaf by leaf, what is refused and why, and the route
counts on the decode span."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu import tracing
from chainermn_tpu.models.hybrid import HybridLM, layer_pattern
from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.serving import (Engine, EngineConfig, ServingStep,
                                   SpeculativeEngine)
from chainermn_tpu.serving.kv_cache import slot_bytes
from chainermn_tpu.serving.state_cache import StateServingStep, serving_step

from tests.models_tests.test_hybrid import SIZES, reference_logits

LENS = [(20, 9), (33, 12), (50, 7), (10, 15), (60, 11), (31, 5)]


@functools.lru_cache(maxsize=None)
def setup():
    model = HybridLM(pattern=layer_pattern(4, 3, 1), **SIZES)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def engine(**over):
    model, params = setup()
    cfg = dict(n_slots=4, capacity=128, buckets=(32, 64, 128), decode_k=4,
               prefill_cohort=2)
    return Engine(model, params, EngineConfig(**dict(cfg, **over)))


@functools.lru_cache(maxsize=None)
def served():
    eng = engine()
    rs = np.random.RandomState(0)
    reqs = [eng.submit(rs.randint(0, 256, (n,)), max_new_tokens=m,
                       **({} if i % 2 == 0 else
                          dict(temperature=0.8, top_k=20, seed=i)))
            for i, (n, m) in enumerate(LENS)]
    eng.run_until_drained()
    return eng, reqs


def test_one_decode_k_trace_and_one_prefill_trace_a_bucket():
    eng, reqs = served()
    assert all(r.state == "done" and len(r.tokens) == m
               for r, (_, m) in zip(reqs, LENS))
    assert eng.steps.decode_k_traces == 1
    assert eng.steps.prefill_traces == {(2, 32): 1, (2, 64): 1}


def test_served_greedy_tokens_are_the_references_best():
    """Logits, not tokens: every greedy served token's reference logit is
    the reference's largest at that position to within float32 noise."""
    eng, reqs = served()
    model, params = setup()
    for r in reqs[::2]:
        seq = np.concatenate([r.prompt, r.tokens[:-1]])
        want = np.asarray(reference_logits(model, params,
                                           jnp.asarray(seq[None])))[0]
        rows = want[len(r.prompt) - 1:]
        gap = rows.max(-1) - rows[np.arange(len(r.tokens)), r.tokens]
        assert gap.max() < 1e-3, gap


def test_last_decode_logits_match_the_reference_row():
    """What the benchmark's ``state_logit_rms`` compares: after prefill and
    decode through the cache, a live slot's logits on the device equal the
    reference's at that position."""
    model, params = setup()
    eng = engine()
    rs = np.random.RandomState(1)
    req = eng.submit(rs.randint(0, 256, (40,)), max_new_tokens=30)
    for _ in range(4):
        # step() syncs internally: one [n_slots, k] int32 pull
        eng.step()  # dlint: disable=DL104
    assert req.state == "running" and len(req.tokens) == 17
    seq = np.concatenate([req.prompt, req.tokens[:-1]])
    want = np.asarray(reference_logits(model, params,
                                       jnp.asarray(seq[None])))[0, -1]
    np.testing.assert_allclose(eng.last_logits[req.slot], want, atol=5e-4)


def test_pages_are_the_declared_leaves_slot_major():
    eng, _ = served()
    steps = eng.steps
    leaves = jax.tree_util.tree_leaves(steps.cache)
    assert all(a.shape[0] == 4 for a in leaves)
    kda = steps.cache["block_0"]["kda"]
    assert kda["state"].shape == (4, 4, 16, 16)
    assert kda["state"].dtype == jnp.float32
    assert kda["conv"].shape == (4, 3, 3 * 64)
    assert steps.cache["block_2"]["mla"]["ckv"].shape == (4, 128, 32 + 8)
    assert steps.cache["idx"].shape == (4,)
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4) + 128 * 40 * 4 + 4
    assert steps.slot_bytes == slot_bytes(steps.cache) == per_slot
    assert steps.cache_bytes() == 4 * per_slot


def test_export_import_round_trip_of_every_leaf_kind():
    """A frozen decoding session moves to another engine leaf by leaf —
    recurrent state, convolution tail, latent page, cursor — and continues
    the exact stream."""
    model, params = setup()
    rs = np.random.RandomState(2)
    prompt = rs.randint(0, 256, (25,))
    oracle = engine()
    want = oracle.submit(prompt, max_new_tokens=20)
    oracle.run_until_drained()

    src, dst = engine(), engine()
    req = src.submit(prompt, max_new_tokens=20)
    src.step()
    src.step()
    session = src.export_session(req)
    pages = session["pages"]
    assert set(pages) == set(src.steps.cache)
    assert pages["block_0"]["kda"]["state"].shape == (4, 16, 16)
    assert pages["block_0"]["kda"]["conv"].shape == (3, 192)
    assert pages["block_2"]["mla"]["ckv"].shape == (128, 40)
    dst.submit(rs.randint(0, 256, (9,)), max_new_tokens=3)   # takes slot 0
    dst.step()
    moved = dst.import_session(session, prompt)
    for name in ("block_0", "block_1", "block_3"):
        for leaf in ("state", "conv"):
            assert np.array_equal(
                np.asarray(dst.steps.cache[name]["kda"][leaf][moved.slot]),
                pages[name]["kda"][leaf])
    assert int(dst.steps.cursors()[moved.slot]) == session["cursor"]
    src.release_held(req)
    dst.run_until_drained()
    assert moved.tokens == want.tokens


def test_a_held_slot_rides_along_unchanged():
    """A frozen session's slot stays in the grid while others decode: its
    recurrent state must not take the ride-along steps."""
    eng = engine()
    rs = np.random.RandomState(3)
    a = eng.submit(rs.randint(0, 256, (20,)), max_new_tokens=40)
    b = eng.submit(rs.randint(0, 256, (22,)), max_new_tokens=40)
    eng.step()
    eng.export_session(a)
    before = np.asarray(eng.steps.cache["block_0"]["kda"]["state"][a.slot])
    other = np.asarray(eng.steps.cache["block_0"]["kda"]["state"][b.slot])
    eng.step()
    state = eng.steps.cache["block_0"]["kda"]["state"]
    assert np.array_equal(np.asarray(state[a.slot]), before)
    assert not np.array_equal(np.asarray(state[b.slot]), other)
    eng.resume_session(a)
    eng.run_until_drained()
    oracle = engine()
    want = oracle.submit(a.prompt, max_new_tokens=40)
    oracle.run_until_drained()
    assert a.tokens == want.tokens


def test_reset_zeroes_every_leaf():
    eng = engine()
    eng.submit(np.arange(12), max_new_tokens=3)
    eng.run_until_drained()
    assert any(np.asarray(a).any()
               for a in jax.tree_util.tree_leaves(eng.steps.cache))
    eng.steps.reset()
    assert not any(np.asarray(a).any()
                   for a in jax.tree_util.tree_leaves(eng.steps.cache))


@pytest.mark.parametrize("what", ["speculative", "int8-block", "ring wrap",
                                  "chunked prefill"])
def test_what_moves_rows_by_cursor_refuses_a_recurrent_state(what):
    model, params = setup()
    cfg = EngineConfig(n_slots=2, capacity=64, buckets=(32, 64))
    with pytest.raises(ValueError, match="recurrent state"):
        if what == "speculative":
            SpeculativeEngine(model, params, model, params, cfg, spec_k=2)
        elif what == "int8-block":
            serving_step(model, params, 2, 64, kv_dtype="int8-block")
        elif what == "ring wrap":
            Engine(model, params, cfg).submit(np.arange(40),
                                              max_new_tokens=30)
        else:
            Engine(model, params, EngineConfig(
                n_slots=2, capacity=64, buckets=(32, 64), prefill_chunk=8))


def test_the_step_is_picked_once_and_a_plain_one_refuses_declared_state():
    model, params = setup()
    assert type(serving_step(model, params, 2, 64)) is StateServingStep
    assert type(engine().steps) is StateServingStep
    with pytest.raises(ValueError, match="StateServingStep"):
        ServingStep(model, params, 2, 64)
    dense = TransformerLM(vocab=43, d_model=32, n_heads=4, n_layers=1,
                          d_ff=48, max_len=64)
    dense_params = dense.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))["params"]
    assert type(serving_step(dense, dense_params, 2, 16)) is ServingStep


def test_the_switch_training_layer_is_still_refused_and_says_what_serves():
    moe = TransformerLM(vocab=43, d_model=32, n_heads=4, n_layers=1, d_ff=48,
                        max_len=64, moe_experts_per_device=2)
    with pytest.raises(ValueError, match="HybridLM"):
        ServingStep(moe, {}, 2, 16)


def test_route_counts_ride_on_the_decode_span(tmp_path):
    """Under a profiler session the ``engine.decode.enqueue`` span carries
    the counts the step computed on the device, and ``engine.admit`` the
    bytes of state it installed."""
    eng = engine()
    rs = np.random.RandomState(4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tracing.clear()
        for n in (20, 30, 12):
            eng.submit(rs.randint(0, 256, (n,)), max_new_tokens=9)
        eng.run_until_drained()
        rows = tracing.rows()
    finally:
        jax.profiler.stop_trace()
    enq = [r for r in rows if r.name == "engine.decode.enqueue"]
    assert enq and all(
        {"experts_touched", "pairs_held", "pairs_routed", "expert_load_max",
         "expert_load_mean", "live"} <= set(r.attrs) for r in enq)
    for r in enq:
        a = r.attrs
        # 3 expert layers, decode_k 4, top-4 routing, 8 held experts
        assert a["pairs_routed"] <= a["live"] * 4 * 3 * 4
        assert a["pairs_routed"] % 4 == 0
        assert a["pairs_held"] <= a["pairs_routed"]
        assert a["experts_touched"] <= 8 * 3 * 4
        assert a["expert_load_max"] * 8 >= a["pairs_held"]
        assert a["expert_load_mean"] == pytest.approx(a["pairs_held"] / 8)
    assert sum(r.attrs["pairs_routed"] for r in enq) > 0
    admits = [r for r in rows if r.name == "engine.admit"]
    assert admits and all(
        r.attrs["state_bytes"] == r.attrs["admitted"] * eng.steps.slot_bytes
        for r in admits)


# -- positional leaves: what a cursor may do to a latent page -----------------

LATENT = dict(vocab=256, d_model=64, n_heads=4, d_head=16, d_ff=128,
              max_len=160, d_nope=16, d_rope=8, kv_rank=32, n_experts=16,
              held_lo=0, held_hi=16, d_expert=32, d_shared=32, top_k=4,
              n_group=1, topk_group=1, routed_scale=2.0, hc_mult=2,
              q_rank=24, mla_gate=False, mla_block=16)


@functools.lru_cache(maxsize=None)
def latent_setup():
    model = HybridLM(pattern=(("mla", "dense"), ("mla", "moe")), **LATENT)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def latent_engine(**over):
    model, params = latent_setup()
    cfg = dict(n_slots=3, capacity=144, buckets=(32, 64, 144), decode_k=4,
               prefill_cohort=2)
    return Engine(model, params, EngineConfig(**dict(cfg, **over)))


def serve_latent(eng):
    rs = np.random.RandomState(0)
    reqs = [eng.submit(rs.randint(0, 256, (n,)), max_new_tokens=m,
                       **({} if i % 2 == 0 else
                          dict(temperature=0.8, top_k=20, seed=i)))
            for i, (n, m) in enumerate([(37, 9), (90, 12), (21, 7), (64, 11),
                                        (130, 6)])]
    eng.run_until_drained()
    assert all(r.state == "done" for r in reqs)
    return [r.tokens for r in reqs]


@pytest.mark.parametrize("chunk,cohort", [(16, 1), (16, 2), (24, 2)])
def test_chunked_prefill_on_positional_leaves_serves_the_same_tokens(
        chunk, cohort):
    """A model whose declared leaves are all latent pages takes
    ``prefill_chunk``: one chunk program whatever the prompt lengths, one
    decode program, and the streams of the one-piece engine (greedy and
    sampled: a chunk that is not final consumes no key)."""
    want = serve_latent(latent_engine())
    eng = latent_engine(prefill_chunk=chunk, prefill_cohort=cohort)
    assert serve_latent(eng) == want
    assert eng.steps.prefill_chunk_traces == {(cohort, chunk): 1}
    assert eng.steps.decode_k_traces == 1 and not eng.steps.prefill_traces
    assert eng.idle() and sorted(eng.free_slots) == [0, 1, 2]


def test_chunks_stream_in_beside_decoding_slots_and_a_parked_page_is_kept():
    """A long prompt arrives in chunks while another slot decodes: the
    decoding slot's dispatches ride over the prefilling slot (parked at its
    cursor) without touching what its chunks have written."""
    model, params = latent_setup()
    rs = np.random.RandomState(1)
    short, long_ = rs.randint(0, 256, (20,)), rs.randint(0, 256, (120,))
    eng = latent_engine(prefill_chunk=16, prefill_cohort=1)
    a = eng.submit(short, max_new_tokens=40)
    b = eng.submit(long_, max_new_tokens=5)
    saw_both = 0
    while not eng.idle():
        eng.step()  # dlint: disable=DL104 — syncs via np.asarray
        saw_both += bool(eng.active) and bool(eng.prefilling)
    assert saw_both >= 5        # decode dispatches rode along the prefill
    alone = latent_engine()
    b2 = alone.submit(long_, max_new_tokens=5)
    alone.run_until_drained()
    assert b.tokens == b2.tokens
    a2 = alone.submit(short, max_new_tokens=40)
    alone.run_until_drained()
    assert a.tokens == a2.tokens


@pytest.mark.parametrize("cohort", [1, 2])
def test_the_chunk_span_names_the_attention_its_program_traced(
        cohort, profiler_session):
    """Every ``engine.admit`` span of a chunk dispatch says which form the
    chunk program's latent attention took when it was traced — here, off
    the chip and at widths that are no lane tiles, the ``jax.numpy`` loop
    and why — beside the pairs that attention computed; the step holds the
    same string from the trace on."""
    eng = latent_engine(prefill_chunk=16, prefill_cohort=cohort)
    assert eng.steps.chunk_attention is None        # nothing traced yet
    tracing.clear()
    with profiler_session():
        serve_latent(eng)
    rows = tracing.rows()
    tracing.clear()
    admits = [r for r in rows if r.name == "engine.admit"]
    assert admits and all(r.attrs["chunk"] == 16 for r in admits)
    how = eng.steps.chunk_attention
    assert how.startswith("loop:") and "multiples of 128" in how
    assert {r.attrs["chunk_attention"] for r in admits} == {how}
    assert all(r.attrs["attended_pairs"] > 0 for r in admits)
    assert eng.steps.prefill_chunk_traces == {(cohort, 16): 1}


@pytest.mark.parametrize("what", ["speculative", "int8-block", "ring wrap"])
def test_a_positional_page_still_refuses_what_has_no_program(what):
    """Per-column requantisation and wrap could be done to a latent page by
    cursor; the programs are not written, and the refusal says so (it does
    not blame a recurrent state the model has not got). Speculation with a
    SEPARATE draft model is refused by what serves such a model instead:
    its own module, in the decode program."""
    model, params = latent_setup()
    cfg = EngineConfig(n_slots=2, capacity=64, buckets=(32, 64))
    with pytest.raises(ValueError) as err:
        if what == "speculative":
            SpeculativeEngine(model, params, model, params, cfg, spec_k=2)
        elif what == "int8-block":
            serving_step(model, params, 2, 64, kv_dtype="int8-block")
        else:
            Engine(model, params, cfg).submit(np.arange(40),
                                              max_new_tokens=30)
    assert "recurrent state" not in str(err.value)
    assert ("self_draft" in str(err.value) if what == "speculative" else
            "no such program" in str(err.value)
            or "no ring wrap" in str(err.value))


def test_the_refusal_names_the_leaf_that_is_a_recurrence():
    from chainermn_tpu.serving.state_cache import (recurrent_leaves,
                                                   refuse_recurrent)

    model, params = setup()             # kda/dense, kda/moe, mla/moe, kda/moe
    leaves = recurrent_leaves(model)
    assert "block_0/kda/state" in leaves and "block_0/kda/conv" in leaves
    assert not any("ckv" in p or p == "idx" for p in leaves)
    assert recurrent_leaves(latent_setup()[0]) == []
    with pytest.raises(ValueError, match="recurrent state") as err:
        refuse_recurrent(model, "chunked prefill")
    assert "block_0/kda/conv" in str(err.value) or (
        "block_0/kda/state" in str(err.value))
    assert "HybridLM" in str(err.value)
    # the step of a model with a recurrent leaf refuses a chunk by itself too
    with pytest.raises(ValueError, match="recurrent state"):
        engine().steps.prefill_chunk(None, None, None, None, None, None,
                                     None, None)
    refuse_recurrent(latent_setup()[0], "chunked prefill",
                     positional_too=False)      # no leaf stands in the way
