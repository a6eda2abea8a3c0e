"""The three kinds of declared leaf through ``Engine`` and
``StateServingStep``: a model of K/V pages and K/V rings
(``models/hybrid.py``'s "gqa" and "swa" mixers) is chunk-prefilled, decoded
past the wrap, exported and imported; what each kind refuses, by name."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu import tracing
from chainermn_tpu.models.hybrid import HybridLM, layer_pattern
from chainermn_tpu.serving import Engine, EngineConfig
from chainermn_tpu.serving.state_cache import (StateServingStep, leaf_kinds,
                                               recurrent_leaves)

from tests.models_tests.test_hybrid import SIZES
from tests.models_tests.test_kv_window_mixers import WINDOW, model

CAP = 128


@functools.lru_cache(maxsize=None)
def setup():
    m = model(max_len=CAP)
    params = m.init(jax.random.PRNGKey(0),
                    jnp.zeros((1, 8), jnp.int32))["params"]
    return m, params


def engine(**over):
    m, params = setup()
    cfg = dict(n_slots=3, capacity=CAP, buckets=(32, 64, CAP), decode_k=4,
               prefill_cohort=1)
    return Engine(m, params, EngineConfig(**dict(cfg, **over)))


def serve(eng, lens, seed=0):
    rs = np.random.RandomState(seed)
    reqs = [eng.submit(rs.randint(0, 64, (n,)), max_new_tokens=k,
                       **({} if i % 2 == 0 else
                          dict(temperature=0.8, top_k=20, seed=i)))
            for i, (n, k) in enumerate(lens)]
    eng.run_until_drained()
    return reqs


def test_leaf_kinds_go_by_the_names_the_model_gives():
    m, _ = setup()
    kinds = leaf_kinds(m)
    assert kinds["block_0/gqa/k"] == kinds["block_4/gqa/v"] == "positional"
    assert kinds["block_1/swa/k_win"] == kinds["block_3/swa/v_win"] == "window"
    assert set(kinds.values()) == {"positional", "window"}
    assert "idx" not in kinds and recurrent_leaves(m) == []
    mixed = HybridLM(pattern=layer_pattern(4, 3, 1), **SIZES)
    kinds = leaf_kinds(mixed)
    assert kinds["block_0/kda/state"] == kinds["block_0/kda/conv"] == \
        "recurrent" and kinds["block_2/mla/ckv"] == "positional"


def test_a_ring_is_the_windows_length_whatever_the_capacity():
    eng = engine()
    cache = eng.steps.cache
    assert cache["block_0"]["gqa"]["k"].shape == (3, CAP, 2 * 16)
    assert cache["block_1"]["swa"]["k_win"].shape == (3, WINDOW, 2 * 16)
    # a slot: 2 pages and 3 rings of keys and values, float32, and a cursor
    assert eng.steps.slot_bytes == (2 * CAP + 3 * WINDOW) * 2 * 32 * 4 + 4


def test_chunks_into_pages_and_rings_serve_the_bucketed_streams():
    """Chunks of three windows beside decoding slots: a window leaf is
    chunk-written and wraps, and the streams are the bucketed engine's."""
    lens = [(59, 30), (7, 40), (40, 9), (24, 17), (100, 20)]
    want = serve(engine(), lens)
    eng = engine(prefill_chunk=3 * WINDOW)
    got = serve(eng, lens)
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert all(r.state == "done" for r in got)
    assert eng.steps.prefill_chunk_traces == {(1, 3 * WINDOW): 1}
    assert eng.steps.decode_k_traces == 1 and not eng.steps.prefill_traces


def test_a_positional_page_still_bounds_prompt_plus_output():
    eng = engine()
    assert "no ring wrap" in eng.steps.no_wrap
    with pytest.raises(ValueError, match="a declared page has no ring wrap"):
        eng.submit(np.arange(100) % 64, max_new_tokens=CAP - 99)
    eng.submit(np.arange(100) % 64, max_new_tokens=CAP - 100)
    # rings alone have nothing the capacity bounds
    rings = model(pattern=(("swa", "dense"), ("swa", "moe")), max_len=CAP)
    p = rings.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert StateServingStep(rings, p["params"], 2, CAP).no_wrap is None


def test_a_recurrent_leaf_still_refuses_chunks_by_its_name():
    mixed = HybridLM(pattern=layer_pattern(4, 3, 1), **SIZES)
    params = mixed.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match=r"chunked prefill is not available "
                       r"for HybridLM: its leaf 'block_0/kda/conv' \(and 5 "
                       r"more\) is a recurrent state"):
        Engine(mixed, params, EngineConfig(
            n_slots=2, capacity=64, buckets=(32, 64), prefill_chunk=16))


def test_self_drafting_refuses_a_window_leaf_by_its_name():
    m, params = setup()
    with pytest.raises(ValueError, match=r"self-drafting is not available "
                       r"for HybridLM: its leaf 'block_1/swa/k_win' is a "
                       r"window ring"):
        StateServingStep(m, params, 2, CAP, self_draft=True)
    with pytest.raises(ValueError, match="positional or window rings"):
        StateServingStep(m, params, 2, CAP, kv_dtype="int8-block")


def test_export_import_round_trip_of_pages_and_wrapped_rings():
    """A decoding session whose rings have wrapped moves to another engine
    leaf by leaf — a ring whole, addressed by the cursor that travels with
    it — and continues the exact stream."""
    rs = np.random.RandomState(2)
    prompt = rs.randint(0, 64, (21,))
    oracle = engine()
    want = oracle.submit(prompt, max_new_tokens=30)
    oracle.run_until_drained()

    src, dst = engine(), engine()
    req = src.submit(prompt, max_new_tokens=30)
    src.step()
    src.step()
    session = src.export_session(req)
    pages = session["pages"]
    assert session["cursor"] > 2 * WINDOW
    assert pages["block_0"]["gqa"]["k"].shape == (CAP, 32)
    assert pages["block_2"]["swa"]["v_win"].shape == (WINDOW, 32)
    dst.submit(rs.randint(0, 64, (9,)), max_new_tokens=3)   # takes slot 0
    dst.step()
    moved = dst.import_session(session, prompt)
    assert moved.slot != 0
    for name in ("block_1", "block_2", "block_3"):
        for leaf in ("k_win", "v_win"):
            assert np.array_equal(
                np.asarray(dst.steps.cache[name]["swa"][leaf][moved.slot]),
                pages[name]["swa"][leaf])
    src.release_held(req)
    dst.run_until_drained()
    assert moved.tokens == want.tokens


def test_read_counts_ride_on_the_decode_span(tmp_path):
    eng = engine(prefill_chunk=3 * WINDOW)
    rs = np.random.RandomState(4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tracing.clear()
        for n in (20, 5, 40):
            eng.submit(rs.randint(0, 64, (n,)), max_new_tokens=9)
        eng.run_until_drained()
        rows = tracing.rows()
    finally:
        jax.profiler.stop_trace()
    enq = [r for r in rows if r.name == "engine.decode.enqueue"]
    names = {"attn_rows_live", "attn_rows_wrapped", "attn_page_columns",
             "attn_ring_columns", "attn_fill_columns", "experts_touched"}
    assert enq and all(names <= set(r.attrs) for r in enq)
    for r in enq:
        a = r.attrs
        assert 0 < a["attn_rows_live"] <= a["live"] * 4       # decode_k 4
        assert a["attn_rows_wrapped"] <= a["attn_rows_live"]
        # every step reads the three rows' rings whole on three layers, and
        # one block (the page: 128 < the decode block) a live row and full
        # layer
        assert a["attn_ring_columns"] == 4 * 3 * 3 * WINDOW
        assert a["attn_page_columns"] == a["attn_rows_live"] * 2 * CAP
        assert a["attn_fill_columns"] >= a["filled_columns"] + a["attn_rows_live"]
    # the prompt of 5 had not wrapped when it started to decode
    assert any(r.attrs["attn_rows_wrapped"] < r.attrs["attn_rows_live"]
               for r in enq)
