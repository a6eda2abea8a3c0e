"""The "gqa" (K/V page) and "swa" (K/V ring) mixers of ``models/hybrid.py``
through ``StateServingStep``, against ``benchmark/references/laguna_mixed.py``
— plain float32, an explicit mask, no cache, written apart from the program.

Sizes: 5 layers in the served pattern (full, window, window, window, full;
the first dense, the rest routed), 6 and 8 query heads over 2 KV heads of 16,
window 8, 8 experts top-2, capacity 64.

Tolerance, one for every comparison with the reference: 2e-4 on logits of
unit scale. A float32 program and a float32 reference that sum in different
orders (blocks under an online softmax against one softmax a row) read 3e-6
to 2e-5 here; K/V leaves rounded through bfloat16 read 2e-3 and more, a
window layer that attends the whole cache 1e-2 and more.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import laguna_mixed as ref
from chainermn_tpu.models.hybrid import (GQAMixer, HybridLM, rope_halves)
from chainermn_tpu.ops import kv_attention
from chainermn_tpu.parallel.expert_share import HeldExperts
from chainermn_tpu.serving.state_cache import (
    StateServingStep, init_state_cache, state_decode_apply,
    state_prefill_apply, state_prefill_chunk_apply)

TOL = 2e-4
WINDOW, CAP, VOCAB = 8, 64, 64
PATTERN = (("gqa", "dense"), ("swa", "moe"), ("swa", "moe"), ("swa", "moe"),
           ("gqa", "moe"))
YARN = {"rope_type": "yarn", "factor": 64.0, "beta_fast": 64, "beta_slow": 1,
        "original_max_position_embeddings": 16,
        "attention_factor": 1.4158883083359672}


@pytest.fixture(autouse=True, scope="module")
def chunk_blocks_of_two_windows():
    """A chunk call walks the page in blocks of 16 columns here (512 in the
    module), so that a toy page is several blocks."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kv_attention, "CHUNK_BLOCK", 16)
        yield


def model(**kw):
    args = dict(
        vocab=VOCAB, d_model=32, n_heads=6, d_head=16, pattern=PATTERN,
        d_ff=64, max_len=CAP, n_experts=8, held_lo=0, held_hi=8, d_expert=16,
        d_shared=16, top_k=2, routed_scale=2.5, n_kv_heads=2, gqa_heads=6,
        swa_heads=8, gqa_rotary=0.5, gqa_theta=5e5, swa_theta=1e4,
        gqa_scaling=YARN, window=WINDOW, attn_gate=True)
    args.update(kw)
    return HybridLM(**args)


def ref_cfg(m, window=WINDOW):
    return {"n_kv_heads": m.n_kv_heads, "d_head": m.d_head, "top_k": m.top_k,
            "routed_scale": m.routed_scale, "norm_eps": m.norm_eps,
            "pattern": m.pattern, "q_block": 8,
            "kinds": {
                "gqa": {"n_heads": m.gqa_heads, "rotary": m.gqa_rotary,
                        "theta": m.gqa_theta, "scaling": dict(m.gqa_scaling)},
                "swa": {"n_heads": m.swa_heads, "rotary": m.swa_rotary,
                        "theta": m.swa_theta, "window": window}}}


@pytest.fixture(scope="module")
def setup():
    m = model()
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                         VOCAB))
    params = m.init(jax.random.PRNGKey(0), toks[:, :8])["params"]
    # a router bias that is not zero, as a served model's
    for i in range(1, 5):
        params[f"block_{i}"]["moe"]["router_bias"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(10 + i), (8,))
    layers = [ref.canonical_layer(params[f"block_{i}"]) for i in range(5)]
    rest = ref.canonical_rest(params)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(jnp.asarray(toks), layers, rest,
                                      ref_cfg(m)))
    return m, params, toks, want, (layers, rest)


def serve(m, params, toks, lens, steps, spoil=None):
    """Bucketed prefill of ``lens`` tokens a row, then ``steps`` decode steps
    teacher-forced on ``toks``: [(position, logits [B, vocab])]."""
    step = StateServingStep(m, params, len(lens), CAP)
    pad = np.zeros((len(lens), 32), np.int32)
    for b, n in enumerate(lens):
        pad[b, :n] = toks[b, :n]
    out = [(np.asarray(lens) - 1, np.asarray(step.prefill(
        pad, np.asarray(lens, np.int32), np.arange(len(lens)))))]
    if spoil is not None:
        step.cache = spoil(step.cache)
    cur = np.asarray(lens)
    for _ in range(steps):
        logits = step.decode(toks[np.arange(len(lens)), cur].astype(np.int32))
        # the logits ARE what is compared with the reference, row by row
        out.append((cur.copy(), np.asarray(logits)))  # dlint: disable=DL110
        cur = cur + 1
    return out, step


def worst(out, want):
    return max(float(np.abs(got[b] - want[b, at[b]]).max())
               for at, got in out for b in range(got.shape[0]))


def test_prefill_and_decode_past_five_windows_match_the_reference(setup):
    m, params, toks, want, _ = setup
    out, step = serve(m, params, toks, [13, 6], 42)
    assert int(step.cursors()[0]) == 55 > 5 * WINDOW
    assert worst(out, want) < TOL


def test_leaves_rounded_through_bfloat16_fail_the_tolerance(setup):
    m, params, toks, want, _ = setup

    def spoil(cache):
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
            if a.dtype == jnp.float32 else a, cache)

    out, _ = serve(m, params, toks, [13, 6], 4, spoil)
    assert worst(out[1:], want) > 5 * TOL


def test_the_window_mask_is_not_vacuous_and_the_full_layers_ignore_it(setup):
    m, params, toks, want, (layers, rest) = setup
    with jax.default_matmul_precision("highest"):
        wide = np.asarray(ref.forward(jnp.asarray(toks), layers, rest,
                                      ref_cfg(m, window=0)))
    # inside the first window both are the same model; past it they are not
    assert np.abs(wide[:, :WINDOW] - want[:, :WINDOW]).max() < TOL
    assert np.abs(wide[:, 2 * WINDOW:] - want[:, 2 * WINDOW:]).max() > 50 * TOL
    out, _ = serve(m, params, toks, [13, 6], 30)
    assert worst(out, wide) > 50 * TOL          # the program has the window
    # a model of full layers alone does not read the field
    full = model(pattern=(("gqa", "dense"), ("gqa", "moe")))
    p = full.init(jax.random.PRNGKey(0), toks[:, :8])["params"]
    a = full.apply({"params": p}, toks)
    b = full.clone(window=3).apply({"params": p}, toks)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("lens", [(59,), (24,), (7,), (41,)])
def test_chunked_prefill_equals_monolithic_leaf_for_leaf(setup, lens):
    """Chunks of three windows: a last partial chunk, a chunk that starts
    inside a wrapped ring (start 24 and 48 are no first write of a column),
    a prompt of one chunk exactly and one under a window."""
    m, params, toks, _, _ = setup
    n, c = lens[0], 3 * WINDOW
    dm = m.clone(decode=True, max_len=CAP)
    cache = init_state_cache(m, 3, CAP)
    slot = np.array([1], np.int32)
    pad = np.zeros((1, 64), np.int32)
    pad[0, :n] = toks[0, :n]
    want_last, want = state_prefill_apply(dm, params, cache, pad,
                                          np.array([n], np.int32), slot)
    got = cache
    for start in range(0, n, c):
        valid = min(c, n - start)
        chunk = np.zeros((1, c), np.int32)
        chunk[0, :valid] = toks[0, start:start + valid]
        last, got = state_prefill_chunk_apply(
            dm, params, got, chunk, np.array([start], np.int32),
            np.array([valid], np.int32), slot)
    np.testing.assert_allclose(np.asarray(last), np.asarray(want_last),
                               atol=TOL)
    assert int(got["idx"][1]) == int(want["idx"][1]) == n
    flat = jax.tree_util.tree_flatten_with_path
    for (path, a), (_, b) in zip(flat(got)[0], flat(want)[0]):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "idx":
            continue
        a, b = np.asarray(a), np.asarray(b)
        used = slice(0, n if name in ("k", "v") else min(n, WINDOW))
        np.testing.assert_allclose(a[1, used], b[1, used], atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
        # the other slots' rows were not touched
        assert not a[0].any() and not a[2].any()


def test_sizes_differ_by_kind(setup):
    m, params, _, _, _ = setup
    full, win = params["block_0"]["gqa"], params["block_1"]["swa"]
    assert full["q_proj"]["kernel"].shape == (32, 6 * 16)
    assert win["q_proj"]["kernel"].shape == (32, 8 * 16)
    assert full["g_proj"]["kernel"].shape == (32, 6)
    assert win["g_proj"]["kernel"].shape == (32, 8)
    for mix in (full, win):
        assert mix["k_proj"]["kernel"].shape == (32, 2 * 16)
        assert mix["o_proj"]["kernel"].shape[1] == 32
    kw = dict(n_kv_heads=2, d_head=16, max_len=CAP)
    f_freq, f_scale = GQAMixer(6, rope_theta=5e5, rotary=0.5,
                               rope_scaling=YARN, **kw)._rotation()
    w_freq, w_scale = GQAMixer(8, rope_theta=1e4, window=WINDOW,
                               **kw)._rotation()
    assert f_freq.shape == (4,) and w_freq.shape == (8,)
    assert f_scale == YARN["attention_factor"] and w_scale == 1.0
    np.testing.assert_allclose(w_freq, 1e4 ** (-np.arange(8) / 8.0), rtol=1e-6)
    # the slowest pair is interpolated by the factor, the fastest is not
    plain = 5e5 ** (-np.arange(4) / 4.0)
    np.testing.assert_allclose(f_freq[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(f_freq[-1], plain[-1] / 64.0, rtol=1e-6)
    # a full layer's query: the second half of the head is not rotated
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 5, 6, 16))
    y = rope_halves(x, jnp.arange(5)[None] + 7, f_freq, f_scale)
    np.testing.assert_array_equal(np.asarray(y[..., 8:]), np.asarray(x[..., 8:]))
    assert np.abs(np.asarray(y[..., :8] - x[..., :8])).max() > 0.1


def test_a_row_that_is_not_live_keeps_page_ring_and_cursor(setup):
    m, params, toks, _, _ = setup
    _, step = serve(m, params, toks, [13, 11], 3)
    dm = m.clone(decode=True, max_len=CAP)
    before = jax.tree_util.tree_map(np.asarray, step.cache)
    _, after, stats = state_decode_apply(
        dm, params, step.cache, jnp.asarray([5, 9], jnp.int32),
        jnp.asarray([True, False]))
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(a[1], np.asarray(b)[1])
        assert not np.array_equal(a[0], np.asarray(b)[0])
    # 16 -> 17 and 14 held: one live row, wrapped; one block of the page a
    # full layer, the whole ring a window layer and row
    assert {k: int(v) for k, v in stats.items() if k.startswith("attn_")} == {
        "attn_rows_live": 1, "attn_rows_wrapped": 1,
        "attn_page_columns": 2 * CAP, "attn_ring_columns": 3 * 2 * WINDOW,
        "attn_fill_columns": 17}


def test_a_right_padded_prefill_row_stops_at_its_length(setup):
    m, params, toks, _, _ = setup
    dm = m.clone(decode=True, max_len=CAP)
    cache = init_state_cache(m, 2, CAP)
    n = 13
    pad = np.zeros((1, 32), np.int32)
    pad[0, :n] = toks[0, :n]
    pad[0, n:] = 3          # what a row must not read or keep
    sid = np.array([0], np.int32)
    _, padded = state_prefill_apply(dm, params, cache, pad,
                                    np.array([n], np.int32), sid)
    _, exact = state_prefill_apply(dm, params, cache, pad[:, :n],
                                   np.array([n], np.int32), sid)
    for a, b in zip(jax.tree_util.tree_leaves(padded),
                    jax.tree_util.tree_leaves(exact)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    k = np.asarray(padded["block_0"]["gqa"]["k"])
    assert k[0, :n].any(axis=-1).all() and not k[0, n:].any()
    # the ring holds positions 5..12, each at its position mod 8
    ring = np.asarray(padded["block_1"]["swa"]["k_win"])[0]
    page = np.asarray(state_prefill_apply(
        m.clone(decode=True, max_len=CAP, window=CAP), params,
        init_state_cache(m.clone(window=CAP), 2, CAP), pad,
        np.array([n], np.int32), sid)[1]["block_1"]["swa"]["k_win"])[0]
    for pos in range(n - WINDOW, n):
        np.testing.assert_allclose(ring[pos % WINDOW], page[pos], atol=1e-6)


def test_every_expert_held_equals_the_uncut_reference_layer():
    layer = HeldExperts(8, 0, 8, 16, 2, 1, 1, 2.5)
    x = jax.random.normal(jax.random.PRNGKey(2), (24, 32))
    p = layer.init(jax.random.PRNGKey(0), x)["params"]
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    got, stats = layer.apply({"params": p}, x)
    cfg = {"top_k": 2, "routed_scale": 2.5}
    with jax.default_matmul_precision("highest"):
        want = ref.routed_ffn(x, p, cfg, jnp.matmul)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert int(stats.pairs_held) == int(stats.pairs_routed) == 48


def test_an_unknown_mixer_kind_raises():
    m = model(pattern=(("gqa", "dense"), ("ssm", "dense")))
    with pytest.raises(ValueError, match="unknown mixer kind 'ssm'"):
        m.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    # a kind's sizes are the model's to give: none falls back to another's
    for missing, said in ((dict(window=0), "a 'swa' layer needs"),
                          (dict(gqa_heads=0), "gqa_heads and gqa_theta"),
                          (dict(swa_theta=None), "swa_heads and swa_theta")):
        with pytest.raises(ValueError, match=said):
            model(**missing).init(jax.random.PRNGKey(0),
                                  np.zeros((1, 8), np.int32))


# ---------------------------------------------------------------------------
# the chunk attention's dispatchers, through the engine
# ---------------------------------------------------------------------------

def lane_wide_engine():
    """The served pattern at a head of 128 (the least the chunk kernel
    takes), 4 and 6 query heads over 2 KV heads, window 8, chunks of two
    windows into 3 slots of 64."""
    from chainermn_tpu.serving import Engine, EngineConfig

    m = model(d_head=128, gqa_heads=4, swa_heads=6)
    params = m.init(jax.random.PRNGKey(0),
                    np.zeros((1, 8), np.int32))["params"]
    return Engine(m, params, EngineConfig(
        n_slots=3, capacity=CAP, buckets=(CAP,), decode_k=4,
        prefill_chunk=2 * WINDOW, prefill_cohort=2))


def streams(eng):
    """Five prompts — under a window, a chunk exactly, several chunks with a
    partial last one — greedy and sampled in turn."""
    rs = np.random.RandomState(0)
    reqs = [eng.submit(rs.randint(0, VOCAB, (n,)), max_new_tokens=new,
                       **({} if i % 2 == 0 else
                          dict(temperature=0.8, top_k=20, seed=i)))
            for i, (n, new) in enumerate([(5, 6), (16, 4), (37, 7), (23, 5),
                                          (44, 6)])]
    eng.run_until_drained()
    assert all(r.state == "done" for r in reqs)
    return [list(r.tokens) for r in reqs]


def test_chunk_spans_name_the_loop_and_the_kernel_serves_the_same_tokens(
        profiler_session, monkeypatch):
    """Every ``engine.admit`` span of a chunk dispatch names the form the
    chunk program's K/V attention took when it was traced: off the chip the
    ``jax.numpy`` bodies, all five layers for the one reason. With the rule
    alone patched the same engine takes the kernel on all five (its body in
    the Pallas interpreter) and serves the same streams: slots that permute,
    a cohort with a sentinel row, last chunks that are partial, rings not
    yet full and wrapped."""
    from chainermn_tpu import tracing
    from chainermn_tpu.ops import latent_attention

    eng = lane_wide_engine()
    assert eng.steps.chunk_attention is None        # nothing traced yet
    tracing.clear()
    with profiler_session():
        want = streams(eng)
    rows = tracing.rows()
    tracing.clear()
    admits = [r for r in rows if r.name == "engine.admit"]
    assert len(admits) >= 5
    assert {r.attrs["chunk_attention"] for r in admits} == {
        "loop:not on a TPU"}
    assert eng.steps.chunk_attention == "loop:not on a TPU"
    assert eng.steps.prefill_chunk_traces == {(2, 2 * WINDOW): 1}
    monkeypatch.setattr(latent_attention, "on_tpu", lambda: True)
    eng = lane_wide_engine()
    got = streams(eng)
    assert eng.steps.chunk_attention == "kernel"
    assert got == want
