"""Latent attention for long prompts (models/hybrid.py: ``yarn_inv_freq``,
the low-rank query, ``latent_chunk_attention``, ``latent_decode_attention``)
at toy size on the CPU, against the benchmark's plain reference
(benchmark/references/xing_mhc.py): YaRN's frequencies and softmax scale
against hand-computed values, prefill in chunks against prefill in one piece
and against the reference's full forward — through the ``jax.numpy`` loop and,
at lane-aligned widths, through the kernel (ops/latent_attention.py, in the
Pallas interpreter) —, a decode step through the page at cursors 0, mid-block
and capacity - 1, that the chunk program holds no score array of chunk x
capacity a head in either form, and a ``decode_k`` dispatch through the decode
kernel against the same dispatch through the loop."""
import contextlib
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import xing_mhc as ref
from chainermn_tpu.models.hybrid import (HybridLM, MLAMixer,
                                         latent_decode_attention,
                                         yarn_inv_freq, yarn_mscale)
from chainermn_tpu.ops import latent_attention
from chainermn_tpu.serving.state_cache import (init_state_cache,
                                               state_decode_apply,
                                               state_prefill_apply,
                                               state_prefill_chunk_apply)

from tests.models_tests.test_hyper_connections import (PATTERN, SIZES, YARN,
                                                       reference_logits,
                                                       setup)

CAP = SIZES["max_len"]      # 96: six blocks of 16


def test_yarn_frequencies_at_factor_64_match_hand_computed_values():
    """d 64, theta 10000, original context 4096, beta 32/1: the correction
    range is floor(10.47) = 10 .. ceil(22.51) = 23 (pair indices); below it
    the plain frequency, above it that over 64, between a linear blend."""
    dim = lambda rot: 64 * math.log(4096 / (rot * 2 * math.pi)) / (
        2 * math.log(10000.0))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (10, 23)
    f = np.asarray(yarn_inv_freq(64, 10000.0, YARN), np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 64, rtol=1e-6)
    assert f[0] == 1.0
    np.testing.assert_allclose(f[10], 0.0562341, rtol=1e-5)
    # pair 16: plain 0.01, ramp 6/13
    np.testing.assert_allclose(
        f[16], 0.01 / 64 * (6 / 13) + 0.01 * (7 / 13), rtol=1e-5)
    np.testing.assert_allclose(f[16], 0.00545673, rtol=1e-5)
    np.testing.assert_allclose(f[31], 1.33352e-4 / 64, rtol=1e-4)
    assert (np.diff(f) < 0).all()
    np.testing.assert_allclose(f, np.asarray(ref.yarn_inv_freq(
        64, 10000.0, YARN)), rtol=1e-6)
    # the softmax scale: 192^-1/2 (0.1 ln 64 + 1)^2, cos and sin unscaled
    assert yarn_mscale(64, 1) == pytest.approx(1.4158883)
    assert 192 ** -0.5 * yarn_mscale(64, 1) ** 2 == pytest.approx(0.144680,
                                                                  rel=1e-5)
    assert ref.softmax_scale(dict(d_nope=128, d_rope=64, rope_scaling=YARN)
                             ) == pytest.approx(0.144680, rel=1e-5)
    assert yarn_mscale(64, 1) / yarn_mscale(64, 1) == 1.0
    assert yarn_mscale(1, 1) == 1.0


def test_the_scale_and_the_frequencies_reach_the_mixer():
    """With YaRN off the same weights give other numbers, and by more than
    the scale alone would (the frequencies differ too)."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(1, 40, 64), jnp.float32)
    pos = jnp.zeros((1,), jnp.int32)
    kw = dict(n_heads=4, d_nope=16, d_rope=8, d_v=16, kv_rank=32,
              rope_theta=1e4, max_len=64, q_rank=24, gate=False, block=16)
    on = MLAMixer(rope_scaling=YARN, **kw)
    p = on.init(jax.random.PRNGKey(0), x, pos)["params"]
    assert {"qa_proj", "q_norm", "qb_proj"} <= set(p) and "q_proj" not in p
    assert "g_proj" not in p
    a = np.asarray(on.apply({"params": p}, x, pos))
    b = np.asarray(MLAMixer(rope_scaling=None, **kw).apply(
        {"params": p}, x, pos))
    unit = dict(YARN, factor=1.0)       # factor 1: plain frequencies, scale 1
    c = np.asarray(MLAMixer(rope_scaling=unit, **kw).apply(
        {"params": p}, x, pos))
    assert np.abs(a - b).max() > 1e-3
    np.testing.assert_allclose(b, c, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="yarn"):
        MLAMixer(rope_scaling=dict(YARN, type="linear"), **kw).apply(
            {"params": p}, x, pos)


def test_low_rank_query_and_blocked_prefill_match_the_reference():
    """The mixer alone (low-rank query with its norm, YaRN, no gate, blocks
    of 16 columns) against the reference's expanded attention."""
    model, params = setup()
    m = params["block_1"]["mla"]
    rs = np.random.RandomState(1)
    y = jnp.asarray(rs.randn(2, 48, 64), jnp.float32)
    mixer = MLAMixer(4, 16, 8, 16, 32, 1e4, CAP, q_rank=24, gate=False,
                     rope_scaling=YARN, block=16)
    got = mixer.apply({"params": m}, y, jnp.zeros((2,), jnp.int32))
    from tests.models_tests.test_hyper_connections import ref_cfg
    with jax.default_matmul_precision("highest"):
        want = ref.mla_mixer(
            y, ref.canonical_layer(params["block_1"]), ref_cfg(model),
            jnp.matmul, ref.identity)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


#: widths the kernel serves (whole lane tiles); everything else as SIZES
ALIGNED = dict(n_heads=2, d_head=128, d_nope=128, d_rope=64, kv_rank=128)


@contextlib.contextmanager
def kernel_path():
    """Inside, ``latent_chunk_attention`` and ``latent_decode_attention``
    take their kernels as they do on the chip, and the kernels run in the
    Pallas TPU interpreter. Yields the list of paths the calls traced inside
    took (a serving step that traces a program keeps its own)."""
    from jax.experimental.pallas import tpu as pltpu

    with pytest.MonkeyPatch.context() as mp, \
            pltpu.force_tpu_interpret_mode(), \
            latent_attention.record_paths() as paths:
        mp.setattr(latent_attention, "on_tpu", lambda: True)
        yield paths


def prefill(model, params, tokens, chunks, cache=None):
    """One slot of a 2-slot cache (a fresh one unless handed in): the
    prompt in the given chunk sizes (a single size: the one-piece program).
    Returns (logits after the last piece, cache)."""
    dm = model.clone(decode=True, max_len=CAP)
    if cache is None:
        cache = init_state_cache(model, 2, CAP)
    if len(chunks) == 1:
        toks = np.zeros((1, chunks[0]), np.int32)
        toks[0, :tokens.size] = tokens
        return state_prefill_apply(dm, params, cache, jnp.asarray(toks),
                                   jnp.asarray([tokens.size]),
                                   jnp.asarray([1]))
    at, c = 0, max(chunks)
    chunk = jax.jit(lambda *a: state_prefill_chunk_apply(dm, *a))
    for n in chunks:
        toks = np.zeros((2, c), np.int32)
        toks[0, :n] = tokens[at:at + n]
        # row 1 is a sentinel row, as the engine pads a cohort
        logits, cache = chunk(
            params, cache, jnp.asarray(toks), jnp.asarray([at, 0]),
            jnp.asarray([n, 1]), jnp.asarray([1, 2]))
        at += n
    return logits[:1], cache


@pytest.mark.parametrize("chunks", [(16, 16, 16, 16, 9), (32, 32, 9),
                                    (24, 24, 24, 1)])
def test_chunked_prefill_equals_one_piece_and_the_reference(chunks):
    """73 tokens in chunks (aligned to the 16-column blocks, twice as wide,
    and across them) and in one piece: the same page (both programs visit a
    query's blocks in the same order with the same contents, so only the
    shapes of the products differ: a few float32 ulps), the same cursor,
    the same logits; and the reference's column 72."""
    model, params = setup()
    tokens = np.random.RandomState(5).randint(0, 256, (73,))
    one, cache1 = prefill(model, params, tokens, (80,))
    got, cache = prefill(model, params, tokens, chunks)
    assert cache["idx"].tolist() == cache1["idx"].tolist() == [0, 73]
    for i in range(3):
        a = np.asarray(cache[f"block_{i}"]["mla"]["ckv"])
        b = np.asarray(cache1[f"block_{i}"]["mla"]["ckv"])
        assert not a[0].any() and not a[1, 73:].any()   # nothing else written
        np.testing.assert_allclose(a[1, :73], b[1, :73], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got, one, rtol=1e-4, atol=1e-4)
    padded = np.zeros((1, 80), np.int64)
    padded[0, :73] = tokens
    want = reference_logits(model, params, padded)[0, 72]
    np.testing.assert_allclose(got[0], want, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("chunks", [(32, 32, 9), (24, 24, 24, 1)])
def test_chunked_prefill_through_the_kernel_equals_the_loop_and_one_piece(
        chunks):
    """The same 73 tokens at widths the kernel serves: every chunk call of
    the three layers takes the kernel (a short last chunk, a sentinel row
    beside the real one, the row in slot 1 of 2), and the pages, the cursor
    and the logits are those of the loop over one piece of 80."""
    model, params = setup(**ALIGNED)
    tokens = np.random.RandomState(5).randint(0, 256, (73,))
    # the cache's shapes come off a trace of the model's one-token call,
    # whose decode attention would note its path too: outside the scope
    cache = init_state_cache(model, 2, CAP)
    with kernel_path() as paths:
        got, cache = prefill(model, params, tokens, chunks, cache)
    assert paths == ["kernel"] * 3      # one trace serves every chunk
    one, cache1 = prefill(model, params, tokens, (80,))
    assert cache["idx"].tolist() == cache1["idx"].tolist() == [0, 73]
    for i in range(3):
        a = np.asarray(cache[f"block_{i}"]["mla"]["ckv"])
        b = np.asarray(cache1[f"block_{i}"]["mla"]["ckv"])
        assert a.shape[-1] == 256       # [c 128 | k_r 64 | 64 zeros]
        assert not a[0].any() and not a[1, 73:].any()
        np.testing.assert_allclose(a[1, :73], b[1, :73], rtol=1e-4,
                                   atol=1e-5)
    np.testing.assert_allclose(got, one, rtol=1e-4, atol=1e-4)
    padded = np.zeros((1, 80), np.int64)
    padded[0, :73] = tokens
    want = reference_logits(model, params, padded)[0, 72]
    np.testing.assert_allclose(got[0], want, rtol=5e-4, atol=5e-4)


def test_the_same_chunks_through_the_loop_off_the_chip():
    """The control of the test above: off the chip the same model's chunk
    calls take the loop, name why, and give the same logits."""
    model, params = setup(**ALIGNED)
    tokens = np.random.RandomState(5).randint(0, 256, (73,))
    cache = init_state_cache(model, 2, CAP)
    with latent_attention.record_paths() as paths:
        got, _ = prefill(model, params, tokens, (32, 32, 9), cache)
    assert paths == ["loop:not on a TPU"] * 3
    one, _ = prefill(model, params, tokens, (80,))
    np.testing.assert_allclose(got, one, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cursor", [0, 21, 32, CAP - 1])
def test_decode_through_the_page_is_the_references_column(cursor):
    """Prefill ``cursor`` tokens, then one decode step at that cursor: the
    reference's logits column there. 0: an empty page; 21: inside a block;
    32: a block's first column; capacity - 1: the page's last column."""
    model, params = setup()
    tokens = np.random.RandomState(6).randint(0, 256, (CAP,))
    dm = model.clone(decode=True, max_len=CAP)
    cache = init_state_cache(model, 2, CAP)
    if cursor:
        _, cache = prefill(model, params, tokens[:cursor], (cursor, 0)[:1]
                           if cursor <= 32 else (32,) * (cursor // 32)
                           + ((cursor % 32,) if cursor % 32 else ()))
    step = jnp.asarray([0, tokens[cursor]], jnp.int32)
    logits, cache, _ = state_decode_apply(
        dm, params, cache, step, jnp.asarray([False, True]))
    assert cache["idx"].tolist() == [0, cursor + 1]
    want = reference_logits(model, params, tokens[None])[0, cursor]
    np.testing.assert_allclose(logits[1], want, rtol=5e-4, atol=5e-4)


def test_decode_reads_the_blocks_the_live_rows_have_filled():
    """The blocked decode loop visits, row by row, the blocks a LIVE row
    has filled: a NaN planted past them (and in a row that is not live)
    never reaches the result, which equals the one-piece softmax over the
    seen columns."""
    rs = np.random.RandomState(7)
    b, h, r, dr, t = 3, 2, 8, 4, 64
    q = jnp.asarray(rs.randn(b, h, r + dr), jnp.float32)
    page = rs.randn(b, t, r + dr).astype(np.float32)
    pos = jnp.asarray([5, 20, 60])
    live = jnp.asarray([True, True, False])
    page[0, 16:] = np.nan           # row 0 reads block 0 alone
    page[1, 32:] = np.nan           # row 1 blocks 0 and 1
    page[2] = np.nan                # row 2 is not live: no block
    got, same = latent_decode_attention(q, jnp.asarray(page), pos, live,
                                        0.3, r, block=16)
    got = np.asarray(got)
    assert np.array_equal(np.asarray(same), page, equal_nan=True)
    assert np.isfinite(got[:2]).all() and not got[2].any()  # not visited
    for row in (0, 1):
        n = int(pos[row]) + 1
        s = np.einsum("hc,tc->ht", np.asarray(q[row]), page[row, :n]) * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ page[row, :n, :r]
        np.testing.assert_allclose(got[row], want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("form", ["loop", "kernel"])
def test_chunk_program_holds_no_score_array_over_the_page(form):
    """A mid size: 8 heads, a chunk of 512 queries, a page of 8,192 columns
    in blocks of 128. A float32 score array of chunk x capacity a head
    would be 8 x 512 x 8,192 x 4 B = 128 MB; the compiled chunk program's
    temporaries stay under a quarter of that, and no array in its HLO has
    both a chunk and a capacity axis beside the heads. The loop's largest
    score array is a chunk by a block a head; the kernel's program (its
    body interpreted here: tests/ops_tests/test_grouped_swiglu_compile.py
    compiles it for the chip) holds no float32 array with the heads, the
    chunk and a block of columns at all: a step scores one head."""
    cap, c, h = 8192, 512, 8
    over = dict(SIZES, n_heads=h, max_len=cap, mla_block=128)
    if form == "kernel":
        over.update(ALIGNED, n_heads=h)
    model = HybridLM(**over, pattern=PATTERN[:2])
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    dm = model.clone(decode=True, max_len=cap)
    cache = jax.eval_shape(lambda: init_state_cache(model, 2, cap))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    with kernel_path() if form == "kernel" else \
            latent_attention.record_paths() as paths:
        compiled = jax.jit(
            lambda p, ca, t, s, v, ids: state_prefill_chunk_apply(
                dm, p, ca, t, s, v, ids), donate_argnums=(1,)).lower(
            shapes, cache, i32(1, c), i32(1), i32(1), i32(1)).compile()
    assert len(paths) == 2 and all(
        p == "kernel" if form == "kernel" else p.startswith("loop:")
        for p in paths)
    whole = h * c * cap * 4
    assert compiled.memory_analysis().temp_size_in_bytes < whole // 4
    text = compiled.as_text()
    shapes_seen = {tuple(int(n) for n in dims.split(","))
                   for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    assert shapes_seen, "no float32 array in the program's text"
    assert not [s for s in shapes_seen if c in s and cap in s]
    if form == "loop":
        assert [s for s in shapes_seen if c in s and 128 in s]  # the blocks
    else:
        tile = min(latent_attention.COLUMN_TILE, cap)
        assert not [s for s in shapes_seen
                    if h in s and c in s and tile in s]


#: eight heads: the decode kernel takes query-heads in eights
DECODE_ALIGNED = dict(ALIGNED, n_heads=8)


def served(model, params, n_new=9):
    """Two prompts through a 3-slot engine, ``decode_k`` 4, one greedy and
    one sampled: (streams, the last dispatch's logits, the step)."""
    from chainermn_tpu.serving import Engine, EngineConfig

    eng = Engine(model, params, EngineConfig(
        n_slots=3, capacity=CAP, buckets=(32, CAP), decode_k=4,
        prefill_cohort=2))
    rs = np.random.RandomState(8)
    reqs = [eng.submit(rs.randint(0, 256, (n,)).astype(np.int32),
                       max_new_tokens=n_new, temperature=temp, seed=3)
            for n, temp in ((21, None), (30, 1.0))]
    eng.run_until_drained()
    return ([list(r.tokens) for r in reqs], np.asarray(eng.last_logits),
            eng.steps)


def test_a_decode_k_dispatch_through_the_kernel_gives_the_loops_tokens():
    """Three latent layers, one query a row, a free slot beside the two
    live ones: the kernel path's streams are the loop path's and the last
    dispatch's logits agree within a decode step's tolerance."""
    model, params = setup(**DECODE_ALIGNED)
    with kernel_path():
        got, logits, steps = served(model, params)
    assert steps.decode_attention == "kernel" and steps.decode_k_traces == 1
    want, want_logits, steps = served(model, params)
    assert steps.decode_attention == "loop:not on a TPU"
    assert got == want and all(len(t) == 9 for t in got)
    np.testing.assert_allclose(logits[:2], want_logits[:2], rtol=5e-4,
                               atol=5e-4)


@pytest.mark.parametrize("form", ["loop", "kernel"])
def test_decode_program_holds_no_copy_of_the_page_and_no_score_array(form):
    """A mid size: 8 heads, 16 slots, pages of 4,096 columns (16 x 4,096 x
    256 x 4 B = 64 MB a layer), one decode step with the pages donated,
    both latent layers through the form asked for. The loop's program keeps
    its temporaries under one page — the page is written and read where it
    lies — and holds the ``[block, heads]`` float32 score array of a visit.
    The kernel's body is interpreted here: the interpreter stages the page
    in a buffer of its own and its score tile is an array like any other,
    so that the kernel's program holds neither in HBM is for
    tests/ops_tests/test_grouped_swiglu_compile.py, which compiles the same
    call for the chip at both cells' shapes; here it lowers, by the same
    entry point, with both calls on the kernel."""
    from chainermn_tpu.serving.state_cache import state_decode_apply

    cap, h, n = 4096, 8, 16
    model = HybridLM(**dict(SIZES, **DECODE_ALIGNED, max_len=cap,
                            mla_block=128), pattern=PATTERN[:2])
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    dm = model.clone(decode=True, max_len=cap)
    cache = jax.eval_shape(lambda: init_state_cache(model, n, cap))
    with kernel_path() if form == "kernel" else \
            latent_attention.record_paths() as paths:
        compiled = jax.jit(
            lambda p, ca, t, live: state_decode_apply(dm, p, ca, t, live),
            donate_argnums=(1,)).lower(
            shapes, cache, jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.bool_)).compile()
    assert len(paths) == 2 and all(
        p == "kernel" if form == "kernel" else p.startswith("loop:")
        for p in paths)
    if form == "kernel":
        return
    page_bytes = n * cap * 256 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < page_bytes
    shapes_seen = {tuple(int(d) for d in dims.split(","))
                   for dims in re.findall(r"f32\[([\d,]+)\]",
                                          compiled.as_text())}
    assert [s for s in shapes_seen if s == (cap, h)]    # a visit's scores
