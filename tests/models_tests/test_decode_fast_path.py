"""The single-query decode branch of every model that does not ask for
the reference kernel (``_grouped_cache_attention``: the page read once at
its stored dtype, query heads folded over the kv heads, both contractions
matmuls with f32 accumulation) against the oracle: the SAME step of the
SAME parameters with ``attention="reference"``, whose branch is bitwise a
row of the full forward (tests/models_tests/test_pos_offset.py,
tests/serving_tests). docs/serving.md §Numerics contract states the two
contracts; this file holds the second to the first."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import TransformerLM

VOCAB, D, HEADS, CAP, SLOTS = 43, 32, 4, 16, 3

# f32 pages: the two branches differ by the order of f32 sums alone.
# bf16 pages: both read the same bf16 page and accumulate in f32; the fast
# path also rounds q (already bf16 here) and p to bf16 for the MXU, a
# relative 2**-9 an element, and every later activation is rounded to
# bf16 (2**-8), so one such flip moves a logit of size 4 by 2**-6: the
# tolerance is three of those.
TOLERANCE = {"float32": dict(rtol=2e-5, atol=2e-5),
             "bfloat16": dict(rtol=2 ** -6, atol=3 * 2 ** -6)}

HEAD_LAYOUTS = {"mha": HEADS, "gqa4to1": 1, "gqa2to1": 2}
# the query's position in each slot: unequal fills, an empty slot, and
# (wrapped) cursors beyond the capacity, where slot j of the ring holds
# position row - ((row - j) mod cap)
CURSORS = {"scalar": 5, "scalar-wrapped": 21,
           "per-slot": [5, 11, 0], "per-slot-wrapped": [37, 16, 3]}


@functools.lru_cache(maxsize=None)
def _params(pos_emb, n_kv, n_layers):
    """One set of parameters for both sides: the oracle's full-forward
    init (f32; a bf16 model casts them where it computes)."""
    ref = TransformerLM(vocab=VOCAB, d_model=D, n_heads=HEADS,
                        n_kv_heads=n_kv, d_ff=64, n_layers=n_layers,
                        max_len=CAP, attention="reference", pos_emb=pos_emb)
    return ref.init(jax.random.PRNGKey(0),
                    jnp.zeros((SLOTS, 4), jnp.int32))["params"]


@functools.lru_cache(maxsize=None)
def _step(attention, dtype, pos_emb, n_kv, cursor, window, n_layers=2):
    """One ``decode=True`` step at ``CURSORS[cursor]`` over pages filled
    from a fixed seed: (logits [SLOTS, VOCAB] f32, cache after the step).
    Cached: a window case compares with the full case's step."""
    dtype = jnp.dtype(dtype)
    dm = TransformerLM(vocab=VOCAB, d_model=D, n_heads=HEADS,
                       n_kv_heads=n_kv, d_ff=64, n_layers=n_layers,
                       max_len=CAP, attention=attention, pos_emb=pos_emb,
                       attention_window=window, dtype=dtype, decode=True)
    tok = jnp.asarray([[1], [2], [3]], jnp.int32)
    pos = jnp.asarray(CURSORS[cursor], jnp.int32)
    shapes = jax.eval_shape(
        lambda: dm.init(jax.random.PRNGKey(0), tok))["cache"]
    rng = np.random.RandomState(7)
    cache = jax.tree_util.tree_map(
        lambda s: (pos if s.dtype == jnp.int32
                   else jnp.asarray(rng.randn(*s.shape), dtype)), shapes)
    # learned positions read pos_offset; rotary ones the cursor in the block
    logits, upd = dm.apply(
        {"params": _params(pos_emb, n_kv, n_layers), "cache": cache}, tok,
        pos_offset=pos, mutable=["cache"])
    return np.asarray(logits[:, 0], np.float32), upd["cache"]


# learned position tables end at max_len, so only rotary models wrap
POSITIONS = [(pos_emb, cursor) for pos_emb in ("learned", "rope")
             for cursor in CURSORS
             if pos_emb == "rope" or "wrapped" not in cursor]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [None, 4], ids=["full", "window4"])
@pytest.mark.parametrize("pos_emb,cursor", POSITIONS,
                         ids=["-".join(p) for p in POSITIONS])
@pytest.mark.parametrize("heads", list(HEAD_LAYOUTS))
def test_fast_step_matches_oracle_step(heads, pos_emb, cursor, window,
                                       dtype):
    args = (dtype, pos_emb, HEAD_LAYOUTS[heads], cursor, window)
    fast, fast_cache = _step("flash", *args)
    want, want_cache = _step("reference", *args)
    assert np.isfinite(fast).all()
    np.testing.assert_allclose(fast, want, **TOLERANCE[dtype])
    # the write precedes attention and is shared: block 0 sees the same
    # input on both sides, so its pages are the same bytes; block 1's
    # input has passed through block 0's attention
    for leaf in ("k", "v", "idx"):
        np.testing.assert_array_equal(
            np.asarray(fast_cache["block_0"][leaf], np.float32),
            np.asarray(want_cache["block_0"][leaf], np.float32))
        np.testing.assert_allclose(
            np.asarray(fast_cache["block_1"][leaf], np.float32),
            np.asarray(want_cache["block_1"][leaf], np.float32),
            **TOLERANCE[dtype])
    # and the comparison is not vacuous: the mask did something
    if window is not None:
        wide, _ = _step("flash", *args[:4], None)
        assert np.abs(wide - fast).max() > 1e-3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cursor", list(CURSORS))
@pytest.mark.parametrize("heads", list(HEAD_LAYOUTS))
def test_fast_step_through_the_kernel_matches_oracle_step(
        monkeypatch, heads, cursor, dtype):
    """The same step with the dispatcher's rule held open
    (``cache_decode_attention`` takes ``ops/page_attention.py::
    page_decode_fwd``, in the Pallas interpreter off the chip): the online
    softmax over blocks of the page is held to the tolerance the one-shot
    form is held to."""
    from chainermn_tpu.ops import latent_attention, page_attention

    monkeypatch.setattr(page_attention, "decode_refusal", lambda *a: None)
    args = (dtype, "rope", HEAD_LAYOUTS[heads], cursor, None)
    with latent_attention.record_paths() as paths:
        fast, fast_cache = _step.__wrapped__("flash", *args)
    assert set(paths) == {"kernel"}                 # every layer's call
    want, want_cache = _step("reference", *args)
    assert np.isfinite(fast).all()
    np.testing.assert_allclose(fast, want, **TOLERANCE[dtype])
    for leaf in ("k", "v", "idx"):
        np.testing.assert_array_equal(
            np.asarray(fast_cache["block_0"][leaf], np.float32),
            np.asarray(want_cache["block_0"][leaf], np.float32))


def test_positions_beyond_the_fill_reach_no_logit():
    """What a page holds beyond its slot's fill changes no bit of the
    logits, an empty slot (fill 0) included."""
    dm = TransformerLM(vocab=VOCAB, d_model=D, n_heads=HEADS, n_kv_heads=2,
                       d_ff=64, n_layers=1, max_len=CAP, attention="flash",
                       pos_emb="rope", decode=True)
    tok = jnp.asarray([[1], [2], [3]], jnp.int32)
    fill = np.asarray([5, 11, 0], np.int32)
    beyond = np.arange(CAP)[None, :, None, None] > fill[:, None, None, None]

    def run(poison):
        rng = np.random.RandomState(3)
        page = lambda: jnp.asarray(np.where(
            beyond, poison, rng.randn(SLOTS, CAP, 2, D // HEADS)),
            jnp.float32)
        cache = {"block_0": {"k": page(), "v": page(),
                             "idx": jnp.asarray(fill)}}
        logits, _ = dm.apply({"params": _params("rope", 2, 1),
                              "cache": cache}, tok, mutable=["cache"])
        return np.asarray(logits)

    # a dead key's score is replaced before the softmax, so its weight is
    # an exact zero and zero times any finite value adds nothing
    np.testing.assert_array_equal(run(0.0), run(1e30))


def _lowered_decode_step(attention):
    """StableHLO of the per-slot GQA decode step over bf16 pages."""
    b, cap, hkv, heads, dh = 4, 64, 2, 8, 16
    dm = TransformerLM(vocab=VOCAB, d_model=heads * dh, n_heads=heads,
                       n_kv_heads=hkv, d_ff=64, n_layers=1, max_len=cap,
                       attention=attention, pos_emb="rope",
                       dtype=jnp.bfloat16, decode=True)
    tok = jnp.zeros((b, 1), jnp.int32)
    params = jax.eval_shape(
        lambda: dm.clone(decode=False, attention="reference").init(
            jax.random.PRNGKey(0), jnp.zeros((b, 4), jnp.int32)))["params"]
    page = jax.ShapeDtypeStruct((b, cap, hkv, dh), jnp.bfloat16)
    cache = {"block_0": {"k": page, "v": page,
                         "idx": jax.ShapeDtypeStruct((b,), jnp.int32)}}
    text = jax.jit(lambda p, c, t: dm.apply(
        {"params": p, "cache": c}, t, mutable=["cache"])).lower(
        params, cache, tok).as_text()
    types = set(re.findall(r"tensor<([0-9x]+x(?:bf16|f32))>", text))
    widened = {f"{b}x{cap}x{heads}x{dh}x{t}" for t in ("bf16", "f32")}
    f32_page = {f"{b}x{cap}x{hkv}x{dh}xf32"}
    assert f"{b}x{cap}x{hkv}x{dh}xbf16" in types   # the search matches
    return types & widened, types & f32_page


def test_fast_step_never_widens_or_upcasts_the_page():
    """The defect PR 25 removed cannot return unseen: no value of shape
    [b, cap, n_heads, d] (the materialised ``jnp.repeat``) and no f32
    value of the page's shape (the upcast copy) in the lowered fast
    step; the reference step has both, so the search is known to see."""
    widened, f32_page = _lowered_decode_step("flash")
    assert not widened and not f32_page
    widened, f32_page = _lowered_decode_step("reference")
    assert widened and f32_page
