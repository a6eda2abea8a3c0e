"""The chunk attention over flat K/V leaves as a kernel (ops/kv_attention.py::
kv_chunk_fwd) in the Pallas interpreter at small lane-aligned widths: equal to
the ``jax.numpy`` bodies it replaces (``_page_chunk_loop`` over a page,
``_ring_chunk_tiles`` over a ring, called directly) and to a dense masked
softmax (tests/ops_tests/attention_oracle.py), at every place a cursor can
stand; what lies past a cursor, past ``valid``, outside the band or in
another row never shows; the leaves come back byte for byte; and the
dispatchers' rule, reason by reason.
tests/ops_tests/test_grouped_swiglu_compile.py compiles the kernel for the
chip at the benchmark's widths."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.ops_tests.attention_oracle import masked_attention_oracle
from chainermn_tpu.ops import kv_attention as kv
from chainermn_tpu.ops import latent_attention as la
from chainermn_tpu.ops.page_write import partitioned_pages

D, NKV = 128, 2             # two KV heads: the second's lanes start at 128
N, T, C = 3, 80, 32         # 80 columns: blocks of 32 leave a last one of 16
BK, TQ = 32, 16             # the tiles of these tests, unless a test says
W = 16                      # the ring: a chunk of 32 is two windows
SCALE = 0.09
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 1e-3}
#: the tiles round an UNNORMALISED probability to bfloat16 where the ring's
#: ``jax.numpy`` tiles round a normalised one: a bfloat16 ulp apart
RING_TOL = {jnp.float32: 2e-6, jnp.bfloat16: 1.5e-2}


def draw(dtype, b, seed=0, g=6, t=T, c=C, d=D, rows=N):
    """(queries ``[b, c, NKV g, d]``, a K and a V leaf ``[rows, t, NKV
    d]``)."""
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32).astype(dtype)
    return f(b, c, NKV * g, d), f(rows, t, NKV * d), f(rows, t, NKV * d)


def page_kernel(q, k, v, pos, valid, slots, bk=BK, tq=TQ):
    pos = jnp.asarray(pos, jnp.int32)
    return np.asarray(kv.kv_chunk_fwd(
        q, k, v, pos, jnp.zeros_like(pos), jnp.asarray(valid, jnp.int32),
        jnp.asarray(slots, jnp.int32), SCALE, column_tile=bk, query_tile=tq))


def page_loop(q, k, v, pos, slots, monkeypatch):
    monkeypatch.setattr(kv, "CHUNK_BLOCK", 32)
    return np.asarray(kv._page_chunk_loop(
        q, k, v, jnp.asarray(pos, jnp.int32), jnp.asarray(slots, jnp.int32),
        SCALE)[0])


def dense(q, keys, values, seen_from, window=None):
    """Row 0 of the oracle's dense masked softmax: ``keys``/``values [L,
    NKV d]`` the positions up to the last query's, the ``C`` queries the last
    ``C`` of them (zeros stand before them as queries nobody reads);
    ``seen_from`` the first key that holds a position."""
    lk, c = keys.shape[0], q.shape[1]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    q_all = jnp.concatenate([jnp.zeros((1, lk - c) + q.shape[2:]),
                             f32(q[:1])], 1)
    kv_seg = (jnp.arange(lk) < seen_from).astype(jnp.int32)[None]
    out = masked_attention_oracle(
        q_all, f32(keys).reshape(1, lk, NKV, -1),
        f32(values).reshape(1, lk, NKV, -1), jnp.zeros((1, lk), jnp.int32),
        kv_seg, True, window, SCALE)
    return np.asarray(out[0, lk - c:])


# (pos, valid, slots, query heads a KV head, leaf columns, column tile)
PAGE_CASES = {
    "cursor-0": ([0], [C], [1], 6, T, BK),
    "cursor-1": ([1], [C], [2], 6, T, BK),
    "cursor-bk-1": ([BK - 1], [C], [0], 6, T, BK),
    "cursor-bk": ([BK], [C], [0], 8, T, BK),
    "cursor-6144": ([6144], [C], [1], 6, 8192, 2048),
    "capacity-minus-C": ([T - C], [C], [0], 6, T, BK),  # columns 48..79 of 80
    "valid-1": ([40], [1], [1], 6, T, BK),
    "valid-inside-a-tile": ([40], [9], [1], 8, T, BK),
    "slots-permute-and-skip": ([40, 5], [C, C], [2, 0], 6, T, BK),
    "sentinel-row": ([37, 0], [C, 1], [1, N], 6, T, BK),
    "cohort-of-2": ([11, 48], [20, C], [0, 1], 8, T, BK),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(PAGE_CASES))
def test_page_kernel_equals_the_loop_and_the_dense_softmax(case, dtype,
                                                           monkeypatch):
    """Same operands, same roundings, the loop's blocks in the loop's order:
    a few float32 ulps. Rows past ``valid`` and a sentinel row come back
    zero."""
    pos, valid, slots, g, t, bk = PAGE_CASES[case]
    q, k, v = draw(dtype, len(pos), seed=len(case), g=g, t=t)
    got = page_kernel(q, k, v, pos, valid, slots, bk=bk)
    want = page_loop(q, k, v, pos, slots, monkeypatch)
    assert got.shape == want.shape == (len(pos), C, NKV * g, D)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    for b, (n, s) in enumerate(zip(valid, slots)):
        if s >= N:
            assert not got[b].any()
            continue
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=TOL[dtype],
                                   atol=TOL[dtype])
        assert not got[b, n:].any()
    if dtype == jnp.float32 and pos[0] + C <= T:
        top = pos[0] + C
        np.testing.assert_allclose(
            got[0, :valid[0]],
            dense(q, k[slots[0], :top], v[slots[0], :top], 0)[:valid[0]],
            rtol=1e-5, atol=1e-5)


# (pos, valid, slots) on a ring of W columns and N rows
RING_CASES = {
    "no-ring-behind": ([0], [C], [1]),
    "a-ring-not-yet-full": ([5], [C], [0]),
    "ring-one-short-of-full": ([W - 1], [C], [2]),
    "ring-just-full": ([W], [C], [2]),
    "pos-100-wrapped": ([100], [C], [1]),
    "pos-5000-wrapped": ([5000], [C], [0]),
    "short-last-chunk": ([37], [11], [1]),
    "cohort-with-a-sentinel": ([100, 3, 0], [C, 20, C], [2, 0, N]),
}


def ring_of(hist, pos, fill=np.nan):
    """A ring row ``[W, w]`` holding ``hist`` (the ``min(pos, W)`` positions
    before ``pos``) at ``position mod W``; a column that holds no position
    holds ``fill``."""
    row = np.full((W, hist.shape[-1]), fill, np.float32)
    for i, p in enumerate(range(pos - hist.shape[0], pos)):
        row[p % W] = hist[i]
    return row


@pytest.mark.parametrize("g", [6, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(RING_CASES))
def test_ring_kernel_equals_the_tiles_and_the_banded_softmax(case, dtype, g):
    """Through the dispatcher's own lay-out of the ring (``q0 = W``, ``first
    = max(W - pos, 0)``, the window static), against the ``jax.numpy`` tiles
    and — band edges and all — the oracle's dense softmax over the last
    window and the chunk. The columns of a ring not yet full that hold no
    position hold NaN here: they lie before ``first``."""
    pos, valid, slots = RING_CASES[case]
    b = len(pos)
    rs = np.random.RandomState(len(case) + g)
    f = lambda *s: np.asarray(rs.randn(*s), np.float32)
    q = jnp.asarray(f(b, C, NKV * g, D)).astype(dtype)
    k, v = (jnp.asarray(f(b, C, NKV * D)).astype(dtype) for _ in "kv")
    hist = [(f(min(p, W), NKV * D), f(min(p, W), NKV * D)) for p in pos]
    rings = []
    for which in (0, 1):
        ring = np.zeros((N, W, NKV * D), np.float32)
        for h, p, s in zip(hist, pos, slots):
            if s < N:
                ring[s] = ring_of(h[which], p)
        rings.append(jnp.asarray(ring).astype(dtype))
    pos_, slots_ = jnp.asarray(pos, jnp.int32), jnp.asarray(slots, jnp.int32)
    got = np.asarray(kv.kv_chunk_fwd(
        q, kv._ring_then_chunk(rings[0], k, pos_, slots_),
        kv._ring_then_chunk(rings[1], v, pos_, slots_),
        jnp.full((b,), W, jnp.int32), jnp.maximum(W - pos_, 0),
        jnp.where(slots_ < N, jnp.asarray(valid, jnp.int32), 0),
        jnp.arange(b), SCALE, window=W, column_tile=BK, query_tile=TQ))
    assert np.isfinite(got).all()
    clean = [jnp.nan_to_num(r) for r in rings]      # the tiles multiply 0 by
    want = np.asarray(kv._ring_chunk_tiles(         # what a column holds
        q, k, v, clean[0], clean[1], pos_, slots_, SCALE))
    for i, (n, s, p) in enumerate(zip(valid, slots, pos)):
        if s >= N:
            assert not got[i].any()
            continue
        np.testing.assert_allclose(got[i, :n], want[i, :n],
                                   rtol=RING_TOL[dtype], atol=RING_TOL[dtype])
        assert not got[i, n:].any()
        if dtype == jnp.float32:
            to = lambda a: np.asarray(a.astype(jnp.float32))
            keys = np.concatenate([hist[i][0], to(k[i])])
            values = np.concatenate([hist[i][1], to(v[i])])
            np.testing.assert_allclose(
                got[i, :n], dense(q[i:i + 1], keys, values, 0, W)[:n],
                rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("column_tile,query_tile", [(32, 32), (16, 8),
                                                    (64, 16), (512, 512)])
def test_any_tiling_of_a_page_gives_the_same_numbers(column_tile, query_tile,
                                                     monkeypatch):
    """Column tiles that divide the page, that leave a partial last block
    and that are wider than the page (one block: the page itself); query
    tiles down to 8 rows."""
    q, k, v = draw(jnp.float32, 2, seed=3)
    pos, valid, slots = [35, 7], [C, 25], [2, 1]
    got = page_kernel(q, k, v, pos, valid, slots, column_tile, query_tile)
    want = page_loop(q, k, v, pos, slots, monkeypatch)
    for b, n in enumerate(valid):
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=2e-6,
                                   atol=2e-6)


@pytest.mark.parametrize("column_tile,query_tile", [(16, 16), (8, 8),
                                                    (32, 16), (512, 512)])
def test_any_tiling_of_a_band_gives_the_same_numbers(column_tile, query_tile):
    """Tiles of a window and under it (a query tile then meets blocks wholly
    inside its band, unmasked), column tiles over it, and one block for all
    48 keys; a query tile is never wider than the window."""
    q, k, v = draw(jnp.float32, 2, seed=4, t=W + C, rows=2)
    q0, first, valid = [W, W], [0, 11], [C, 21]
    got = np.asarray(kv.kv_chunk_fwd(
        q, k, v, *(jnp.asarray(a, jnp.int32) for a in (q0, first, valid)),
        jnp.arange(2), SCALE, window=W, column_tile=column_tile,
        query_tile=query_tile))
    for b, n in enumerate(valid):
        np.testing.assert_allclose(
            got[b, :n], dense(q[b:b + 1], k[b], v[b], first[b], W)[:n],
            rtol=1e-5, atol=1e-5)
        assert not got[b, n:].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_what_no_real_query_sees_is_never_a_number_read(dtype, monkeypatch):
    """NaN in every other row, in every column past ``pos + valid`` of the
    row (and, the interpreter pads a partial block with NaN, past the page's
    end), in the queries past ``valid``; on a band also before ``first``:
    none reaches the result, and the rows past ``valid`` are zero."""
    q, k, v = draw(dtype, 1, seed=5)
    pos, valid, slots = [8], [25], [1]          # sees columns 0..32 of 80
    spoil = lambda a, at: jnp.asarray(np.where(
        at, np.nan, np.asarray(a.astype(jnp.float32)))).astype(dtype)
    rows = np.arange(N)[:, None, None] != 1
    cols = np.arange(T)[None, :, None] >= 8 + 25
    got = page_kernel(spoil(q, np.arange(C)[None, :, None, None] >= 25),
                      spoil(k, rows | cols), spoil(v, rows | cols), pos,
                      valid, slots)
    want = page_loop(q, k, v, pos, slots, monkeypatch)  # on the clean page
    assert np.isfinite(got).all() and not got[0, 25:].any()
    np.testing.assert_allclose(got[0, :25], want[0, :25], rtol=TOL[dtype],
                               atol=TOL[dtype])
    # a band: keys 0..47, the first 5 hold no position, 20 real queries
    q, k, v = draw(dtype, 1, seed=6, t=W + C, rows=1)
    cols = ((np.arange(W + C) < 5) | (np.arange(W + C) >= W + 20))[None, :,
                                                                   None]
    args = ([W], [5], [20])
    band = lambda k, v: np.asarray(kv.kv_chunk_fwd(
        q, k, v, *(jnp.asarray(a, jnp.int32) for a in args), jnp.arange(1),
        SCALE, window=W, column_tile=BK, query_tile=TQ))
    got = band(spoil(k, cols), spoil(v, cols))
    assert np.isfinite(got).all() and not got[0, 20:].any()
    np.testing.assert_array_equal(got, band(k, v))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("leaf", ["page", "ring"])
def test_the_leaves_come_back_byte_for_byte(leaf, dtype, monkeypatch):
    """Through the dispatchers, with the leaves donated as the serving step
    donates them: the kernel reads them and hands back what it was given."""
    monkeypatch.setattr(la, "on_tpu", lambda: True)     # take the kernel,
    #                      interpreted: ``kv.on_tpu`` still says what is so
    t = T if leaf == "page" else W
    q, k, v = draw(dtype, 2, seed=7, t=t)
    before = [np.asarray(a).view(np.uint8).copy() for a in (k, v)]
    pos, slots = jnp.asarray([30, 4]), jnp.asarray([2, 0])
    valid = jnp.asarray([C, 17])
    new = draw(dtype, 2, seed=8, t=C, rows=2)[1:]

    def call(q, k_leaf, v_leaf):
        with la.record_paths() as paths:
            if leaf == "page":
                out = kv.page_chunk_attention(q, k_leaf, v_leaf, pos, slots,
                                              SCALE, valid)
            else:
                out = (kv.ring_chunk_attention(q, *new, k_leaf, v_leaf, pos,
                                               slots, SCALE, valid), k_leaf,
                       v_leaf)
        assert paths == ["kernel"]
        return out

    o, k_out, v_out = jax.jit(call, donate_argnums=(1, 2))(q, k, v)
    for a, was in zip((k_out, v_out), before):
        assert a.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8), was)
    o = np.asarray(o)
    assert np.isfinite(o).all() and not o[1, 17:].any()


def test_the_dispatchers_take_the_kernel_with_the_loops_numbers(monkeypatch):
    """The page path and the ring path through their dispatchers, kernel
    against loop: the same call, the rule alone patched."""
    q, k, v = draw(jnp.float32, 2, seed=9)
    ring = draw(jnp.float32, 2, seed=10, t=W)[1:]
    new = draw(jnp.float32, 2, seed=11, t=C, rows=2)[1:]
    pos, slots = jnp.asarray([30, 4]), jnp.asarray([2, N])
    calls = {
        "page": lambda: kv.page_chunk_attention(q, k, v, pos, slots,
                                                SCALE)[0],
        "ring": lambda: kv.ring_chunk_attention(q, *new, *ring, pos, slots,
                                                SCALE),
        "ring-without-slots": lambda: kv.ring_chunk_attention(
            q, *new, *(r[:2] for r in ring), pos, None, SCALE),
    }
    monkeypatch.setattr(kv, "CHUNK_BLOCK", 32)
    want = {}
    for name, call in calls.items():
        with la.record_paths() as paths:
            want[name] = np.asarray(call())
        assert paths == ["loop:not on a TPU"]
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    for name, call in calls.items():
        with la.record_paths() as paths:
            got = np.asarray(call())
        assert paths == ["kernel"]
        real = slice(0, 2 if name == "ring-without-slots" else 1)
        np.testing.assert_allclose(got[real], want[name][real], rtol=2e-6,
                                   atol=2e-6)
        if name != "ring-without-slots":
            assert not got[1].any()             # the sentinel row


REFUSALS = {
    "d_head-64": (dict(d=64), None, "d_head 64 is no multiple of 128"),
    "d_head-192": (dict(d=192), None, "d_head 192 is no multiple of 128"),
    "a-chunk-of-12": (dict(c=12), None, "chunk of 12 queries"),
    "a-chunk-of-a-window-and-a-half": (dict(c=24, t=W), W,
                                       "no multiple of the window 16"),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_the_dispatcher_keeps_the_loop_and_names_the_reason(case,
                                                            monkeypatch):
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    monkeypatch.setattr(kv, "CHUNK_BLOCK", 32)
    over, window, reason = REFUSALS[case]
    q, k, v = draw(jnp.bfloat16, 1, **over)
    assert reason in kv.kv_chunk_refusal(q, k, window)
    pos, slots = jnp.asarray([3]), jnp.asarray([1])
    with la.record_paths() as paths:
        if window:
            o = kv.ring_chunk_attention(q, *draw(
                jnp.bfloat16, 1, t=q.shape[1], rows=1)[1:], k, v, pos, slots,
                SCALE)
        else:
            o = kv.page_chunk_attention(q, k, v, pos, slots, SCALE)[0]
    assert paths == [f"loop:{kv.kv_chunk_refusal(q, k, window)}"]
    assert np.isfinite(np.asarray(o)).all()


@pytest.mark.parametrize("what", [
    "a-leaf-320-wide", "dtypes-differ", "an-int8-leaf", "off-the-chip",
    "pages-over-several-devices"])
def test_the_rule_refuses_other_widths_dtypes_placements_and_backends(
        what, monkeypatch):
    q, k, _ = draw(jnp.bfloat16, 1)
    if what == "off-the-chip":
        assert kv.kv_chunk_refusal(q, k) == "not on a TPU"
        return
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    assert kv.kv_chunk_refusal(q, k) is None
    assert kv.kv_chunk_refusal(q, k[:, :W], W) is None
    if what == "pages-over-several-devices":
        with partitioned_pages():
            assert kv.kv_chunk_refusal(q, k) == (
                "pages split over several devices")
            with la.record_paths() as paths:
                kv.page_chunk_attention(q, k, k, jnp.asarray([3]),
                                        jnp.asarray([0]), SCALE)
        assert paths == ["loop:pages split over several devices"]
        assert kv.kv_chunk_refusal(q, k) is None
    elif what == "a-leaf-320-wide":
        assert "leaf width 320 is no multiple of 128" in kv.kv_chunk_refusal(
            q, jnp.zeros((N, T, 320), jnp.bfloat16))
    elif what == "dtypes-differ":
        assert "not one of bfloat16, float32" in kv.kv_chunk_refusal(
            q.astype(jnp.float32), k)
    else:
        assert "not one of bfloat16, float32" in kv.kv_chunk_refusal(
            q.astype(jnp.int8), k.astype(jnp.int8))
