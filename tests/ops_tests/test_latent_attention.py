"""The chunk's latent attention as a kernel (ops/latent_attention.py) in the
Pallas interpreter at small lane-aligned widths: equal to the ``jax.numpy``
loop it replaces (models/hybrid.py::latent_chunk_attention) and to a one-piece
softmax over the expanded keys and values, at every place a cursor can stand;
the page comes back byte for byte; and the dispatcher's rule, reason by reason.
Then the same for the decode step's absorbed attention (``latent_decode_fwd``
against ``latent_decode_attention``'s loop and a one-piece softmax over the
page's columns as they lie). tests/ops_tests/test_grouped_swiglu_compile.py
compiles both kernels for the chip at the benchmark's widths."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models import hybrid
from chainermn_tpu.ops import latent_attention as la
from chainermn_tpu.ops.page_write import partitioned_pages

R, DN, DR, DV, H = 128, 128, 64, 128, 2
WIDTH = 256                 # [c 128 | k_r 64 | 64 zeros]
T, N, C = 80, 3, 32         # 80 columns: blocks of 32 leave a last one of 16
SCALE = 0.07


def draw(dtype, b, seed=0, c=C, t=T, width=WIDTH, dr=DR, r=R):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    page = np.array(f(N, t, width))
    page[..., r + dr:] = 0.0
    return dict(q_nope=f(b, c, H, DN).astype(dtype),
                q_rope=f(b, c, H, dr).astype(dtype),
                page=jnp.asarray(page).astype(dtype),
                w_kvb=(f(r, H, DN + DV) / np.sqrt(r)).astype(dtype))


def the_loop(a, pos, slots, block=32):
    """The ``jax.numpy`` loop: what ``latent_chunk_attention`` runs off the
    chip (these tests run there)."""
    assert la.chunk_kernel_refusal(
        a["q_nope"], a["q_rope"], a["page"], a["w_kvb"]) == "not on a TPU"
    return np.asarray(hybrid.latent_chunk_attention(
        a["q_nope"], a["q_rope"], a["page"], a["w_kvb"],
        jnp.asarray(pos), SCALE, block, jnp.asarray(slots))[0])


def one_piece(a, pos, slots, b):
    """Row ``b``: a softmax over every column its queries see, keys and
    values expanded once, float64."""
    g = lambda x: np.asarray(x.astype(jnp.float32), np.float64)
    row = g(a["page"])[slots[b]]
    kv = np.einsum("tr,rhe->the", row[:, :R], g(a["w_kvb"]))
    s = (np.einsum("qhe,khe->hqk", g(a["q_nope"])[b], kv[..., :DN])
         + np.einsum("qhe,ke->hqk", g(a["q_rope"])[b],
                     row[:, R:R + DR])) * SCALE
    qpos = pos[b] + np.arange(C)
    s = np.where(np.arange(row.shape[0])[None] <= qpos[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khe->qhe", p / p.sum(-1, keepdims=True),
                     kv[..., DN:])


CURSORS = {
    "cursor-0": ([0], [C], [1]),
    "mid-block": ([21], [C], [2]),
    "block-edge": ([32], [C], [0]),
    "last-partial-block": ([T - C], [C], [0]),     # columns 48..79 of 80
    "short-last-chunk": ([40], [9], [1]),
    "slots-permute-and-skip": ([40, 5], [C, C], [2, 0]),
    "sentinel-row": ([37, 0], [C, 1], [1, N]),
    "cohort-of-2": ([11, 48], [20, C], [0, 1]),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 1e-3)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CURSORS))
def test_kernel_equals_the_loop_and_the_one_piece_softmax(case, dtype, tol):
    """Same operands, same roundings (bf16: keys, values and probabilities
    rounded to the page's dtype as the loop rounds them), another order of
    additions: a few float32 ulps, and in bf16 now and then a probability
    that rounds the other way. Rows past ``valid`` are
    nobody's (finite in a query tile that holds a real row, for which the
    row stops at ``pos + valid``); whole tiles past it, and a sentinel row,
    come back zero."""
    pos, valid, slots = CURSORS[case]
    a = draw(dtype, len(pos), seed=len(case))
    got = np.asarray(la.latent_chunk_fwd(
        **a, pos=jnp.asarray(pos), valid=jnp.asarray(valid),
        slots=jnp.asarray(slots), scale=SCALE, column_tile=32,
        query_tile=16))
    want = the_loop(a, pos, slots)
    assert got.shape == want.shape == (len(pos), C, H, DV)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    for b, (v, s) in enumerate(zip(valid, slots)):
        if s >= N:
            assert not got[b].any()
            continue
        np.testing.assert_allclose(got[b, :v], want[b, :v], rtol=tol,
                                   atol=tol)
        assert not got[b, -(-v // 16) * 16:].any()
        if dtype == jnp.float32:
            np.testing.assert_allclose(
                got[b, :v], one_piece(a, pos, slots, b)[:v], rtol=1e-5,
                atol=1e-5)


@pytest.mark.parametrize("column_tile,query_tile", [(32, 32), (16, 8),
                                                    (64, 16), (512, 512)])
def test_any_tiling_gives_the_same_numbers(column_tile, query_tile):
    """Column tiles that divide the page, that leave a partial last block
    and that are wider than the page (one block: the page itself); query
    tiles down to 8 rows."""
    a = draw(jnp.float32, 2, seed=3)
    pos, valid, slots = [35, 7], [C, 25], [2, 1]
    got = np.asarray(la.latent_chunk_fwd(
        **a, pos=jnp.asarray(pos), valid=jnp.asarray(valid),
        slots=jnp.asarray(slots), scale=SCALE, column_tile=column_tile,
        query_tile=query_tile))
    want = the_loop(a, pos, slots)
    for b, v in enumerate(valid):
        np.testing.assert_allclose(got[b, :v], want[b, :v], rtol=2e-6,
                                   atol=2e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_what_lies_past_the_cursor_or_the_page_is_never_a_number_read(dtype):
    """NaN in every other row, in every column past ``pos + valid`` of the
    row, and (the interpreter pads a partial block with NaN) past the page's
    end: none reaches the result, not even a padded query's."""
    a = draw(dtype, 1, seed=5)
    pos, valid, slots = [50], [30], [1]         # sees columns 0..79: all
    page = np.array(a["page"].astype(jnp.float32))
    page[0] = page[2] = np.nan
    short = dict(a, page=jnp.asarray(page).astype(dtype))
    got = la.latent_chunk_fwd(**short, pos=jnp.asarray(pos),
                              valid=jnp.asarray(valid),
                              slots=jnp.asarray(slots), scale=SCALE,
                              column_tile=32, query_tile=16)
    assert np.isfinite(np.asarray(got)).all()
    page[1, 40:] = np.nan                       # now a chunk at 8..39
    early = dict(a, page=jnp.asarray(page).astype(dtype))
    got = np.asarray(la.latent_chunk_fwd(
        **early, pos=jnp.asarray([8]), valid=jnp.asarray([C]),
        slots=jnp.asarray(slots), scale=SCALE, column_tile=32,
        query_tile=16))
    assert np.isfinite(got).all()
    want = the_loop(a, [8], slots)              # the loop on the clean page
    tol = 2e-6 if dtype == jnp.float32 else 1e-3
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_page_comes_back_byte_for_byte(dtype, monkeypatch):
    """Through the dispatcher, with the page donated as the serving step
    donates it: ``latent_chunk_attention`` hands back the page it was given,
    every byte of it."""
    monkeypatch.setattr(la, "on_tpu", lambda: True)     # take the kernel...
    a = draw(dtype, 2, seed=7)
    before = np.asarray(a["page"]).view(np.uint8).copy()
    pos, slots = jnp.asarray([30, 4]), jnp.asarray([2, 0])

    def call(q_nope, q_rope, page, w_kvb):
        with la.record_paths() as paths:
            out = hybrid.latent_chunk_attention(
                q_nope, q_rope, page, w_kvb, pos, SCALE, 32, slots,
                jnp.asarray([C, 17]))
        assert paths == ["kernel"]
        return out

    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():              # ...interpreted
        o, page = jax.jit(call, donate_argnums=(2,))(
            a["q_nope"], a["q_rope"], a["page"], a["w_kvb"])
    assert page.dtype == dtype
    np.testing.assert_array_equal(np.asarray(page).view(np.uint8), before)
    assert np.isfinite(np.asarray(o)).all()


REFUSALS = {
    "a-576-wide-page": (dict(width=576), "page width 576 is no multiple"),
    "a-rank-of-96": (dict(r=96, width=256), "kv_rank 96"),
    "a-chunk-of-12": (dict(c=12), "chunk of 12 queries"),
    # no call the loop could run either: the rule alone
    "d_rope-lanes-missing": (dict(width=256, dr=192),
                             "128 values after the latent, d_rope 192"),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_the_dispatcher_keeps_the_loop_and_names_the_reason(case,
                                                            monkeypatch):
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    over, reason = REFUSALS[case]
    a = draw(jnp.bfloat16, 1, **over)
    assert reason in la.chunk_kernel_refusal(**a)
    if a["page"].shape[-1] - a["w_kvb"].shape[0] < a["q_rope"].shape[-1]:
        return
    with la.record_paths() as paths:
        o, _ = hybrid.latent_chunk_attention(
            **a, pos=jnp.asarray([3]), scale=SCALE, block=16)
    assert paths == [f"loop:{la.chunk_kernel_refusal(**a)}"]
    assert np.isfinite(np.asarray(o)).all()


def test_the_dispatcher_keeps_the_loop_for_pages_over_several_devices(
        monkeypatch):
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    a = draw(jnp.bfloat16, 1)
    assert la.chunk_kernel_refusal(**a) is None
    with partitioned_pages():
        assert la.chunk_kernel_refusal(**a) == (
            "pages split over several devices")
        with la.record_paths() as paths:
            hybrid.latent_chunk_attention(**a, pos=jnp.asarray([3]),
                                          scale=SCALE, block=16)
    assert paths == ["loop:pages split over several devices"]
    assert la.chunk_kernel_refusal(**a) is None


@pytest.mark.parametrize("what", ["dtypes-differ", "an-int8-page",
                                  "off-the-chip"])
def test_the_dispatcher_refuses_other_dtypes_and_other_backends(what,
                                                                monkeypatch):
    a = draw(jnp.bfloat16, 1)
    if what == "off-the-chip":
        assert la.chunk_kernel_refusal(**a) == "not on a TPU"
        return
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    if what == "dtypes-differ":
        a["q_nope"] = a["q_nope"].astype(jnp.float32)
    else:
        a["page"] = a["page"].astype(jnp.int8)
    assert "not one of bfloat16, float32" in la.chunk_kernel_refusal(**a)


def test_paths_are_recorded_only_inside_a_scope_and_scopes_nest():
    a = draw(jnp.float32, 1)
    call = lambda: hybrid.latent_chunk_attention(
        **a, pos=jnp.asarray([0]), scale=SCALE, block=16)
    call()                                  # no scope: nothing to note into
    with la.record_paths() as outer:
        call()
        with la.record_paths() as inner:
            call()
            call()
        call()
    assert len(inner) == 2 and len(outer) == 2
    assert set(inner + outer) == {"loop:not on a TPU"}


# ---------------------------------------------------------------------------
# the decode step's absorbed attention
# ---------------------------------------------------------------------------

def draw_decode(dtype, hq, t, b=3, seed=0, width=WIDTH):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32).astype(dtype)
    return f(b, hq, width), f(b, t, width)


def decode_loop(q, page, pos, live, offs, block=32):
    """The ``jax.numpy`` loop: what ``latent_decode_attention`` runs off
    the chip (these tests run there)."""
    assert la.decode_kernel_refusal(q, page, R) == "not on a TPU"
    return np.asarray(hybrid.latent_decode_attention(
        q, page, jnp.asarray(pos), jnp.asarray(live), SCALE, R, block=block,
        offs=offs)[0])


def decode_one_piece(q, page, pos, offs, b):
    """Row ``b``: a softmax over every column each query-head sees,
    float64."""
    g = lambda x: np.asarray(x.astype(jnp.float32), np.float64)
    row = g(page)[b]
    s = g(q)[b] @ row.T * SCALE
    last = pos[b] + np.asarray(offs or (0,) * q.shape[1])
    s = np.where(np.arange(row.shape[0])[None] <= last[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ row[:, :R]


def two_queries(hq):
    return tuple(j for j in range(2) for _ in range(hq // 2))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-3)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t", [T, 96], ids=["capacity-80", "capacity-96"])
@pytest.mark.parametrize("hq", [32, 256])
@pytest.mark.parametrize("queries", [1, 2])
@pytest.mark.parametrize("rows", ["all-live", "one-dead"])
def test_decode_kernel_equals_the_loop_and_the_one_piece_softmax(
        rows, queries, hq, t, dtype, tol):
    """One query a row and two side by side (``offs``), 32 and 256
    query-heads, a capacity that is (96) and is not (80) a multiple of the
    32-column tile: same operands and roundings as the loop, another order
    of additions. Rows at cursor 0, inside a block and on the page's last
    column (a second query there would sit past the page: it sees the whole
    page and no more), then a block's last and next first column beside a
    row that is not live, which comes back zero."""
    offs = two_queries(hq) if queries == 2 else None
    q, page = draw_decode(dtype, hq, t, seed=hq + t)
    pos, live = (([0, 37, t - 1], [True, True, True]) if rows == "all-live"
                 else ([31, 5, 32], [True, False, True]))
    got = np.asarray(la.latent_decode_fwd(
        q, page, jnp.asarray(pos), jnp.asarray(live), SCALE, R, offs=offs,
        column_tile=32))
    want = decode_loop(q, page, pos, live, offs)
    assert got.shape == want.shape == (3, hq, R)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    for b, alive in enumerate(live):
        if not alive:
            assert not got[b].any()
        elif dtype == jnp.float32:
            np.testing.assert_allclose(
                got[b], decode_one_piece(q, page, pos, offs, b),
                rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("column_tile", [16, 32, 48, 512, None])
def test_any_decode_column_tile_gives_the_same_numbers(column_tile):
    """Tiles that divide the page, that leave a partial last block, that are
    wider than the page (one block: the page itself) and the one the call
    picks for itself."""
    q, page = draw_decode(jnp.float32, 16, T, seed=3)
    pos, live = [35, 7, T - 2], [True, True, True]
    offs = two_queries(16)
    got = np.asarray(la.latent_decode_fwd(
        q, page, jnp.asarray(pos), jnp.asarray(live), SCALE, R, offs=offs,
        column_tile=column_tile))
    np.testing.assert_allclose(got, decode_loop(q, page, pos, live, offs),
                               rtol=2e-6, atol=2e-6)


def test_the_decode_tile_follows_the_capacity_and_the_query_heads():
    """What the benchmark's two latent cells show the call: 256 query-heads
    over 3,328 columns take the tile the chip measured best there, 32 over
    33,024 theirs; a longer page or fewer heads widen it, and a page
    shorter than a tile is one block."""
    assert la.decode_column_tile(3328, 256) == 512
    assert la.decode_column_tile(33024, 32) == 2048
    assert la.decode_column_tile(3328, 32) == 768
    assert la.decode_column_tile(33024, 256) == 1536
    assert la.decode_column_tile(96, 32) == 96
    assert la.decode_column_tile(3328, 4096) == 256


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_decode_kernel_never_reads_past_a_fill_the_page_or_into_a_dead_row(
        dtype):
    """NaN past every row's last seen column (inside its last block and in
    the blocks after it), past the page's end (the interpreter pads a
    partial block with NaN) and all over a row that is not live: none
    reaches the result. The kernel's twin of tests/models_tests/
    test_mla_long.py::test_decode_reads_the_blocks_the_live_rows_have_filled.
    """
    q, clean = draw_decode(dtype, 16, T, seed=5)
    offs = two_queries(16)
    pos, live = [5, 40, T - 1], [True, False, True]
    page = np.array(clean.astype(jnp.float32))
    page[0, 5 + 2:] = np.nan        # row 0 sees 0..6 (its second query: 6)
    page[1] = np.nan                # row 1 is not live
    got = np.asarray(la.latent_decode_fwd(
        q, jnp.asarray(page).astype(dtype), jnp.asarray(pos),
        jnp.asarray(live), SCALE, R, offs=offs, column_tile=32))
    assert np.isfinite(got).all() and not got[1].any()
    tol = 2e-6 if dtype == jnp.float32 else 2e-3
    np.testing.assert_allclose(got, decode_loop(q, clean, pos, live, offs),
                               rtol=tol, atol=tol)


DECODE_REFUSALS = {
    "a-576-wide-page": (dict(width=576), 128, "page width 576"),
    "a-rank-of-96": (dict(), 96, "kv_rank 96"),
    "12-query-heads": (dict(hq=12), 128, "12 query-heads"),
}


@pytest.mark.parametrize("case", list(DECODE_REFUSALS))
def test_the_decode_dispatcher_keeps_the_loop_and_names_the_reason(
        case, monkeypatch):
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    over, r, reason = DECODE_REFUSALS[case]
    q, page = draw_decode(jnp.bfloat16, **{"hq": 16, "t": T, **over})
    assert reason in la.decode_kernel_refusal(q, page, r)
    with la.record_paths() as paths:
        o, _ = hybrid.latent_decode_attention(
            q, page, jnp.asarray([3, 0, 70]), jnp.ones((3,), bool), SCALE, r,
            block=16)
    assert paths == [f"loop:{la.decode_kernel_refusal(q, page, r)}"]
    assert np.isfinite(np.asarray(o)).all()


@pytest.mark.parametrize("what", ["queries-narrower", "dtypes-differ",
                                  "an-int8-page", "several-devices",
                                  "off-the-chip"])
def test_the_decode_dispatcher_refuses_for_cause(what, monkeypatch):
    q, page = draw_decode(jnp.bfloat16, 16, T)
    if what == "off-the-chip":
        assert la.decode_kernel_refusal(q, page, R) == "not on a TPU"
        return
    monkeypatch.setattr(la, "on_tpu", lambda: True)
    assert la.decode_kernel_refusal(q, page, R) is None
    if what == "several-devices":
        with partitioned_pages():
            assert la.decode_kernel_refusal(q, page, R) == (
                "pages split over several devices")
    elif what == "queries-narrower":
        assert "queries 192 wide, the page 256" in la.decode_kernel_refusal(
            q[..., :192], page, R)
    else:
        if what == "dtypes-differ":
            q = q.astype(jnp.float32)
        else:
            page = page.astype(jnp.int8)
        assert "not one of bfloat16, float32" in la.decode_kernel_refusal(
            q, page, R)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_decode_dispatcher_takes_the_kernel_and_hands_the_page_back(
        dtype, monkeypatch):
    """Through the dispatcher with the page donated, as the serving step
    donates it: the kernel's numbers, and the page byte for byte."""
    monkeypatch.setattr(la, "on_tpu", lambda: True)     # take the kernel...
    q, page = draw_decode(dtype, 16, T, seed=7)
    before = np.asarray(page).view(np.uint8).copy()
    pos, live = [30, 4, 79], [True, True, False]

    def call(q, page):
        with la.record_paths() as paths:
            out = hybrid.latent_decode_attention(
                q, page, jnp.asarray(pos), jnp.asarray(live), SCALE, R)
        assert paths == ["kernel"]
        return out

    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():              # ...interpreted
        o, same = jax.jit(call, donate_argnums=(1,))(q, page)
    assert same.dtype == dtype
    np.testing.assert_array_equal(np.asarray(same).view(np.uint8), before)
    monkeypatch.setattr(la, "on_tpu", lambda: False)
    tol = 2e-6 if dtype == jnp.float32 else 2e-3
    np.testing.assert_allclose(np.asarray(o),
                               decode_loop(q, same, pos, live, None),
                               rtol=tol, atol=tol)
