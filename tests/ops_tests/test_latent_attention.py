"""The chunk's latent attention as a kernel (ops/latent_attention.py) in the
Pallas interpreter at small lane-aligned widths: equal to the ``jax.numpy``
loop it replaces (models/hybrid.py::latent_chunk_attention) and to a one-piece
softmax over the expanded keys and values, at every place a cursor can stand;
the page comes back byte for byte; and the dispatcher's rule, reason by reason.
tests/ops_tests/test_grouped_swiglu_compile.py compiles the kernel for the
chip at the benchmark's widths."""
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
