"""The dense model's one-query decode attention as a kernel
(ops/page_attention.py::page_decode_fwd) against the ``jax.numpy`` form it
stands in for (models/transformer.py::_grouped_cache_attention), and the
dispatcher's rule. The kernel runs in the Pallas interpreter here;
tests/ops_tests/test_grouped_swiglu_compile.py compiles it for the chip."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models import transformer
from chainermn_tpu.models.transformer import (_grouped_cache_attention,
                                              cache_decode_attention)
from chainermn_tpu.ops import latent_attention as la
from chainermn_tpu.ops import page_attention as pa
from chainermn_tpu.ops import page_write as pw

TOLERANCE = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
             jnp.bfloat16: dict(rtol=2 ** -6, atol=2 ** -6)}


def _operands(b, cap, h_kv, h, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    draw = lambda k, shape: jax.random.normal(k, shape).astype(dtype)
    return (draw(ks[0], (b, h, d)), draw(ks[1], (b, cap, h_kv, d)),
            draw(ks[2], (b, cap, h_kv, d)))


def _assert_kernel_is_the_form(q, k, v, row, dtype, **kw):
    got = jax.block_until_ready(pa.page_decode_fwd(q, k, v, row, **kw))
    want = _grouped_cache_attention(q, k, v, jnp.asarray(row), None)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOLERANCE[dtype])


# capacity 16 in blocks of 4 columns: a block is ``block_rows / h_kv``
FILLS = {
    "empty-and-parked": [0, 0, 0],
    "one-short-of-a-block": [2, 6, 14],       # sees 3, 7, 15 columns
    "exactly-a-block": [3, 7, 11],
    "the-capacity": [15, 15, 3],
    "past-the-capacity": [37, 16, 3],         # test_decode_fast_path's ring
    "parked-beside-live": [0, 9, 0],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("fill", list(FILLS))
@pytest.mark.parametrize("h_kv,h", [(4, 4), (2, 4), (2, 24)],
                         ids=["mha", "gqa2to1", "gqa12to1"])
def test_kernel_matches_the_whole_page_form(h_kv, h, fill, dtype):
    q, k, v = _operands(3, 16, h_kv, h, 16, dtype)
    _assert_kernel_is_the_form(q, k, v, jnp.asarray(FILLS[fill], jnp.int32),
                               dtype, block_rows=4 * h_kv)


@pytest.mark.parametrize("row", [0, 5, 15, 21], ids=lambda r: f"row{r}")
def test_a_scalar_cursor_is_every_slots_cursor(row):
    """``generate()``'s contract: one cursor ``()`` for all rows."""
    q, k, v = _operands(2, 16, 2, 4, 16, jnp.float32, seed=row)
    _assert_kernel_is_the_form(q, k, v, jnp.asarray(row, jnp.int32),
                               jnp.float32, block_rows=8)


@pytest.mark.parametrize("cap,block_rows", [(13, 8), (16, 64), (24, 16)],
                         ids=["cap13-ragged", "one-block", "cap24"])
def test_any_capacity_and_a_block_wider_than_the_page(cap, block_rows):
    """A page that is no whole number of blocks ends its last block with the
    page (the columns an earlier block held are masked, not counted twice);
    a block wider than the page is the page."""
    q, k, v = _operands(4, cap, 2, 8, 16, jnp.float32, seed=cap)
    row = jnp.asarray([0, cap - 1, cap + 5, cap // 2], jnp.int32)
    _assert_kernel_is_the_form(q, k, v, row, jnp.float32,
                               block_rows=block_rows)


@pytest.mark.parametrize("buffers", [1, 2, 3, 8])
def test_any_number_of_copies_in_flight(buffers):
    """An item's blocks are started ``buffers - 1`` items ahead, across slot
    boundaries, into the buffer the item will be read from: more buffers
    than a slot has blocks, than the call has items, and none ahead."""
    q, k, v = _operands(4, 16, 2, 4, 16, jnp.float32, seed=buffers)
    _assert_kernel_is_the_form(q, k, v, jnp.asarray([9, 0, 40, 3], jnp.int32),
                               jnp.float32, block_rows=8, buffers=buffers)
    _assert_kernel_is_the_form(q[:1], k[:1], v[:1], jnp.asarray([2]),
                               jnp.float32, block_rows=8, buffers=buffers)


def test_at_the_served_row_shape():
    """StarCoder2-3B's cache row: 24 query heads over 2 KV heads of 128, in
    bfloat16, at the default block."""
    q, k, v = _operands(3, 512, 2, 24, 128, jnp.bfloat16)
    _assert_kernel_is_the_form(q, k, v, jnp.asarray([300, 0, 700], jnp.int32),
                               jnp.bfloat16)


def test_what_lies_past_the_cursor_reaches_no_output():
    q, k, v = _operands(3, 16, 2, 4, 16, jnp.float32)
    row = jnp.asarray([5, 11, 0], jnp.int32)
    past = (jnp.arange(16)[None] > row[:, None])[..., None, None]
    run = lambda poison: np.asarray(pa.page_decode_fwd(
        q, jnp.where(past, poison, k), jnp.where(past, poison, v), row,
        block_rows=8))
    np.testing.assert_array_equal(run(0.0), run(jnp.nan))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_behind_the_page_write_in_a_scan_of_four_steps(dtype):
    """The shape ``decode_k_apply`` gives it: the pages donated and carried
    through a ``lax.scan``, each step's row written in place by
    ``page_write_rows`` and then attended, every slot's cursor advancing,
    one parked at 0's side, one wrapping."""
    n, cap, h_kv, h, d = 5, 8, 2, 4, 128
    q, kp, vp = _operands(n, cap, h_kv, h, d, dtype)
    new = jax.random.normal(jax.random.PRNGKey(9),
                            (4, 2, n, 1, h_kv, d)).astype(dtype)
    pos0 = jnp.asarray([0, 6, 3, 3, 7], jnp.int32)

    def run(write, attend, kp, vp):
        def body(carry, new):
            kp, vp, pos = carry
            kp, vp = write(kp, vp, new[0], new[1], pos % cap)
            return (kp, vp, pos + 1), attend(q, kp, vp, pos)

        (kp, vp, _), o = jax.lax.scan(body, (kp, vp, pos0), new)
        return o, kp, vp

    want = run(pw.vmap_write_rows,
               lambda *a: _grouped_cache_attention(*a, None), kp, vp)
    got = jax.block_until_ready(jax.jit(
        lambda kp, vp: run(pw.page_write_rows, lambda *a: pa.page_decode_fwd(
            *a, block_rows=8), kp, vp),
        donate_argnums=(0, 1))(kp + 0, vp + 0))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               **TOLERANCE[dtype])
    for g, w in zip(got[1:], want[1:]):         # the pages: the same bytes
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))


PAGE = dict(cap=2048, h_kv=2, d=128, dtype=jnp.bfloat16, window=None,
            partitioned=False, tpu=True)


@pytest.mark.parametrize("change,reason", [
    ({}, None),                                     # sc2-3b-serve-batchgen
    ({"dtype": jnp.float32}, None),
    ({"h_kv": 4}, None),
    ({"h_kv": 8}, None),
    ({"cap": 256}, None),                           # one block a slot
    ({"tpu": False}, "not on a TPU"),
    ({"partitioned": True}, "pages split over several devices"),
    ({"window": 512}, "attention_window 512"),
    ({"d": 64, "h_kv": 16}, "a cache row [16, 64] of bfloat16 is not whole "
                            "tiles of 128 lanes"),  # gpt2-medium, chip_smoke
    ({"d": 256}, "a cache row [2, 256] of bfloat16 is not whole tiles of "
                 "128 lanes"),
    ({"h_kv": 1}, "a cache row [1, 128] of bfloat16 is not whole tiles of "
                  "128 lanes"),
    ({"h_kv": 12}, "a cache row [12, 128] of bfloat16 is not whole tiles of "
                   "128 lanes"),
    ({"dtype": jnp.float16}, "pages float16: not one of bfloat16, float32"),
    ({"cap": 2000}, "capacity 2000 is not whole blocks of 256 columns"),
])
def test_the_rule_refuses_by_what_the_call_shows(monkeypatch, change, reason):
    """Each refusal by its reason, the choice noted for whoever traces the
    program, and the form that ran is the form that was noted."""
    c = {**PAGE, **change}
    monkeypatch.setattr(la, "on_tpu", lambda: c["tpu"])
    ran = []
    monkeypatch.setattr(pa, "page_decode_fwd",
                        lambda q, *a, **k: ran.append("kernel") or q)
    monkeypatch.setattr(transformer, "_grouped_cache_attention",
                        lambda q, *a, **k: ran.append("xla") or q)
    q = jax.ShapeDtypeStruct((4, 24, c["d"]), c["dtype"])
    page = jax.ShapeDtypeStruct((4, c["cap"], c["h_kv"], c["d"]), c["dtype"])
    assert pa.decode_refusal(q, page, c["window"]) == (
        reason if not c["partitioned"] else None)
    with la.record_paths() as paths, pw.partitioned_pages(c["partitioned"]):
        cache_decode_attention(q, page, page, None, c["window"])
    assert paths == ["kernel" if reason is None else f"xla:{reason}"]
    assert ran == ["kernel" if reason is None else "xla"]


def test_the_dispatcher_keeps_the_whole_page_form_off_a_tpu():
    """What every CPU test and example runs: today's program, and it says
    so."""
    q, k, v = _operands(3, 16, 2, 4, 16, jnp.float32)
    row = jnp.asarray([5, 20, 0], jnp.int32)
    with la.record_paths() as paths:
        got = cache_decode_attention(q, k, v, row, None)
    assert len(paths) == 1 and paths[0].startswith("xla:")
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_grouped_cache_attention(q, k, v, row,
                                                              None)))
