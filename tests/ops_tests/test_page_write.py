"""The per-slot cache write (ops/page_write.py): the kernel leaves the pages
byte for byte what ``vmap(dynamic_update_slice)`` leaves. The kernel runs in
the Pallas interpreter here; tests/ops_tests/test_grouped_swiglu_compile.py
compiles it for the chip."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops import page_write as pw


def _pages(n, cap, h, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    draw = lambda k, shape: jax.random.normal(k, shape).astype(dtype)
    return (draw(ks[0], (n, cap, h, d)), draw(ks[1], (n, cap, h, d)),
            draw(ks[2], (n, 1, h, d)), draw(ks[3], (n, 1, h, d)))


def _bytes(x):
    return np.asarray(x).view(np.uint8)


def _assert_same_bytes(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bytes(g), _bytes(w))


@pytest.mark.parametrize("cap", [16, 13], ids=["cap16", "cap13"])
@pytest.mark.parametrize("h", [1, 2, 8])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_pages_equal_vmap_dynamic_update_slice(dtype, h, cap):
    """Cursors at 0, at cap - 1, beyond cap (the ring position
    ``pos % cap``, as the block computes it) and equal in two slots."""
    kp, vp, kn, vn = _pages(6, cap, h, 16, dtype)
    pos = jnp.asarray([0, cap - 1, cap + 3, 5, 5, 3 * cap], jnp.int32)
    start = pos % cap
    got = pw.page_write_rows(kp, vp, kn, vn, start)
    _assert_same_bytes(got, pw.vmap_write_rows(kp, vp, kn, vn, start))
    # the rows written hold the new rows, every other row its old bytes
    for page, old, new in ((got[0], kp, kn), (got[1], vp, vn)):
        page, old, new = (np.asarray(a.astype(jnp.float32))
                          for a in (page, old, new))
        for i, s in enumerate(np.asarray(start)):
            np.testing.assert_array_equal(page[i, s], new[i, 0])
            keep = np.arange(cap) != s
            np.testing.assert_array_equal(page[i, keep], old[i, keep])


@pytest.mark.parametrize("n", [1, 64])
def test_one_slot_and_a_full_grid(n):
    kp, vp, kn, vn = _pages(n, 24, 2, 128, jnp.bfloat16, seed=n)
    start = jnp.asarray(np.random.RandomState(n).randint(0, 24, (n,)),
                        jnp.int32)
    _assert_same_bytes(pw.page_write_rows(kp, vp, kn, vn, start),
                       pw.vmap_write_rows(kp, vp, kn, vn, start))


@pytest.mark.parametrize("start", [[-3, 40, 7], [12, 11, 1 << 30]],
                         ids=["below-and-above", "far-above"])
def test_start_out_of_range_clamps_like_dynamic_update_slice(start):
    kp, vp, kn, vn = _pages(3, 12, 2, 16, jnp.float32)
    start = jnp.asarray(start, jnp.int32)
    _assert_same_bytes(pw.page_write_rows(kp, vp, kn, vn, start),
                       pw.vmap_write_rows(kp, vp, kn, vn, start))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_under_jit_with_the_pages_donated(dtype):
    kp, vp, kn, vn = _pages(4, 16, 2, 128, dtype)
    start = jnp.asarray([0, 15, 8, 8], jnp.int32)
    want = pw.vmap_write_rows(kp, vp, kn, vn, start)
    got = jax.jit(pw.page_write_rows, donate_argnums=(0, 1))(
        kp, vp, kn, vn, start)
    _assert_same_bytes(got, want)


@pytest.mark.parametrize("write", ["kernel", "chooser"])
def test_inside_a_scan_of_four_steps(write):
    """The shape ``decode_k_apply`` gives it: the pages carried through a
    ``lax.scan``, every slot's cursor advancing, one wrapping."""
    fn = pw.page_write_rows if write == "kernel" else pw.write_rows
    n, cap = 5, 8
    kp, vp, _, _ = _pages(n, cap, 2, 128, jnp.bfloat16)
    rows = jax.random.normal(jax.random.PRNGKey(9),
                             (4, 2, n, 1, 2, 128)).astype(jnp.bfloat16)
    pos0 = jnp.asarray([0, 6, 3, 3, 7], jnp.int32)

    def run(f):
        def body(carry, new):
            kp, vp, pos = carry
            kp, vp = f(kp, vp, new[0], new[1], pos % cap)
            return (kp, vp, pos + 1), None

        (k, v, pos), _ = jax.lax.scan(body, (kp, vp, pos0), rows)
        return k, v, pos

    got, want = jax.jit(lambda: run(fn))(), run(pw.vmap_write_rows)
    _assert_same_bytes(got[:2], want[:2])
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(pos0) + 4)


@pytest.mark.parametrize("fault", ["dtype", "rows", "slots"])
def test_refuses_rows_that_are_not_one_per_slot_in_the_pages_dtype(fault):
    kp, vp, kn, vn = _pages(3, 8, 2, 16, jnp.float32)
    start = jnp.zeros((3,), jnp.int32)
    if fault == "dtype":
        kn, match = kn.astype(jnp.bfloat16), "copies bytes"
    elif fault == "rows":
        kn = vn = jnp.concatenate([kn, kn], axis=1)
        match = "one row per slot"
    else:
        kn, vn, match = kn[:2], vn[:2], "one row per slot"
    with pytest.raises(ValueError, match=match):
        pw.page_write_rows(kp, vp, kn, vn, start)


@pytest.mark.parametrize("h,d,dtype,whole", [
    (2, 128, jnp.bfloat16, True),      # StarCoder2-3B's page row
    (2, 128, jnp.float32, True),
    (8, 128, jnp.bfloat16, True),
    (24, 256, jnp.bfloat16, True),
    (1, 128, jnp.float32, True),
    (1, 128, jnp.bfloat16, False),     # MQA in bf16: half a packed sublane
    (12, 128, jnp.bfloat16, False),    # 12 rows in tiles of 8
    (12, 64, jnp.bfloat16, False),     # the chip smoke model: 64 lanes
    (4, 8, jnp.float32, False),        # the toy models of the serving tests
])
def test_rows_are_whole_tiles(h, d, dtype, whole):
    assert pw.rows_are_whole_tiles(h, d, dtype) is whole


def _kernel_calls(monkeypatch):
    calls = []
    real = pw.page_write_rows
    monkeypatch.setattr(
        pw, "page_write_rows",
        lambda *a, **k: calls.append(a[0].shape) or real(*a, **k))
    return calls


@pytest.mark.parametrize("h,d,dtype,partitioned,kernel", [
    (2, 128, jnp.bfloat16, False, True),
    (2, 128, jnp.float32, False, True),
    (2, 128, jnp.bfloat16, True, False),    # pages over several devices
    (4, 16, jnp.float32, False, False),     # a row that is no whole tile
    (1, 128, jnp.bfloat16, False, False),
])
def test_write_rows_picks_by_shape_and_by_the_trace_scope(
        monkeypatch, h, d, dtype, partitioned, kernel):
    calls = _kernel_calls(monkeypatch)
    kp, vp, kn, vn = _pages(3, 8, h, d, dtype)
    start = jnp.asarray([0, 7, 3], jnp.int32)
    with pw.partitioned_pages(partitioned):
        got = pw.write_rows(kp, vp, kn, vn, start)
    assert bool(calls) is kernel
    _assert_same_bytes(got, pw.vmap_write_rows(kp, vp, kn, vn, start))


def test_write_rows_keeps_the_vmap_form_for_a_slab_of_rows(monkeypatch):
    calls = _kernel_calls(monkeypatch)
    kp, vp, _, _ = _pages(3, 8, 2, 128, jnp.bfloat16)
    kn, vn = kp[:, 2:5] * 2, vp[:, 2:5] * 2         # l == 3 rows a slot
    start = jnp.asarray([0, 5, 3], jnp.int32)
    got = pw.write_rows(kp, vp, kn, vn, start)
    assert not calls
    _assert_same_bytes(got, pw.vmap_write_rows(kp, vp, kn, vn, start))


def test_partitioned_scope_nests_and_restores():
    seen = lambda: getattr(pw._trace, "partitioned", False)
    assert not seen()
    with pw.partitioned_pages():
        assert seen()
        with pw.partitioned_pages(False):
            assert not seen()
        assert seen()
    assert not seen()
