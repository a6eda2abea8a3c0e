"""The grouped expert product in chunks of the expert's width
(ops/grouped_swiglu.py: experts too wide for their three blocks to sit in
VMEM whole), in the Pallas interpreter: what it gives against the one-step
kernel and against plain ``jax.numpy``, the rule that picks the chunk, and
that tiles past ``n_active`` come back zero."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops import grouped_swiglu as gs


def problem(seed, n_tiles, tile, d, f, e, n_active):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n_tiles * tile, d), jnp.float32)
    te = np.sort(rs.randint(0, e, (n_tiles,))).astype(np.int32)
    te[n_active:] = te[n_active - 1]
    w = lambda *s: jnp.asarray(rs.randn(*s) * s[1] ** -0.5, jnp.float32)
    return (x, jnp.asarray(te), jnp.asarray([n_active], jnp.int32),
            w(e, d, f), w(e, d, f), w(e, f, d))


def plain(x, te, na, wg, wu, wd, tile):
    out = []
    for i in range(x.shape[0] // tile):
        rows = x[i * tile:(i + 1) * tile]
        k = int(te[i])
        h = jax.nn.silu(rows @ wg[k]) * (rows @ wu[k])
        out.append(h @ wd[k] if i < int(na[0]) else jnp.zeros_like(rows))
    return jnp.concatenate(out)


def tight(monkeypatch, d, f_block, itemsize=4):
    """VMEM budgets under which ``width_block`` takes ``f_block`` columns of
    a wider expert."""
    monkeypatch.setattr(gs, "_WHOLE_BUDGET", 0)
    monkeypatch.setattr(gs, "_CHUNK_BUDGET", 6 * d * f_block * itemsize)


@pytest.mark.parametrize("tile", [gs.NARROW_TILE, gs.WIDE_TILE])
@pytest.mark.parametrize("f_block", [128, 256])
def test_chunks_of_the_width_give_what_the_whole_expert_gives(
        monkeypatch, tile, f_block):
    args = problem(0, 5, tile, 64, 512, 4, 3)
    with jax.default_matmul_precision("highest"):
        whole = gs.grouped_swiglu(*args, tile=tile)
        tight(monkeypatch, 64, f_block)
        assert gs.width_block(64, 512, 4) == f_block
        # traced anew: the jitted entry point keeps the whole-expert program
        chunked = gs.grouped_swiglu.__wrapped__(*args, tile=tile)
        want = plain(*args, tile)
    np.testing.assert_allclose(chunked, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(chunked, whole, atol=2e-5, rtol=2e-5)
    assert np.abs(np.asarray(chunked[3 * tile:])).max() == 0.0
    assert np.abs(np.asarray(chunked[:3 * tile])).min() > 0.0


def test_a_chunk_is_the_largest_divisor_in_lane_tiles_that_fits(monkeypatch):
    # 384 columns would fit the budget and do not divide 512: 256 it is
    tight(monkeypatch, 64, 384)
    assert gs.width_block(64, 512, 4) == 256
    assert gs.width_block(64, 768, 4) == 384


@pytest.mark.parametrize("d,f,want", [
    (2560, 768, 768),       # ling-3.0-flash-vl: whole
    (3584, 1024, 1024),     # xing4.0-29b-a4b: whole, 44 MB double-buffered
    (7168, 2048, 256),      # deepseek-v3: 8 chunks of 3.7 MB blocks
    (64, 512, 512),
])
def test_the_rule_keeps_served_experts_whole_and_chunks_the_wide_one(
        d, f, want):
    assert gs.width_block(d, f, 2) == want
    assert f % gs.width_block(d, f, 2) == 0


def test_an_expert_no_chunk_of_which_fits_is_refused_by_name():
    with pytest.raises(ValueError, match="fits VMEM"):
        gs.width_block(1 << 20, 2048, 2)
