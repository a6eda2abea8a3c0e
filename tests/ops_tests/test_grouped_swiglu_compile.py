"""The grouped expert product compiled for a described TPU v5e at the
benchmark's widths (d 2560, expert width 768, 128 held experts, bf16), both
tile classes: what interpret mode cannot show — the three double-buffered
3.9 MB weight blocks must fit the kernel's VMEM limit and the row tiles the
chip's tiling. Nothing runs; no number comes out of this."""
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops import grouped_swiglu as gs


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tile,rows", [(gs.NARROW_TILE, 1024 + 128 * 15),
                                       (gs.WIDE_TILE, 8192 + 128 * 127)])
def test_compiles_for_v5e_at_the_published_widths(one_chip, monkeypatch,
                                                  tile, rows):
    monkeypatch.setattr(gs, "on_tpu", lambda: True)     # Mosaic, not interpret
    rows = -(-rows // tile) * tile
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: gs.grouped_swiglu.__wrapped__(*a, tile=tile)).lower(
        sds((rows, 2560), jnp.bfloat16), sds((rows // tile,), jnp.int32),
        sds((1,), jnp.int32), sds((128, 2560, 768), jnp.bfloat16),
        sds((128, 2560, 768), jnp.bfloat16),
        sds((128, 768, 2560), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("grouped_swiglu_narrow" if tile == gs.NARROW_TILE
            else "grouped_swiglu_wide") in text
