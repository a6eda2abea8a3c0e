"""Kernels compiled for a described TPU v5e at the benchmark's widths: what
interpret mode cannot show. Nothing runs; no number comes out of this. One
file for every such compile: the worker that is handed it loads the TPU's
library, and a second file could go to a worker that cannot.

- The grouped expert product (d 2560, expert width 768, 128 held experts,
  bf16), both tile classes: the three double-buffered 3.9 MB weight blocks
  must fit the kernel's VMEM limit and the row tiles the chip's tiling.
- The per-slot cache write (ops/page_write.py) at StarCoder2-3B's page and
  over the row shapes ``rows_are_whole_tiles`` admits: it compiles, under
  its own name, with the pages aliased and no copy of a page; and what the
  rule refuses is refused for cause."""
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.ops import grouped_swiglu as gs
from chainermn_tpu.ops import page_write as pw


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


def _compile_page_write(one_chip, monkeypatch, n, cap, h, d, dtype):
    monkeypatch.setattr(pw, "on_tpu", lambda: True)     # Mosaic, not interpret
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    page, row = sds((n, cap, h, d), dtype), sds((n, 1, h, d), dtype)
    return jax.jit(pw.page_write_rows, donate_argnums=(0, 1)).lower(
        page, page, row, row, sds((n,), jnp.int32)).compile()


@pytest.mark.parametrize("h,d,dtype", [
    (2, 128, jnp.bfloat16),     # sc2-3b-serve-batchgen's page
    (2, 128, jnp.float32),      # what int8-block pages arrive as
    (8, 128, jnp.bfloat16),
    (24, 128, jnp.bfloat16),
    (4, 256, jnp.bfloat16),
    (1, 128, jnp.float32),
])
def test_page_write_compiles_in_place_for_v5e(one_chip, monkeypatch, h, d,
                                              dtype):
    assert pw.rows_are_whole_tiles(h, d, dtype)
    n, cap = 64, 2048
    compiled = _compile_page_write(one_chip, monkeypatch, n, cap, h, d, dtype)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "page_write_rows" in text
    mem = compiled.memory_analysis()
    page_bytes = n * cap * h * d * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes >= 2 * page_bytes    # both pages in place
    assert mem.temp_size_in_bytes < page_bytes // 64    # and no copy of one


@pytest.mark.parametrize("h,d,dtype", [
    (1, 128, jnp.bfloat16),     # MQA in bf16
    (12, 64, jnp.bfloat16),     # chip_smoke.py's model
    (12, 128, jnp.float32),
])
def test_rows_the_rule_refuses_cannot_be_written_in_place(
        one_chip, monkeypatch, h, d, dtype):
    """Why ``write_rows`` keeps the ``vmap`` form for them: the chip's
    compiler refuses the row's DMA, or takes it at the price of a relayout
    of the page (a copy a call)."""
    assert not pw.rows_are_whole_tiles(h, d, dtype)
    n, cap = 16, 512
    try:
        compiled = _compile_page_write(one_chip, monkeypatch, n, cap, h, d,
                                       dtype)
    except Exception as e:
        assert "aligned to tiling" in str(e)
        return
    page_bytes = n * cap * h * d * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes >= page_bytes
