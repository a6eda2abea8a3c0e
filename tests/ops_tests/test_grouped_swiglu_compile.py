"""Kernels compiled for a described TPU v5e at the benchmark's widths: what
interpret mode cannot show. Nothing runs; no number comes out of this. One
file for every such compile: the worker that is handed it loads the TPU's
library, and a second file could go to a worker that cannot.

- The grouped expert product (d 2560, expert width 768, 128 held experts,
  bf16), both tile classes: the three double-buffered 3.9 MB weight blocks
  must fit the kernel's VMEM limit and the row tiles the chip's tiling.
- The same product at deepseek-v3's widths (d 7168, expert width 2048, 16
  held experts): in chunks of the width, since no whole block fits.
- The per-slot cache write (ops/page_write.py) at StarCoder2-3B's page and
  over the row shapes ``rows_are_whole_tiles`` admits: it compiles, under
  its own name, with the pages aliased and no copy of a page; and what the
  rule refuses is refused for cause.
- The chunk's latent attention (ops/latent_attention.py) at
  ``xing4-serve-longdoc``'s shapes, behind the page write as the mixer calls
  it: one custom call under its own name, the donated page updated in place
  and read where it lies (no copy of it), and no float32 score array among
  the program's HBM temporaries.
- The decode step's absorbed attention (the same file's
  ``latent_decode_fwd``) at both latent cells' shapes, behind the scatter
  that writes the round's latents: one custom call under its own name, the
  donated page in place, no copy of it and no ``[block, heads]`` float32
  score array in HBM.
- The decode step's KDA recurrence (ops/kda_state.py) at
  ``ling3-flash-serve-reasongen``'s shapes, through the dispatcher: one
  custom call under its own name, the donated state in place, no second
  copy of it.
- The chunk attention over flat K/V leaves (ops/kv_attention.py::
  ``kv_chunk_fwd``) at ``laguna-xs2-serve-mixedctx``'s shapes, through both
  dispatchers as ``GQAMixer`` calls them: behind the page's write with the
  pages donated (one custom call under its own name, the pages in place, no
  copy of one) and behind the ring's lay-out; no float32 score array among
  either program's HBM temporaries.
- The dense model's one-query decode attention (ops/page_attention.py::
  ``page_decode_fwd``) at the served page shapes, through the model's
  dispatcher behind the per-slot write with the pages donated: one custom
  call under its own name, both pages in place and read where they lie (the
  flattened view is the same bytes: no ``copy``, ``reshape`` or ``convert``
  of a page), no score array in HBM."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu.models import hybrid, transformer
from chainermn_tpu.ops import grouped_swiglu as gs
from chainermn_tpu.ops import kda_state as ks
from chainermn_tpu.ops import kv_attention as kva
from chainermn_tpu.ops import latent_attention as la
from chainermn_tpu.ops import page_attention as pa
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


@pytest.mark.parametrize("tile,rows", [(gs.NARROW_TILE, 2048 + 16 * 15),
                                       (gs.WIDE_TILE, 24576 + 16 * 127)])
def test_a_wide_expert_compiles_for_v5e_in_chunks_of_its_width(
        one_chip, monkeypatch, tile, rows):
    """deepseek-v3's share: d 7168, expert width 2048, 16 held experts. One
    28 MB block an operand would not fit VMEM even single-buffered; the
    kernel takes 8 chunks of 256 columns (``width_block``) under the same
    name, a float32 accumulator a row tile beside them."""
    monkeypatch.setattr(gs, "on_tpu", lambda: True)     # Mosaic, not interpret
    assert gs.width_block(7168, 2048, 2) == 256
    rows = -(-rows // tile) * tile
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: gs.grouped_swiglu.__wrapped__(*a, tile=tile)).lower(
        sds((rows, 7168), jnp.bfloat16), sds((rows // tile,), jnp.int32),
        sds((1,), jnp.int32), sds((16, 7168, 2048), jnp.bfloat16),
        sds((16, 7168, 2048), jnp.bfloat16),
        sds((16, 2048, 7168), jnp.bfloat16)).compile()
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


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_latent_chunk_attention_compiles_in_place_for_v5e(one_chip,
                                                          monkeypatch, dtype):
    """A chunk of 2,048 queries, 32 heads, rank 512, a page of 12 x 33,024
    columns of 640 values (the last block of 1,024 columns is partial): the
    chunk's latents written into the donated page, then the chunk attends
    it, as ``MLAMixer`` does under ``mla_chunk``."""
    monkeypatch.setattr(la, "on_tpu", lambda: True)     # Mosaic, not interpret
    b, c, h, n, t, w = 1, 2048, 32, 12, 33024, 640
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(q_nope, q_rope, page, ckv, w_kvb, pos, valid, slots):
        page = hybrid._write_window(page, ckv, pos, valid, slots)
        return hybrid.latent_chunk_attention(
            q_nope, q_rope, page, w_kvb, pos, 0.1447, 512, slots, valid)

    with la.record_paths() as paths:
        compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
            sds((b, c, h, 128), dtype), sds((b, c, h, 64), dtype),
            sds((n, t, w), dtype), sds((b, c, w), dtype),
            sds((512, h, 256), dtype), *[sds((b,), jnp.int32)] * 3).compile()
    assert paths == ["kernel"]
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_chunk_fwd" in text
    mem = compiled.memory_analysis()
    page_bytes = n * t * w * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes >= page_bytes        # the page in place
    assert mem.temp_size_in_bytes < page_bytes // 16    # and no copy of it
    # what a score array would be: float32 with the chunk and a block of
    # columns (the loop's 512, the kernel's tile) as axes
    f32 = {tuple(int(d) for d in dims.split(","))
           for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    assert not [s for s in f32 if c in s and (
        512 in s or la.COLUMN_TILE in s or t in s)]
    # beside the page, no float32 array is larger than the result
    assert max(int(np.prod(s)) for s in f32
               if s != (n, t, w)) == b * c * h * 128


@pytest.mark.parametrize("cell", ["dsv3-two-queries", "xing4-one-query"])
def test_latent_decode_attention_compiles_in_place_for_v5e(one_chip,
                                                           monkeypatch, cell):
    """``dsv3-serve-mtp-reasongen``: 128 slots of 3,328 columns (6.5 tiles
    of 512), two positions a slot side by side, 256 query-heads;
    ``xing4-serve-longdoc``: 12 slots of 33,024 columns (8 tiles of 4,096
    and 256 columns more), one position, 32 query-heads. The step's latents
    scattered into the donated page, then the queries attend it, as
    ``MLAMixer`` does under ``mla_absorbed``."""
    monkeypatch.setattr(la, "on_tpu", lambda: True)     # Mosaic, not interpret
    n, t, l, h = (128, 3328, 2, 128) if cell.startswith("dsv3") else (
        12, 33024, 1, 32)
    w, r, dtype = 640, 512, jnp.bfloat16
    offs = tuple(j for j in range(l) for _ in range(h)) if l > 1 else None
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(q_cat, page, ckv, pos, live):
        page = page.at[jnp.arange(n)[:, None],
                       pos[:, None] + jnp.arange(l)[None]].set(
                           ckv, mode="drop")
        return hybrid.latent_decode_attention(q_cat, page, pos, live, 0.1147,
                                              r, offs=offs)

    with la.record_paths() as paths:
        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            sds((n, l * h, w), dtype), sds((n, t, w), dtype),
            sds((n, l, w), dtype), sds((n,), jnp.int32),
            sds((n,), jnp.bool_)).compile()
    assert paths == ["kernel"]
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_decode_fwd" in text
    mem = compiled.memory_analysis()
    page_bytes = n * t * w * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes >= page_bytes        # the page in place
    assert mem.temp_size_in_bytes < page_bytes // 16    # and no copy of it
    # what a visit's score array would be: float32, the query-heads beside
    # a block of columns (the loop's, the kernel's tile) or the capacity
    tile = la.decode_column_tile(t, l * h)
    f32 = {tuple(int(d) for d in dims.split(","))
           for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    assert not [s for s in f32 if len(s) == 2 and l * h in s and (
        tile in s or min(hybrid.DECODE_BLOCK, t) in s or t in s)]
    # no float32 array is larger than the result
    assert max(int(np.prod(s)) for s in f32) == n * l * h * r


def test_kda_state_step_compiles_in_place_for_v5e(one_chip, monkeypatch):
    """``ling3-flash-serve-reasongen``: 128 slots of 32 heads of a 128 x 128
    float32 state, 268 MB a layer; all 32 heads of a row a grid step (2 MB
    in, 2 MB out, each double-buffered)."""
    monkeypatch.setattr(ks, "on_tpu", lambda: True)     # Mosaic, not interpret
    n, h, d = 128, 32, 128
    assert ks.head_block(h, d, d) == h
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    with la.record_paths(ks.PATHS) as paths:
        compiled = jax.jit(hybrid.kda_decode_step, donate_argnums=(5,)).lower(
            sds((n, h, d)), sds((n, h, d)), sds((n, h, d)), sds((n, h, d)),
            sds((n, h)), sds((n, h, d, d)), sds((n,), jnp.bool_)).compile()
    assert paths == ["kernel"]
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_step_fwd" in text
    mem = compiled.memory_analysis()
    state_bytes = n * h * d * d * 4
    assert mem.alias_size_in_bytes >= state_bytes       # the state in place
    assert mem.temp_size_in_bytes < state_bytes // 16   # and no copy of it


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("leaf", ["page", "ring"])
def test_kv_chunk_attention_compiles_in_place_for_v5e(one_chip, monkeypatch,
                                                      leaf, dtype):
    """A chunk of 2,048 queries over 8 KV heads of 128: 48 query heads over
    a page of 20 x 32,768 columns of 1,024 values (the chunk's keys and
    values written into the donated pages, then the chunk attends them, as
    ``GQAMixer`` does under ``gqa_chunk``), 64 over rings of 512 columns
    (the ring laid in order before the chunk, then written, under
    ``swa_chunk``)."""
    monkeypatch.setattr(la, "on_tpu", lambda: True)     # the kernel, and
    monkeypatch.setattr(kva, "on_tpu", lambda: True)    # Mosaic, not interpret
    b, c, n, d, w = 1, 2048, 20, 128, 1024
    h, t = (48, 32768) if leaf == "page" else (64, 512)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(q, k, v, k_leaf, v_leaf, pos, valid, slots):
        if leaf == "ring":
            o = kva.ring_chunk_attention(q, k, v, k_leaf, v_leaf, pos, slots,
                                         0.0884, valid)
            return (o,) + tuple(kva.write_ring(a, new, pos, valid, slots)
                                for a, new in ((k_leaf, k), (v_leaf, v)))
        k_leaf, v_leaf = (hybrid._write_window(a, new, pos, valid, slots)
                          for a, new in ((k_leaf, k), (v_leaf, v)))
        return kva.page_chunk_attention(q, k_leaf, v_leaf, pos, slots, 0.0884,
                                        valid)

    with la.record_paths() as paths:
        compiled = jax.jit(chunk, donate_argnums=(3, 4)).lower(
            sds((b, c, h, d), dtype), sds((b, c, w), dtype),
            sds((b, c, w), dtype), sds((n, t, w), dtype),
            sds((n, t, w), dtype), *[sds((b,), jnp.int32)] * 3).compile()
    assert paths == ["kernel"]
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kv_chunk_fwd" in text
    mem = compiled.memory_analysis()
    leaf_bytes = n * t * w * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes >= 2 * leaf_bytes    # both leaves in place
    if leaf == "page":
        assert mem.temp_size_in_bytes < leaf_bytes // 8     # no copy of one
    # what a score array (or a running maximum) would be: float32, the KV
    # heads and their groups beside queries and columns, no axis a head wide
    f32 = {tuple(int(x) for x in dims.split(","))
           for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    assert not [s for s in f32 if len(s) >= 4 and d not in s]
    # beside a float32 leaf, no float32 array is larger than the result
    assert max(int(np.prod(s)) for s in f32
               if s != (n, t, w)) == b * c * h * d


@pytest.mark.parametrize("h_kv,h,dtype", [
    (2, 24, jnp.bfloat16),      # sc2-3b-serve-batchgen's page: 12 to 1
    (2, 24, jnp.float32),
    (4, 32, jnp.bfloat16),
    (8, 32, jnp.bfloat16),
    (8, 8, jnp.bfloat16),       # no grouping
])
def test_page_decode_attention_compiles_in_place_for_v5e(one_chip,
                                                         monkeypatch, h_kv,
                                                         h, dtype):
    """64 slots of 2,048 columns of ``h_kv`` heads of 128: each slot's new
    row written at its cursor into the donated pages, then one query a slot
    attends them, as ``TransformerBlock`` does under ``cache_write`` and
    ``attend_cache``."""
    for op in (la, pa, pw):     # the kernels, and Mosaic, not interpret
        monkeypatch.setattr(op, "on_tpu", lambda: True)
    n, cap, d = 64, 2048, 128
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(q, k_page, v_page, k_new, v_new, row):
        k_page, v_page = pw.write_rows(k_page, v_page, k_new, v_new,
                                       row % cap)
        return transformer.cache_decode_attention(
            q, k_page, v_page, row, None), k_page, v_page

    page, new = sds((n, cap, h_kv, d), dtype), sds((n, 1, h_kv, d), dtype)
    with la.record_paths() as paths:
        compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
            sds((n, h, d), dtype), page, page, new, new,
            sds((n,), jnp.int32)).compile()
    assert paths == ["kernel"]
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "page_decode_fwd" in text
    mem = compiled.memory_analysis()
    page_bytes = n * cap * h_kv * d * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes >= 2 * page_bytes    # both pages in place
    assert mem.temp_size_in_bytes < page_bytes // 64    # and no copy of one
    # no operation but the two kernels gives a page, in either shape
    name = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    shapes = (f"{name}[{n},{cap},{h_kv},{d}]", f"{name}[{n},{cap * h_kv},{d}]")
    made = [line for line in text.splitlines()
            if any(f" = {s}" in line for s in shapes)
            and not re.search(r"\b(parameter|bitcast|custom-call|"
                              r"get-tuple-element)\(", line)]
    assert not made, made
    # and beside a float32 page no float32 array is larger than the
    # result: no score array
    f32 = {tuple(int(x) for x in dims.split(","))
           for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    assert max(int(np.prod(s)) for s in f32 if int(np.prod(s))
               != n * cap * h_kv * d) == n * h * d
