"""Pallas grouped SwiGLU: one expert feed-forward per row tile.

The rows of ``x`` arrive sorted by expert, each expert's group padded to
whole tiles, so a tile belongs to exactly one expert. A scalar-prefetched
``tile_expert`` picks that expert's three weight blocks for the tile's DMA:
consecutive tiles of one expert reuse the blocks already in VMEM, tiles past
``n_active`` repeat the last active expert (no DMA) and only write zeros.
The work therefore follows the routing while the program's shapes do not:
one trace whatever the counts.

Per tile: ``silu(x @ Wg) * (x @ Wu) @ Wd`` with f32 accumulation, the hidden
activation rounded to the compute dtype before the down projection. At decode
sizes (a few rows per expert) the kernel is bound by the weight reads —
three contiguous ``[d, f]`` / ``[f, d]`` blocks per touched expert.

An expert whose three blocks, double-buffered, pass what VMEM gives
(:func:`width_block`: 7168 x 2048 in bf16 is 28 MB a block) is taken in
CHUNKS of its width ``f``: a second, inner grid axis walks the chunks, each
step computes the chunk's part of the hidden activation (its columns are
independent) and adds its part of the down projection to a float32
accumulator that is rounded and written with the last chunk. Tiles past
``n_active`` hold their block index at the last chunk fetched, so they still
cost no DMA. Experts that fit are served by the one-step kernel as before.

The kernel's name carries its tile class (``grouped_swiglu_narrow`` for the
decode tile, ``grouped_swiglu_wide`` for the prefill tile) so a trace reducer
can tell the bandwidth-bound calls from the compute-bound ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.utils import on_tpu

__all__ = ["grouped_swiglu", "width_block", "NARROW_TILE", "WIDE_TILE"]

#: rows per tile: decode (a few pairs per expert) and prefill
NARROW_TILE = 16
WIDE_TILE = 128

# three double-buffered weight blocks of the largest expert served whole
# (3584 x 1024 bf16: 3 x 2 x 7.3 MB) plus the row tiles and f32 temporaries
_VMEM_LIMIT = 64 * 1024 * 1024
#: what the three double-buffered weight blocks may take whole, and what
#: they may take a chunk once an expert is chunked
_WHOLE_BUDGET = 48 * 1024 * 1024
_CHUNK_BUDGET = 24 * 1024 * 1024
_LANES = 128


def width_block(d: int, f: int, itemsize: int) -> int:
    """Columns of an expert's width one grid step takes: all ``f`` where the
    three double-buffered blocks fit ``_WHOLE_BUDGET``, else the largest
    divisor of ``f`` in whole lane tiles that fits ``_CHUNK_BUDGET``."""
    if 6 * d * f * itemsize <= _WHOLE_BUDGET:
        return f
    fits = [b for b in range(_LANES, f, _LANES)
            if f % b == 0 and 6 * d * b * itemsize <= _CHUNK_BUDGET]
    if not fits:
        raise ValueError(
            f"no chunk of an expert of width {f} (in whole lane tiles, a "
            f"divisor) fits VMEM at d {d}, {itemsize} B a value")
    return fits[-1]


def _kernel(te_ref, na_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    del te_ref                      # consumed by the index maps
    i = pl.program_id(0)

    @pl.when(i < na_ref[0])
    def _compute():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        o_ref[...] = jnp.dot(
            h, wd_ref[0], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)

    @pl.when(i >= na_ref[0])
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)


def _kernel_chunked(te_ref, na_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                    acc):
    del te_ref                      # consumed by the index maps
    i, j = pl.program_id(0), pl.program_id(1)
    active = i < na_ref[0]

    @pl.when(active & (j == 0))
    def _start():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(active)
    def _compute():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc[...] += jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(active & (j == pl.num_programs(1) - 1))
    def _finish():
        o_ref[...] = acc[...].astype(o_ref.dtype)

    @pl.when(~active & (j == 0))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("tile",))
def grouped_swiglu(x_rows, tile_expert, n_active, w_gate, w_up, w_down, *,
                   tile: int):
    """``x_rows`` ``[n_tiles * tile, d]``; ``tile_expert`` int32
    ``[n_tiles]`` (the expert of each tile; tiles at or past ``n_active``
    repeat the last active tile's expert); ``n_active`` int32 ``[1]``;
    ``w_gate``/``w_up`` ``[E, d, f]``, ``w_down`` ``[E, f, d]``. Returns
    ``[n_tiles * tile, d]`` in ``x_rows.dtype``; rows of inactive tiles
    are zero. A grid step takes :func:`width_block` columns of an expert's
    width: all of them where the expert's three blocks fit VMEM."""
    m, d = x_rows.shape
    e, _, f = w_gate.shape
    if m % tile:
        raise ValueError(f"rows {m} are not whole tiles of {tile}")
    n_tiles = m // tile
    # a divisor of f in whole lane tiles, or f itself: the chunked kernel
    # below walks f // fb of them under one float32 accumulator
    fb = width_block(d, f, jnp.dtype(w_gate.dtype).itemsize)
    name = ("grouped_swiglu_narrow" if tile <= NARROW_TILE
            else "grouped_swiglu_wide")
    if fb < f:
        n_chunks = f // fb
        row = lambda i, j, te, na: (i, 0)
        # a tile past n_active stays on the last chunk fetched: no DMA
        chunk = lambda i, j, na: jnp.where(i < na[0], j, n_chunks - 1)
        cols = lambda i, j, te, na: (te[i], 0, chunk(i, j, na))
        rows = lambda i, j, te, na: (te[i], chunk(i, j, na), 0)
        return pl.pallas_call(
            _kernel_chunked,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_tiles, n_chunks),
                in_specs=[
                    pl.BlockSpec((tile, d), row),
                    pl.BlockSpec((1, d, fb), cols),
                    pl.BlockSpec((1, d, fb), cols),
                    pl.BlockSpec((1, fb, d), rows),
                ],
                out_specs=pl.BlockSpec((tile, d), row),
                scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((m, d), x_rows.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=not on_tpu(),
            name=name,
        )(tile_expert, n_active, x_rows, w_gate, w_up, w_down)
    row = lambda i, te, na: (i, 0)
    weight = lambda i, te, na: (te[i], 0, 0)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((tile, d), row),
                pl.BlockSpec((1, d, f), weight),
                pl.BlockSpec((1, d, f), weight),
                pl.BlockSpec((1, f, d), weight),
            ],
            out_specs=pl.BlockSpec((tile, d), row),
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=not on_tpu(),
        name=name,
    )(tile_expert, n_active, x_rows, w_gate, w_up, w_down)
