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

__all__ = ["grouped_swiglu", "NARROW_TILE", "WIDE_TILE"]

#: rows per tile: decode (a few pairs per expert) and prefill
NARROW_TILE = 16
WIDE_TILE = 128

# three double-buffered weight blocks of the largest expert served
# (2560 x 768 bf16: 3 x 2 x 3.9 MB) plus the row tiles and f32 temporaries
_VMEM_LIMIT = 64 * 1024 * 1024


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


@functools.partial(jax.jit, static_argnames=("tile",))
def grouped_swiglu(x_rows, tile_expert, n_active, w_gate, w_up, w_down, *,
                   tile: int):
    """``x_rows`` ``[n_tiles * tile, d]``; ``tile_expert`` int32
    ``[n_tiles]`` (the expert of each tile; tiles at or past ``n_active``
    repeat the last active tile's expert); ``n_active`` int32 ``[1]``;
    ``w_gate``/``w_up`` ``[E, d, f]``, ``w_down`` ``[E, f, d]``. Returns
    ``[n_tiles * tile, d]`` in ``x_rows.dtype``; rows of inactive tiles
    are zero."""
    m, d = x_rows.shape
    e, _, f = w_gate.shape
    if m % tile:
        raise ValueError(f"rows {m} are not whole tiles of {tile}")
    n_tiles = m // tile
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
        name=("grouped_swiglu_narrow" if tile <= NARROW_TILE
              else "grouped_swiglu_wide"),
    )(tile_expert, n_active, x_rows, w_gate, w_up, w_down)
