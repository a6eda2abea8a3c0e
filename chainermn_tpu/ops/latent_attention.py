"""Pallas latent chunk attention: a chunk of queries over a latent page, the
page re-expanded and scored a block of columns at a time in VMEM.

A latent page holds ``[c | k_r | padding]`` a column (``c`` the ``r``-value
latent, ``k_r`` the shared rotary key). The chunk's causal attention over it
in the EXPANDED form needs per-head keys and values, ``c @ W_kvb[:, h]``; an
expanded copy in HBM would scale with the page, and a blocked ``jax.numpy``
loop (``models/hybrid.py::latent_chunk_attention``) writes every block's
float32 scores and probabilities to HBM and reads them back (PERF.md §6,
PR 32). Here one grid step takes ONE head and ONE block of columns of row
``slots[b]`` of the page, where the page lies (no gather, no copy: the
block's index comes from the scalar-prefetched ``slots``), and in VMEM

* expands it through that head's ``W_kvb[:, h, :]`` to ``[block, d_nope]``
  keys and ``[block, d_v]`` values,
* scores the chunk's queries against them, a tile of queries at a time: the
  no-rope product plus the rotary product over the page's lanes after ``r``
  (``k_r`` beside zeros) against the rotary query padded likewise, so every
  slice is whole lane tiles,
* masks by column <= query position and folds into a running softmax whose
  float32 ``m``, ``l`` and ``acc`` live in VMEM scratch across the blocks.

The work follows the cursors while the program's shapes do not: blocks past
the last column a query of the row sees (``pos + valid``) repeat the last
needed block's index (no DMA) and are skipped, a query tile skips the
blocks wholly above its diagonal, and a query tile past ``valid`` is never
computed (its rows come back zero). The arithmetic is the loop's: operands
in the page's dtype, float32 accumulation, float32 ``exp``, probabilities
rounded to the page's dtype before the value product.

:func:`chunk_kernel_refusal` is the dispatcher's rule: which calls the kernel
can serve, by what it can see at trace time. :func:`record_paths` lets the
caller that traces a program learn which path each chunk call took.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.flash_attention import _fit_block
from chainermn_tpu.ops.page_write import pages_are_partitioned
from chainermn_tpu.utils import on_tpu

__all__ = ["latent_chunk_fwd", "chunk_kernel_refusal", "record_paths",
           "note_path", "COLUMN_TILE", "QUERY_TILE"]

LANES = 128
#: page columns one grid step expands and scores, and queries folded into the
#: running softmax at a time inside a step. Measured on a v5e at the widths
#: served (PERF.md §6, PR 32): what a step pays a query ROW whatever the
#: block's width (two reductions along the lanes, the rescaled accumulator)
#: makes 1,024 columns half again as fast as 512, and 2,048 no faster
COLUMN_TILE = 1024
QUERY_TILE = 1024
_NEG = -1e30     # finite stand-in for -inf: exp(_NEG - m) is exactly 0
# q (two blocks), page block, weight block and the float32 output block,
# each double-buffered, the three float32 accumulators over the chunk and a
# [QUERY_TILE, COLUMN_TILE] float32 score tile with its temporaries: about
# 25 MB at the widths served, where the default limit is 16
_VMEM_LIMIT = 64 * 1024 * 1024

_trace = threading.local()


@contextlib.contextmanager
def record_paths():
    """Trace-time scope: yields a list that receives, in call order, the
    path every chunk call traced inside took (``"kernel"`` or
    ``"loop:<reason>"``)."""
    before = getattr(_trace, "paths", None)
    _trace.paths = paths = []
    try:
        yield paths
    finally:
        _trace.paths = before


def note_path(path: str) -> None:
    paths: Optional[List[str]] = getattr(_trace, "paths", None)
    if paths is not None:
        paths.append(path)


def chunk_kernel_refusal(q_nope, q_rope, page, w_kvb) -> Optional[str]:
    """Why :func:`latent_chunk_fwd` cannot serve this call, or ``None`` if
    it can: every slice the kernel takes is whole lane tiles, the page is
    read where it lies by one device, and the program runs on a TPU."""
    c, dn, dr = q_nope.shape[1], q_nope.shape[-1], q_rope.shape[-1]
    r, width = w_kvb.shape[0], page.shape[-1]
    dv = w_kvb.shape[-1] - dn
    if r % LANES or dn % LANES or dv % LANES:
        return (f"kv_rank {r}, d_nope {dn}, d_v {dv} are not all multiples "
                f"of {LANES}")
    if width % LANES:
        return f"page width {width} is no multiple of {LANES}"
    if width - r < dr:
        return f"page holds {width - r} values after the latent, d_rope {dr}"
    if c % 8:
        return f"chunk of {c} queries is no multiple of 8"
    if page.dtype not in (jnp.bfloat16, jnp.float32) or not (
            q_nope.dtype == q_rope.dtype == w_kvb.dtype == page.dtype):
        return (f"page {page.dtype}, queries {q_nope.dtype}/{q_rope.dtype}, "
                f"weights {w_kvb.dtype}: not one of bfloat16, float32")
    if pages_are_partitioned():
        return "pages split over several devices"
    if not on_tpu():
        return "not on a TPU"
    return None


def _kernel(rows_ref, pos_ref, valid_ref, nblk_ref, qn_ref, qr_ref, page_ref,
            w_ref, o_ref, acc, mrow, lrow, kv, *, scale, r, dn, tq, bk):
    del rows_ref                    # consumed by the page's index map
    b, j = pl.program_id(0), pl.program_id(2)
    pos, valid = pos_ref[b], valid_ref[b]
    col0 = j * bk

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        mrow[...] = jnp.full_like(mrow, _NEG)
        lrow[...] = jnp.zeros_like(lrow)

    def fold(i, masked):
        rows = pl.ds(i * tq, tq)
        nt = (((1,), (1,)), ((), ()))           # q @ k^T
        s = (jax.lax.dot_general(qn_ref[0, rows, :], kv[:, :dn], nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0, rows, :], page_ref[0, :, r:],
                                   nt, preferred_element_type=jnp.float32)
             ) * scale
        if masked:
            # a padded query beside real ones sees what the last real one
            # sees: nothing past ``pos + valid``, where the page may end
            qpos = jnp.minimum(pos + valid - 1, pos + i * tq
                               + jax.lax.broadcasted_iota(
                                   jnp.int32, (tq, 1), 0))
            col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            s = jnp.where(col <= qpos, s, _NEG)
        # block 0 holds column 0, which every query sees, and it is every
        # live tile's first: ``m`` is finite from then on
        m_prev = mrow[rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        lrow[rows, :1] = alpha * lrow[rows, :1] + jnp.sum(p, -1,
                                                          keepdims=True)
        acc[rows, :] = alpha * acc[rows, :] + jnp.dot(
            p.astype(kv.dtype), kv[:, dn:],
            preferred_element_type=jnp.float32)
        mrow[rows, :1] = m_new

    @pl.when(j < nblk_ref[b])
    def _block():
        # this head's keys and values of the block, rounded as the loop's
        # einsum rounds them
        x = jnp.dot(page_ref[0, :, :r], w_ref[...],
                    preferred_element_type=jnp.float32)
        # what lies past the row's last seen column (in the page's last
        # block: past the page's end) need be no number, and a probability
        # of zero would not make it one
        seen = col0 + jax.lax.broadcasted_iota(
            jnp.int32, (bk, 1), 0) < pos + valid
        kv[...] = jnp.where(seen, x, 0.0).astype(kv.dtype)
        for i in range(qn_ref.shape[1] // tq):
            lo = pos + i * tq                   # the tile's first position
            live = (i * tq < valid) & (col0 <= lo + tq - 1)
            below = col0 + bk - 1 <= lo         # no column above any query
            pl.when(live & below)(functools.partial(fold, i, False))
            pl.when(live & jnp.logical_not(below))(
                functools.partial(fold, i, True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = lrow[:, :1]
        o_ref[0] = acc[...] / jnp.where(l == 0.0, 1.0, l)


def latent_chunk_fwd(q_nope, q_rope, page, w_kvb, pos, valid, slots, scale,
                     *, column_tile: int = COLUMN_TILE,
                     query_tile: int = QUERY_TILE):
    """``q_nope [B, C, H, dn]``, ``q_rope [B, C, H, dr]``: the queries at
    positions ``pos[b] + 0..C-1``, of which the first ``valid[b]`` are real;
    row ``slots[b]`` of ``page [N, T, W]`` holds ``[c (r) | k_r (dr) |
    zeros]`` for every column those queries see (a slot past ``N`` is a
    sentinel row: nothing is computed for it); ``w_kvb [r, H, dn + dv]``;
    ``pos``, ``valid``, ``slots`` int32 ``[B]``. Shapes as
    :func:`chunk_kernel_refusal` admits them. Returns ``[B, C, H, dv]``
    float32, zero in the query tiles past ``valid``. The page is read only."""
    b, c, h, dn = q_nope.shape
    n, t, width = page.shape
    r = w_kvb.shape[0]
    dv = w_kvb.shape[-1] - dn
    wr = width - r
    tq = _fit_block(query_tile, c)      # divides c, a multiple of 8
    bk = column_tile if t >= column_tile else t
    nb = pl.cdiv(t, bk)
    pos, valid, slots = (jnp.asarray(a, jnp.int32)
                         for a in (pos, valid, slots))
    valid = jnp.where(slots < n, valid, 0)
    nblk = jnp.where(valid > 0, jnp.minimum((pos + valid + bk - 1) // bk, nb),
                     0)
    q_rope = jnp.pad(q_rope, ((0, 0),) * 3 + ((0, wr - q_rope.shape[-1]),))
    per_head = lambda b, h, j, *_: (b, 0, h)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, r=r, dn=dn, tq=tq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h, nb),
            in_specs=[
                pl.BlockSpec((1, c, dn), per_head),
                pl.BlockSpec((1, c, wr), per_head),
                # a block past the row's last needed one repeats it: the
                # pipeline sees the index it holds and starts no DMA
                pl.BlockSpec((1, bk, width),
                             lambda b, h, j, rows, pos, valid, nblk: (
                                 rows[b], jnp.minimum(
                                     j, jnp.maximum(nblk[b] - 1, 0)), 0)),
                pl.BlockSpec((r, dn + dv), lambda b, h, j, *_: (0, h)),
            ],
            out_specs=pl.BlockSpec((1, c, dv), per_head),
            scratch_shapes=[pltpu.VMEM((c, dv), jnp.float32),
                            pltpu.VMEM((c, LANES), jnp.float32),
                            pltpu.VMEM((c, LANES), jnp.float32),
                            pltpu.VMEM((bk, dn + dv), page.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, c, h * dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=not on_tpu(),
        name="latent_chunk_fwd",
    )(jnp.minimum(slots, n - 1), pos, valid, nblk,
      q_nope.reshape(b, c, h * dn), q_rope.reshape(b, c, h * wr), page,
      w_kvb.reshape(r, h * (dn + dv)))
    return out.reshape(b, c, h, dv)
