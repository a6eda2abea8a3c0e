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

The decode step's attention over the same page is the ABSORBED form: the
queries already carry ``W_kvb``'s key half, so a column is scored and
weighted as it lies, no expansion (``models/hybrid.py::
latent_decode_attention``). :func:`latent_decode_fwd` is its kernel: one grid
step takes ONE row's block of columns against all the row's query-heads
(one query's, or a few consecutive positions' side by side), the running
softmax of the row in VMEM scratch across its blocks, the blocks past the
row's fill neither copied nor computed.

:func:`chunk_kernel_refusal` and :func:`decode_kernel_refusal` are the
dispatchers' rules: which calls a kernel can serve, by what it can see at
trace time. :func:`record_paths` lets the caller that traces a program learn
which path each call took (and, under another kind, which path any other
dispatcher of the repo took: ``ops/kda_state.py``).
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.flash_attention import _fit_block
from chainermn_tpu.ops.page_write import pages_are_partitioned
from chainermn_tpu.utils import on_tpu

__all__ = ["latent_chunk_fwd", "chunk_kernel_refusal", "latent_decode_fwd",
           "decode_kernel_refusal", "decode_column_tile", "record_paths",
           "note_path", "COLUMN_TILE", "QUERY_TILE"]

LANES = 128
#: page columns one grid step expands and scores, and queries folded into the
#: running softmax at a time inside a step. Measured on a v5e at the widths
#: served (PERF.md §6, PR 32): what a step pays a query ROW whatever the
#: block's width (two reductions along the lanes, the rescaled accumulator)
#: makes 1,024 columns half again as fast as 512, and 2,048 no faster
COLUMN_TILE = 1024
QUERY_TILE = 1024
#: what fixes the decode kernel's column tile (decode_column_tile). A row
#: pays every grid step a fixed cost whatever the block's width and, past
#: its fill, half a block in the mean: the width that balances them grows
#: with the root of the capacity over a column's cost, which is the MXU's
#: under many query-heads (2,176 flop a head at 197 TFLOP/s) and the page's
#: 1,280 B at 819 GB/s under few. The step's cost is fitted to a v5e at the
#: two shapes served (PERF.md §6, PR 35; ms a call): 256 query-heads over
#: 3,328 columns 1.17 / 1.13 / 1.10 / 1.15 / 1.22 at 256 / 384 / 512 / 768
#: / 1,024 columns; 32 over 33,024 columns 0.55 / 0.47 / 0.49 / 0.53 / 0.59
#: at 512 / 1,024 / 2,048 / 4,096 / 8,192: the rule gives 512 and 2,048
DECODE_STEP_NS = 220.0
DECODE_HEAD_COLUMN_NS = 2176 / 197e3
DECODE_COLUMN_NS = 1280 / 819.0
_NEG = -1e30     # finite stand-in for -inf: exp(_NEG - m) is exactly 0
# q (two blocks), page block, weight block and the float32 output block,
# each double-buffered, the three float32 accumulators over the chunk and a
# [QUERY_TILE, COLUMN_TILE] float32 score tile with its temporaries: about
# 25 MB at the widths served, where the default limit is 16
_VMEM_LIMIT = 64 * 1024 * 1024

_trace = threading.local()


@contextlib.contextmanager
def record_paths(kind: str = "attention"):
    """Trace-time scope: yields a list that receives, in call order, the
    path every dispatcher of ``kind`` traced inside took: the latent
    attention's calls (``"kernel"`` or ``"loop:<reason>"``) by default,
    the recurrent state step's (``ops/kda_state.py``: ``"kernel"`` or
    ``"xla:<reason>"``) under ``"state_step"``. Scopes of different kinds
    nest without seeing each other."""
    scopes = getattr(_trace, "paths", None)
    if scopes is None:
        scopes = _trace.paths = {}
    before = scopes.get(kind)
    scopes[kind] = paths = []
    try:
        yield paths
    finally:
        scopes[kind] = before


def note_path(path: str, kind: str = "attention") -> None:
    paths: Optional[List[str]] = getattr(_trace, "paths", {}).get(kind)
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
    return _placement_refusal()


def _placement_refusal() -> Optional[str]:
    """What both kernels ask of where the program runs: the page read where
    it lies by one device, on a TPU."""
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


def decode_kernel_refusal(q_cat, page, r) -> Optional[str]:
    """Why :func:`latent_decode_fwd` cannot serve this call, or ``None`` if
    it can: by :func:`chunk_kernel_refusal`'s rules."""
    hq, width = q_cat.shape[1], page.shape[-1]
    if r % LANES or width % LANES:
        return (f"kv_rank {r}, page width {width} are not both multiples of "
                f"{LANES}")
    if q_cat.shape[-1] != width:
        return f"queries {q_cat.shape[-1]} wide, the page {width}"
    if hq % 8:
        return f"{hq} query-heads a row are no multiple of 8"
    if page.dtype not in (jnp.bfloat16, jnp.float32) or (
            q_cat.dtype != page.dtype):
        return (f"page {page.dtype}, queries {q_cat.dtype}: not one of "
                "bfloat16, float32")
    return _placement_refusal()


def decode_column_tile(t: int, hq: int) -> int:
    """Page columns one grid step of :func:`latent_decode_fwd` scores, from
    what the call shows — the page's capacity ``t`` and the query-heads a
    row ``hq`` —, in whole multiples of 256 and no more than the page has
    (the reasoning and the chip's readings are beside ``DECODE_STEP_NS``)."""
    column_ns = max(hq * DECODE_HEAD_COLUMN_NS, DECODE_COLUMN_NS)
    bk = max(round(math.sqrt(t * DECODE_STEP_NS / column_ns) / 256), 1) * 256
    return bk if bk < t else t


def _decode_kernel(nblk_ref, pos_ref, q_ref, page_ref, o_ref, acc, mrow, lrow,
                   *, scale, r, bk, t, offs):
    b, j = pl.program_id(0), pl.program_id(1)
    hq = q_ref.shape[1]
    pos = pos_ref[b]
    col0 = j * bk

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        mrow[...] = jnp.full_like(mrow, _NEG)
        lrow[...] = jnp.zeros_like(lrow)

    def fold(masked):
        blk = page_ref[0]
        s = jax.lax.dot_general(q_ref[0], blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        v = blk[:, :r]
        if masked:
            # query-head i sees columns <= pos + offs[i], inside the page
            head = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0)
            off = jnp.full((hq, 1), offs[0], jnp.int32)
            for i in range(1, hq):
                if offs[i] != offs[i - 1]:
                    off = off + jnp.where(head >= i, offs[i] - offs[i - 1], 0)
            col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            s = jnp.where(col <= jnp.minimum(pos + off, t - 1), s, _NEG)
            # what lies past the row's last seen column (in the page's last
            # block: past the page's end) need be no number, and a
            # probability of zero would not make it one
            seen = col0 + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0) <= jnp.minimum(pos + max(offs), t - 1)
            v = jnp.where(seen, v, jnp.zeros_like(v))
        # block 0 holds column 0, which every query sees: ``m`` is finite
        # from the row's first block on
        m_prev = mrow[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        lrow[:, :1] = alpha * lrow[:, :1] + jnp.sum(p, -1, keepdims=True)
        acc[...] = alpha * acc[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        mrow[:, :1] = m_new

    live = j < nblk_ref[b]
    # no column of the block above any query of the row, or past the page
    whole = (col0 + bk - 1 <= pos + min(offs)) & (col0 + bk <= t)
    pl.when(live & whole)(functools.partial(fold, False))
    pl.when(live & jnp.logical_not(whole))(functools.partial(fold, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = lrow[:, :1]
        o_ref[0] = acc[...] / jnp.where(l == 0.0, 1.0, l)


def latent_decode_fwd(q_cat, page, pos, live, scale, r, *, offs=None,
                      column_tile: Optional[int] = None):
    """``q_cat [B, Hq, W]``: the absorbed queries of row ``b`` (the no-rope
    query through ``W_kvb``'s key half beside the rotary query, padded to
    the page's width), ``Hq`` query-heads that are one position's heads or,
    with ``offs`` (a tuple of ``Hq`` ints), several positions' side by side:
    query-head ``i`` sees columns ``<= pos[b] + offs[i]`` of row ``b`` of
    ``page [N, T, W]``, whose first ``r`` values a column are the latent.
    Shapes as :func:`decode_kernel_refusal` admits them. Returns ``[B, Hq,
    r]`` float32, zeros for a row that is not ``live``. The page is read
    only, a block of ``column_tile`` columns (:func:`decode_column_tile`) a
    grid step, and of a row only the blocks that hold a column it sees."""
    b, hq, width = q_cat.shape
    t = page.shape[1]
    offs = (0,) * hq if offs is None else tuple(int(o) for o in offs)
    bk = decode_column_tile(t, hq) if column_tile is None else min(
        column_tile, t)
    nb = pl.cdiv(t, bk)
    pos = jnp.asarray(pos, jnp.int32)
    nblk = jnp.where(live, jnp.minimum((pos + max(offs)) // bk + 1, nb), 0)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, r=r, bk=bk, t=t,
                          offs=offs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, hq, width), lambda b, j, *_: (b, 0, 0)),
                # a block past the row's last needed one repeats it: the
                # pipeline sees the index it holds and starts no DMA
                pl.BlockSpec((1, bk, width), lambda b, j, nblk, pos: (
                    b, jnp.minimum(j, jnp.maximum(nblk[b] - 1, 0)), 0)),
            ],
            out_specs=pl.BlockSpec((1, hq, r), lambda b, j, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((hq, r), jnp.float32),
                            pltpu.VMEM((hq, LANES), jnp.float32),
                            pltpu.VMEM((hq, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=not on_tpu(),
        name="latent_decode_fwd",
    )(nblk, pos, q_cat, page)
