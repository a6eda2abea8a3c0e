"""Grouped-query attention over K/V leaves of a serving cache, in blocks of
columns under an online softmax, at the dtype the leaves are stored in.

A K/V leaf here is FLAT: ``[rows, columns, n_kv · d_head]`` — a column is one
position's keys (or values) of every KV head side by side, whole lane tiles
wide, so that a block of columns is a plain ``[block, n_kv · d_head]`` matrix
read where it lies. Two kinds of leaf:

* a PAGE (``columns`` = the capacity): column ``j`` holds position ``j``;
* a RING (``columns`` = the window ``W``): column ``j`` holds the newest
  position ``≡ j (mod W)`` — it wraps by nature, and after any write every
  column that holds a position at all holds one inside the window of the
  row's newest position.

Four paths (``models/hybrid.py::GQAMixer`` scopes them ``gqa_chunk``,
``swa_chunk``, ``gqa_decode``, ``swa_decode``):

* :func:`page_chunk_attention` — a chunk ``[B, C]`` of queries at the rows'
  cursors against the FILLED columns of a page plus the chunk itself: no
  score array over the page, no float32 copy or head-repeat of the page;
* :func:`ring_chunk_attention` — the same chunk on a window layer: the
  ring's last ``W`` positions laid out in position order before the chunk's
  own keys (:func:`_ring_then_chunk`), every query against the keys inside
  its band (a 2,048-token chunk is four windows: the band is skipped INSIDE
  the chunk too); the caller then writes the chunk's last ``min(valid, W)``
  rows at ``position mod W`` (:func:`write_ring`);
* :func:`page_decode_attention` — one query a row: ONE loop over the (row,
  block) pairs that hold a column a LIVE row has filled, so a step reads
  what is cached row by row, not the capacity;
* :func:`ring_decode_attention` — one query a row over the whole ring
  (``≤ W`` columns), every row at once.

The two chunk paths are DISPATCHERS over two forms, chosen at trace time by
what the call shows (:func:`kv_chunk_refusal`: lane-tile widths, one dtype,
leaves that one device holds, a TPU; the choice is noted for whoever traces
the program, ``latent_attention.record_paths``):

* :func:`kv_chunk_fwd`, ONE Pallas kernel for both — a blocked flash
  forward whose scores and probabilities never leave VMEM. A grid step is
  (row, KV head, block of columns): the KV head's keys and values are lanes
  ``[k · d, (k + 1) · d)`` of the leaf, read where they lie through the
  scalar-prefetched ``slots`` (no gather, no widened K or V), its ``heads /
  n_kv`` query heads stay resident with their running softmax while the
  row's blocks pass. The work follows the cursors while the shapes do not:
  blocks no real query of the row sees repeat a needed block's index (no
  DMA) and are skipped, a query tile skips the blocks wholly above its
  diagonal, before the first held column or below its band and masks only
  the blocks that cross one of those edges, queries past ``valid`` come
  back zero, a sentinel slot computes nothing. Per-row scalars ``q0`` (the
  column of query 0), ``first`` (the first column that holds a position) and
  ``valid``, a static ``window``: the page is ``q0 = pos``, ``first = 0``;
  the ring is the SAME kernel over the laid-out keys with ``q0 = W``,
  ``first = max(W − pos, 0)``, ``window = W``;
* the ``jax.numpy`` bodies (:func:`_page_chunk_loop`: one loop over blocks
  of ``CHUNK_BLOCK`` columns up to the last column a query sees;
  :func:`_ring_chunk_tiles`: query tiles of ``W`` against the ``2W`` keys
  that can lie inside their band) wherever the rule refuses — off a TPU,
  ``d_head`` or a leaf's width no lane tiles, a chunk that is no multiple
  of 8 (or of ``W``), pages over several devices. Every float32 score
  array of theirs goes through HBM several times: a tenth of the matrix
  unit's peak where the kernel reads a third and more (PERF.md §6, PR 44).

The arithmetic is one: operands in the leaf's dtype, float32 accumulation,
float32 ``exp``, probabilities rounded to the leaf's dtype before the value
product; float32 leaves keep float32 products (:func:`_exact`).

Grouping without a widened K or V: the chunk paths fold the query heads to
``[n_kv, heads / n_kv]`` and contract against the ``n_kv`` heads the block
has. The one-query paths lay the row's queries out BLOCK-DIAGONALLY,
``[n_kv · d_head, heads]`` with head ``h``'s query in the rows of its KV head
and zeros elsewhere: scores are then ``block @ Q`` and values ``pᵀ @ block``,
the block the left operand of the one and the right of the other as it lies
(a product batched over the KV heads makes the compiler re-lay the block),
at ``n_kv`` times the flops of a step that is bound by its bytes.

The one-query paths are plain ``jax.numpy``: a Pallas kernel for the decode
page loop (bound by bandwidth, another grid) is the later step (ROADMAP
S19's decode half).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops import latent_attention
from chainermn_tpu.ops.flash_attention import _fit_block
from chainermn_tpu.ops.latent_attention import _NEG, LANES
from chainermn_tpu.utils import on_tpu

__all__ = ["CHUNK_BLOCK", "DECODE_BLOCK", "COLUMN_TILE", "QUERY_TILE",
           "column_blocks", "decode_blocks", "decode_columns",
           "kv_chunk_fwd", "kv_chunk_refusal",
           "page_chunk_attention", "page_decode_attention",
           "ring_chunk_attention", "ring_decode_attention", "ring_positions",
           "write_ring", "write_window"]

#: page columns a block of a chunk call: its float32 scores are
#: ``[heads, chunk, CHUNK_BLOCK]``
CHUNK_BLOCK = 512
#: page columns of one row a decode visit reads: a visit pays the carried
#: accumulators' row out and in whatever the block's width, and a row's last
#: block is read whole whatever its fill
DECODE_BLOCK = 2048
#: leaf columns one grid step of :func:`kv_chunk_fwd` scores, and queries of
#: one head folded into the running softmax at a time inside a step (on a
#: ring no more than the window: a wider tile only masks more). Measured on a
#: v5e at the shapes served (PERF.md §6, PR 44; 2,048 queries, 48 heads over
#: 8, a page of 32,768 columns at a cursor of 6,144, ms a call): what a fold
#: pays a query ROW whatever the block's width (``m``, ``l`` and the
#: accumulator out and in, two reductions along the lanes) makes 1,024
#: columns over half again as fast as 512 — 5.02 at 512 x 512, 3.20 at 512 x
#: 1,024, 3.08 at 1,024 x 1,024 (59% of the matrix unit's peak; 71% at a
#: cursor of 28,672) — and 2,048 columns (3.48) and 256 (9.15) slower; query
#: tiles of 128 and 256 lose a fifth and more. The ring (64 heads, window
#: 512): 1.45-1.50 at 512 x 1,024, 1.54 at 512 x 512, 1.65 at 1,024 x 1,024
COLUMN_TILE = 1024
QUERY_TILE = 1024
# the queries of a KV head's group and the float32 output block over the
# whole chunk, each double-buffered, ``m`` and ``l`` a lane tile a query and
# a [QUERY_TILE, COLUMN_TILE] float32 score tile with its temporaries: about
# 55 MB at 8 query heads over 2,048 queries, where the default limit is 16
_VMEM_LIMIT = 96 * 1024 * 1024


def _exact(page):
    """float32 leaves keep float32 products (the matrix unit's default would
    round them to bfloat16)."""
    return jax.lax.Precision.HIGHEST if page.dtype == jnp.float32 else None


def write_window(page, chunk, pos, n, slots):
    """``page [N, T, w]`` with ``chunk[b, :n[b]]`` written into row
    ``slots[b]`` at columns ``pos[b] ..`` and everything else as it was
    (``chunk [B, C, w]``, ``C <= T``; a slot past ``N`` writes nothing):
    one window a chunk row, read, blended and put back where the page
    lies."""
    rows, t, w = page.shape
    c = chunk.shape[1]
    chunk = chunk.astype(page.dtype)
    for b in range(chunk.shape[0]):
        s0 = jnp.clip(pos[b], 0, t - c)     # the window stays inside the page
        off = pos[b] - s0
        j = jnp.arange(c)
        keep = (j >= off) & (j - off < n[b]) & (slots[b] < rows)
        at = (jnp.minimum(slots[b], rows - 1), s0, 0)
        new = jnp.where(keep[:, None], jnp.roll(chunk[b], off, axis=0),
                        jax.lax.dynamic_slice(page, at, (1, c, w))[0])
        page = jax.lax.dynamic_update_slice(page, new[None], at)
    return page


def column_blocks(page, top, block, slots):
    """(block width, number of blocks that cover columns ``< top``, a
    function ``(page, j) -> (block j of the rows ``slots`` [B, block, w], its
    column ids, which of them are block j's own)``). The last block of a
    page that is no multiple of the width starts early, inside the page, and
    disowns the columns the block before it has."""
    rows, t, w = page.shape
    block = min(block, t)

    def take(page, j):
        s0 = jnp.minimum(j * block, t - block)
        col = s0 + jnp.arange(block)
        blk = jnp.concatenate([jax.lax.dynamic_slice(
            page, (jnp.minimum(slots[b], rows - 1), s0, 0), (1, block, w))
            for b in range(slots.shape[0])])
        return blk, col, col >= j * block

    return block, (top + block - 1) // block, take


def _fold(q, n_kv):
    """``[B, C, H, d] -> [B, C, n_kv, H / n_kv, d]``: query head ``h`` reads
    KV head ``h // (H / n_kv)``."""
    b, c, h, d = q.shape
    return q.reshape(b, c, n_kv, h // n_kv, d)


def _unfold(o):
    """``[B, n_kv, G, C, d] -> [B, C, H, d]``."""
    b, n_kv, g, c, d = o.shape
    return jnp.moveaxis(o, 3, 1).reshape(b, c, n_kv * g, d)


def kv_chunk_refusal(q, k_leaf, window: Optional[int] = None
                     ) -> Optional[str]:
    """Why :func:`kv_chunk_fwd` cannot serve this call, or ``None`` if it
    can: a head is whole lane tiles of a column, the chunk whole sublane
    tiles (and, on a ring, whole windows), leaf and queries one dtype the
    matrix unit takes, the leaf read where it lies by one device, on a
    TPU. ``q [B, C, H, d]``, ``k_leaf [N, T, n_kv · d]``, ``window`` the
    ring's length where the leaf is one."""
    c, d = q.shape[1], q.shape[-1]
    width = k_leaf.shape[-1]
    if d % LANES:
        return f"d_head {d} is no multiple of {LANES}"
    if width % LANES:
        return f"leaf width {width} is no multiple of {LANES}"
    if c % 8:
        return f"chunk of {c} queries is no multiple of 8"
    if window and c % window:
        return f"chunk of {c} queries is no multiple of the window {window}"
    if k_leaf.dtype not in (jnp.bfloat16, jnp.float32) or (
            q.dtype != k_leaf.dtype):
        return (f"leaf {k_leaf.dtype}, queries {q.dtype}: not one of "
                "bfloat16, float32")
    return latent_attention._placement_refusal()


def _takes_kernel(q, leaf, window=None) -> bool:
    """The dispatchers' choice, noted for whoever traces the program."""
    refusal = kv_chunk_refusal(q, leaf, window)
    latent_attention.note_path(
        "kernel" if refusal is None else f"loop:{refusal}")
    return refusal is None


def _chunk_kernel(rows_ref, q0_ref, first_ref, valid_ref, jlo_ref, jhi_ref,
                  q_ref, k_ref, v_ref, o_ref, mrow, lrow, *, scale, window,
                  tq, bk, exact):
    del rows_ref                    # consumed by the leaves' index maps
    b, j = pl.program_id(0), pl.program_id(2)
    q0, first, valid = q0_ref[b], first_ref[b], valid_ref[b]
    groups, c = q_ref.shape[2], q_ref.shape[3]
    col0 = j * bk
    nt = (((1,), (1,)), ((), ()))               # q @ k^T

    def each_tile(do):
        """``do(g, rows, i)`` for every (query head of the group, query tile
        ``i``): loops, not unrolled code, over what may be 64 tiles."""
        def over_heads(i, _):
            rows = pl.ds(pl.multiple_of(i * tq, tq), tq)
            jax.lax.fori_loop(0, groups, lambda g, _: do(g, rows, i), None)
        jax.lax.fori_loop(0, c // tq, over_heads, None)

    @pl.when(j == 0)
    def _init():
        def clear(g, rows, i):
            o_ref[0, 0, g, rows, :] = jnp.zeros((tq, o_ref.shape[-1]),
                                                jnp.float32)
            mrow[g, rows, :] = jnp.full((tq, LANES), _NEG, jnp.float32)
            lrow[g, rows, :] = jnp.zeros((tq, LANES), jnp.float32)
        each_tile(clear)

    def fold(rows, seen, v, g, _):
        """Head ``g``'s queries ``rows`` against the block: every query sees
        every column of it, or ``seen [tq, bk]`` says which and ``v`` is the
        block's values with what nobody sees cleared (both the tile's, one
        for the group's heads)."""
        s = jax.lax.dot_general(q_ref[0, 0, g, rows, :], k_ref[0], nt,
                                precision=exact,
                                preferred_element_type=jnp.float32) * scale
        if seen is None:
            v = v_ref[0]
        else:
            s = jnp.where(seen, s, -jnp.inf)
        # ``m`` starts at a finite stand-in for -inf, so a query that meets
        # its first block with no column of it in sight (a band's corner)
        # keeps ``p`` 0 and ``alpha`` 1 and nothing is a NaN
        m_prev = mrow[g, rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        lrow[g, rows, :1] = alpha * lrow[g, rows, :1] + jnp.sum(
            p, -1, keepdims=True)
        o_ref[0, 0, g, rows, :] = alpha * o_ref[0, 0, g, rows, :] + jnp.dot(
            p.astype(v.dtype), v, precision=exact,
            preferred_element_type=jnp.float32)
        mrow[g, rows, :1] = m_new

    def tile(i, _):
        rows = pl.ds(pl.multiple_of(i * tq, tq), tq)
        lo = q0 + i * tq                        # the tile's first query
        # a padded query beside real ones sees what the last real one sees
        last = jnp.minimum(lo + tq, q0 + valid) - 1
        live = ((i * tq < valid) & (col0 <= last)
                & (col0 + bk - 1 >= first))
        whole = (col0 + bk - 1 <= lo) & (col0 >= first)
        if window is not None:
            live &= col0 + bk - 1 > lo - window
            whole &= col0 > lo + tq - 1 - window

        @pl.when(live & whole)
        def _whole():
            jax.lax.fori_loop(0, groups,
                              functools.partial(fold, rows, None, None), None)

        @pl.when(live & jnp.logical_not(whole))
        def _masked():
            at = jnp.minimum(lo + jax.lax.broadcasted_iota(
                jnp.int32, (tq, 1), 0), q0 + valid - 1)
            col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            seen = (col <= at) & (col >= first)
            if window is not None:
                seen &= col > at - window
            # a column no query of the row sees (before ``first``, past ``q0
            # + valid``, past the leaf's end) need hold no number, and a
            # probability of zero would not make it one
            col = col0 + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            v = jnp.where((col >= first) & (col < q0 + valid), v_ref[0], 0.0)
            jax.lax.fori_loop(
                0, groups, functools.partial(
                    fold, rows, seen, v), None)

    @pl.when((j >= jlo_ref[b]) & (j < jhi_ref[b]))
    def _block():
        jax.lax.fori_loop(0, c // tq, tile, None)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        def divide(g, rows, i):
            l = lrow[g, rows, :1]
            real = i * tq + jax.lax.broadcasted_iota(
                jnp.int32, (tq, 1), 0) < valid
            o_ref[0, 0, g, rows, :] = jnp.where(
                real, o_ref[0, 0, g, rows, :] / jnp.where(l == 0.0, 1.0, l),
                0.0)
        each_tile(divide)


def kv_chunk_fwd(q, k_leaf, v_leaf, q0, first, valid, slots, scale, *,
                 window: Optional[int] = None,
                 column_tile: int = COLUMN_TILE,
                 query_tile: int = QUERY_TILE):
    """Grouped-query attention of a chunk over a flat K/V leaf, ONE Pallas
    kernel: scores and probabilities in VMEM, the work following the rows'
    cursors. ``q [B, C, H, d]``, the first ``valid[b]`` queries of a row
    real; row ``slots[b]`` of ``k_leaf``/``v_leaf [N, T, n_kv · d]`` is the
    row's keys and values (a slot past ``N`` is a sentinel row: nothing is
    computed for it). Query ``i`` sees column ``J`` iff ``first[b] <= J <=
    q0[b] + i`` and, with a ``window`` ``W`` (static), ``J > q0[b] + i -
    W``: ``q0`` is the column of query 0, ``first`` the first column that
    holds a position; ``q0``, ``first``, ``valid``, ``slots`` int32 ``[B]``.
    Shapes as :func:`kv_chunk_refusal` admits them. Returns ``[B, C, H, d]``
    float32, zero in the rows past ``valid``. The leaves are read only, and
    of a row only the column blocks a real query of it sees.

    One grid step is (row, KV head, block of columns): the head's ``H /
    n_kv`` query heads, all ``C`` queries of each, stay in VMEM with their
    running softmax (``m``, ``l`` and the output block as the accumulator)
    while the row's blocks pass, each read ONCE — 128 lanes of its columns.
    Inside a step a query tile skips the block where it lies wholly above
    the tile's diagonal, before ``first`` or below the tile's band, and
    masks only where the block crosses one of those edges."""
    b, c, h, d = q.shape
    n, t, width = k_leaf.shape
    n_kv = width // d
    groups = h // n_kv
    # divides c, a multiple of 8
    tq = _fit_block(min(query_tile, window or query_tile), c)
    bk = column_tile if t >= column_tile else t
    nb = pl.cdiv(t, bk)
    q0, first, valid, slots = (jnp.asarray(a, jnp.int32)
                               for a in (q0, first, valid, slots))
    valid = jnp.where(slots < n, valid, 0)
    low = first if window is None else jnp.maximum(first, q0 - window + 1)
    jhi = jnp.where(valid > 0, jnp.minimum((q0 + valid + bk - 1) // bk, nb),
                    0)
    jlo = jnp.clip(low // bk, 0, jnp.maximum(jhi - 1, 0))
    # a KV head's query heads side by side, each a [C, d] matrix
    qg = jnp.moveaxis(q.reshape(b, c, n_kv, groups, d), 1, 3)
    per_head = lambda b, k, j, *_: (b, k, 0, 0, 0)
    # a block outside the row's needed ones repeats the nearest needed one:
    # the pipeline sees the index it holds and starts no DMA
    leaf_spec = pl.BlockSpec(
        (1, bk, d), lambda b, k, j, rows, q0, first, valid, jlo, jhi: (
            rows[b], jnp.clip(j, jlo[b], jnp.maximum(jhi[b] - 1, jlo[b])),
            k))
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, window=window, tq=tq,
                          bk=bk, exact=_exact(k_leaf)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(b, n_kv, nb),
            in_specs=[pl.BlockSpec((1, 1, groups, c, d), per_head),
                      leaf_spec, leaf_spec],
            out_specs=pl.BlockSpec((1, 1, groups, c, d), per_head),
            scratch_shapes=[pltpu.VMEM((groups, c, LANES), jnp.float32),
                            pltpu.VMEM((groups, c, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, groups, c, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=not on_tpu(),
        name="kv_chunk_fwd",
    )(jnp.minimum(slots, n - 1), q0, first, valid, jlo, jhi, qg, k_leaf,
      v_leaf)
    return _unfold(out)


def page_chunk_attention(q, k_page, v_page, pos, slots, scale, valid=None):
    """Causal attention of a chunk of queries over a K/V page. ``q [B, C, H,
    d]``: the queries at positions ``pos[b] + 0..C-1``, the first
    ``valid[b]`` of them real (all of them without ``valid``); row
    ``slots[b]`` of ``k_page``/``v_page [N, T, n_kv · d]`` holds every
    column up to those positions, the chunk's own included (the caller wrote
    them). Returns ``(o [B, C, H, d]`` float32, ``k_page, v_page)``.

    Two forms, chosen here at trace time by what the call shows
    (:func:`kv_chunk_refusal`; the choice is noted for whoever traces the
    program, ``latent_attention.record_paths``): :func:`kv_chunk_fwd` with
    ``q0 = pos`` and ``first = 0``, which stops a row at ``pos + valid`` and
    leaves the queries past ``valid`` zero, or the ``jax.numpy`` loop
    (:func:`_page_chunk_loop`), which computes every row."""
    b, c = q.shape[:2]
    if not _takes_kernel(q, k_page):
        return _page_chunk_loop(q, k_page, v_page, pos, slots, scale)
    return kv_chunk_fwd(
        q, k_page, v_page, pos, jnp.zeros((b,), jnp.int32),
        jnp.full((b,), c, jnp.int32) if valid is None else valid, slots,
        scale), k_page, v_page


def _page_chunk_loop(q, k_page, v_page, pos, slots, scale):
    """:func:`page_chunk_attention` block by block in ``jax.numpy``: blocks
    past the last column a query sees are not read; the pages ride the
    loop's carry and the caller keeps what comes out, so that a page just
    written is read where it lies."""
    b, c, h, d = q.shape
    n_kv = k_page.shape[-1] // d
    qg = _fold(q.astype(k_page.dtype), n_kv)
    qpos = pos[:, None] + jnp.arange(c)[None]                       # [B, C]
    real = slots < k_page.shape[0]
    block, n_blocks, take = column_blocks(
        k_page, jnp.max(jnp.where(real, pos, 0)) + c, CHUNK_BLOCK, slots)
    exact = _exact(k_page)

    def body(j, carry):
        k_page, v_page, m, l, acc = carry
        kb, col, own = take(k_page, j)
        vb = take(v_page, j)[0]
        s = jnp.einsum("bqkgd,btkd->bkgqt", qg,
                       kb.reshape(b, block, n_kv, d), precision=exact,
                       preferred_element_type=jnp.float32) * scale
        seen = (col[None, None] <= qpos[:, :, None]) & own      # [B, C, blk]
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bkgqt,btkd->bkgqd", p.astype(vb.dtype),
            vb.reshape(b, block, n_kv, d), precision=exact,
            preferred_element_type=jnp.float32)
        return k_page, v_page, m_new, l, acc

    # column 0 is seen by every query, so ``m`` is finite after block 0
    shape = (b, n_kv, h // n_kv, c)
    init = (k_page, v_page, jnp.full(shape, -jnp.inf, jnp.float32),
            jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (d,), jnp.float32))
    k_page, v_page, _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    return _unfold(acc / l[..., None]), k_page, v_page


def ring_positions(pos, window):
    """``[B, W]``: the position ring column ``j`` of row ``b`` holds once
    position ``pos[b]`` is written — the newest one ``≡ j (mod W)``, negative
    where the column holds none yet."""
    p = pos[:, None]
    return p - (p - jnp.arange(window)[None]) % window


def _call_rows(ring, slots):
    """The call's rows of ``ring [N, W, w]``: all of them without ``slots``,
    else rows ``slots`` (a slot past ``N`` reads the last row; its write
    drops)."""
    return ring if slots is None else ring[
        jnp.minimum(slots, ring.shape[0] - 1)]


def write_ring(ring, chunk, pos, n, slots=None):
    """``ring [N, W, w]`` with the LAST ``min(n[b], W)`` of ``chunk[b,
    :n[b]]`` (positions ``pos[b] ..``) written into row ``slots[b]`` (``b``
    itself without ``slots``) at ``position mod W``, everything else as it
    was; a slot past ``N`` and a row with ``n`` 0 write nothing. ``chunk [B,
    C, w]``."""
    window = ring.shape[1]
    c = chunk.shape[1]
    old = _call_rows(ring, slots)
    # the newest position of the chunk that lands in each column
    newest = ring_positions(pos + n - 1, window)                    # [B, W]
    mine = (newest >= pos[:, None]) & (n[:, None] > 0)
    src = jnp.clip(newest - pos[:, None], 0, c - 1)
    new = jnp.where(mine[..., None], jnp.take_along_axis(
        chunk.astype(ring.dtype), src[..., None], axis=1), old)
    if slots is None:
        return new
    return ring.at[slots].set(new, mode="drop")


def _ring_then_chunk(ring, chunk, pos, slots, pad=0):
    """``[B, W + C + pad, w]``: the ring's last ``W`` positions in position
    order (``pos - W ..``; what a ring not yet full holds before position 0
    is nobody's), then the chunk's own ``[B, C, w]``, then ``pad`` zeros."""
    window = ring.shape[1]
    at = (pos[:, None] + jnp.arange(window)[None]) % window
    prev = jnp.take_along_axis(_call_rows(ring, slots), at[..., None],
                               axis=1)
    chunk = jnp.pad(chunk.astype(ring.dtype), ((0, 0), (0, pad), (0, 0)))
    return jnp.concatenate([prev, chunk], 1)


def ring_chunk_attention(q, k, v, k_ring, v_ring, pos, slots, scale,
                         valid=None):
    """Causal attention of a chunk of queries inside a window of ``W``
    positions, ``W`` the ring's length. ``q [B, C, H, d]`` at positions
    ``pos[b] + 0..C-1``, the first ``valid[b]`` of them real (all of them
    without ``valid``); ``k``, ``v [B, C, n_kv · d]`` the chunk's own;
    row ``slots[b]`` (``b`` itself without ``slots``) of ``k_ring``/``v_ring
    [N, W, n_kv · d]`` holds the positions before ``pos[b]`` (the chunk not
    yet written: it would overwrite what its first queries see). Query ``i``
    sees key position ``p`` where ``pos + i - W < p <= pos + i``: the
    ring's positions are laid in order before the chunk's
    (:func:`_ring_then_chunk`: key ``J`` holds position ``pos - W + J``).
    Returns ``o [B, C, H, d]`` float32.

    Two forms, chosen as :func:`page_chunk_attention` chooses:
    :func:`kv_chunk_fwd` over the laid-out keys with ``q0 = W``, ``first =
    max(W - pos, 0)`` and the window ``W``, or the ``jax.numpy`` tiles
    (:func:`_ring_chunk_tiles`)."""
    b, c = q.shape[:2]
    window = k_ring.shape[1]
    if not _takes_kernel(q, k_ring, window):
        return _ring_chunk_tiles(q, k, v, k_ring, v_ring, pos, slots, scale)
    real = True if slots is None else slots < k_ring.shape[0]
    return kv_chunk_fwd(
        q, _ring_then_chunk(k_ring, k, pos, slots),
        _ring_then_chunk(v_ring, v, pos, slots),
        jnp.full((b,), window, jnp.int32), jnp.maximum(window - pos, 0),
        jnp.where(real, jnp.full((b,), c) if valid is None else valid, 0),
        jnp.arange(b), scale, window=window)


def _ring_chunk_tiles(q, k, v, k_ring, v_ring, pos, slots, scale):
    """:func:`ring_chunk_attention` in ``jax.numpy``: query tile ``t`` (``W``
    queries) meets keys ``[tW, (t + 2)W)`` of its row alone, one softmax a
    tile."""
    b, c, h, d = q.shape
    window, w = k_ring.shape[1:]
    n_kv = w // d
    n_tiles = -(-c // window)
    pad = n_tiles * window - c
    exact = _exact(k_ring)
    kk, vv = (_ring_then_chunk(ring, chunk, pos, slots, pad).reshape(
        b, (n_tiles + 1) * window, n_kv, d)
        for ring, chunk in ((k_ring, k), (v_ring, v)))
    qg = jnp.pad(_fold(q.astype(k_ring.dtype), n_kv),
                 ((0, 0), (0, pad)) + ((0, 0),) * 3)
    qi = jnp.arange(window)[:, None]            # query, inside its tile
    kj = jnp.arange(2 * window)[None]           # key, from the tile's start

    def tile(t):
        take = lambda a, n: jax.lax.dynamic_slice_in_dim(a, t * window, n, 1)
        s = jnp.einsum("bqkgd,btkd->bkgqt", take(qg, window),
                       take(kk, 2 * window), precision=exact,
                       preferred_element_type=jnp.float32) * scale
        # key at ring-then-chunk index J holds position pos - W + J
        band = (kj > qi) & (kj <= qi + window)                   # [W, 2W]
        filled = t * window + kj >= window - pos[:, None, None]  # [B, 1, 2W]
        s = jnp.where((band[None] & filled)[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)               # a query sees itself
        return jnp.einsum("bkgqt,btkd->bkgqd", p.astype(vv.dtype),
                          take(vv, 2 * window), precision=exact,
                          preferred_element_type=jnp.float32)

    o = jax.lax.map(tile, jnp.arange(n_tiles))          # [nt, B, k, g, W, d]
    o = jnp.moveaxis(o, 0, 3).reshape(b, n_kv, h // n_kv, n_tiles * window, d)
    return _unfold(o[:, :, :, :c])


def _diagonal_queries(q, n_kv):
    """``q [B, H, d] -> [B, n_kv · d, H]``: head ``h``'s query in the rows
    of its own KV head, zeros in the others'."""
    b, h, d = q.shape
    qg = q.reshape(b, n_kv, h // n_kv, d)
    eye = jnp.eye(n_kv, dtype=q.dtype)
    return jnp.einsum("bkgd,kj->bkdjg", qg, eye).reshape(b, n_kv * d, h)


def _own_values(o, n_kv):
    """``o [..., H, n_kv · d] -> [..., H, d]``: head ``h``'s product with
    its own KV head's values."""
    h = o.shape[-2]
    d = o.shape[-1] // n_kv
    o = o.reshape(o.shape[:-2] + (n_kv, h // n_kv, n_kv, d))
    own = jnp.arange(n_kv)
    return jnp.moveaxis(o[..., own, :, own, :], 0, -3).reshape(
        o.shape[:-4] + (h, d))


def decode_blocks(pos, live, block):
    """``[B]``: the blocks of ``block`` columns :func:`page_decode_attention`
    visits for each row — those that hold a column ``<= pos`` of a LIVE
    row, none for the others. Times ``block`` it is the columns the step
    reads from a page."""
    return jnp.where(live, pos // block + 1, 0)


def decode_columns(pos, live, capacity, block=DECODE_BLOCK):
    """The columns :func:`page_decode_attention` reads from ONE page of
    ``capacity`` columns in a step (int32): its visits times their width."""
    block = min(block, capacity)
    return decode_blocks(pos, live, block).sum(dtype=jnp.int32) * block


def page_decode_attention(q, k_page, v_page, pos, live, scale,
                          block=DECODE_BLOCK):
    """One query a row over its K/V page, block by block. ``q [B, H, d]``;
    row ``b`` of ``k_page``/``v_page [B, T, n_kv · d]`` sees columns ``<=
    pos[b]`` (its own, already written, included). ONE loop over the (row,
    block) pairs of :func:`decode_blocks`; a row that is not live is not
    visited and gets zeros (nobody reads it). The pages ride the carry.
    Returns ``(o [B, H, d]`` float32, ``k_page, v_page)``."""
    b, h, d = q.shape
    t, w = k_page.shape[1:]
    n_kv = w // d
    block = min(block, t)
    q_diag = _diagonal_queries(q.astype(k_page.dtype), n_kv)
    n_of = decode_blocks(pos, live, block)
    ends = jnp.cumsum(n_of)
    exact = _exact(k_page)

    def body(i, carry):
        k_page, v_page, m, l, acc = carry
        row = jnp.minimum(jnp.searchsorted(ends, i, side="right"), b - 1)
        j = i - (ends[row] - n_of[row])
        s0 = jnp.minimum(j * block, t - block)
        cut = lambda page: jax.lax.dynamic_slice(
            page, (row, s0, 0), (1, block, w))[0]
        at = lambda a: jax.lax.dynamic_index_in_dim(a, row, 0, False)
        kb, vb = cut(k_page), cut(v_page)
        s = jnp.dot(kb, at(q_diag), precision=exact,
                    preferred_element_type=jnp.float32) * scale   # [blk, H]
        col = s0 + jnp.arange(block)
        seen = ((col <= at(pos)) & (col >= j * block))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        m_new = jnp.maximum(at(m), s.max(0))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(at(m) - m_new)
        pv = _own_values(jnp.dot(p.T.astype(vb.dtype), vb, precision=exact,
                                 preferred_element_type=jnp.float32), n_kv)
        put = lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, row, 0)
        return (k_page, v_page, put(m, m_new),
                put(l, alpha * at(l) + p.sum(0)),
                put(acc, alpha[:, None] * at(acc) + pv))

    # a row's block 0 holds column 0, which the row sees: ``m`` is finite
    # from its first visit on
    init = (k_page, v_page, jnp.full((b, h), -jnp.inf, jnp.float32),
            jnp.zeros((b, h), jnp.float32), jnp.zeros((b, h, d), jnp.float32))
    k_page, v_page, _, l, acc = jax.lax.fori_loop(0, ends[-1], body, init)
    return (jnp.where(l[..., None] > 0, acc / l[..., None], 0.0), k_page,
            v_page)


def ring_decode_attention(q, k_ring, v_ring, pos, scale):
    """One query a row over its whole ring. ``q [B, H, d]``; row ``b`` of
    ``k_ring``/``v_ring [B, W, n_kv · d]`` has position ``pos[b]`` written
    (:func:`write_ring`): every column that holds a position holds one
    inside the window, so the mask is "holds one" alone
    (:func:`ring_positions`), and the order of the columns does not matter
    to a softmax over keys that carry their own rotation. Returns ``o [B,
    H, d]`` float32."""
    n_kv = k_ring.shape[-1] // q.shape[-1]
    exact = _exact(k_ring)
    s = jnp.einsum("btc,bch->bth", k_ring,
                   _diagonal_queries(q.astype(k_ring.dtype), n_kv),
                   precision=exact,
                   preferred_element_type=jnp.float32) * scale
    held = ring_positions(pos, k_ring.shape[1]) >= 0                # [B, W]
    p = jax.nn.softmax(jnp.where(held[..., None], s, -jnp.inf), 1)
    return _own_values(jnp.einsum(
        "bth,btc->bhc", p.astype(v_ring.dtype), v_ring, precision=exact,
        preferred_element_type=jnp.float32), n_kv)
