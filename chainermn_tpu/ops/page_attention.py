"""Pallas one-query attention over the dense model's K/V pages, the work
following each slot's fill.

The decode step of ``models/transformer.py::TransformerBlock`` attends ONE
query a slot over that slot's rows of the K and V pages, ``[slots, capacity,
h_kv, d_head]`` as ``ops/page_write.py`` writes them in place. The
``jax.numpy`` form (``models/transformer.py::_grouped_cache_attention``: two
einsums and a softmax over the whole page) reads the CAPACITY of every slot
whatever its fill — 4.03 GB a step in ``sc2-3b-serve-batchgen`` where 0.3 GB
are filled (PERF.md §6, PR 45). Here ONE kernel a layer walks the blocks of
columns that hold a position some slot sees, and no others:

* the pages stay in HBM and are read where they lie, viewed as ``[slots,
  capacity · h_kv, d_head]`` — the same bytes, since a column's ``h_kv``
  heads are consecutive rows of one memory tile (:func:`decode_refusal`
  admits the shapes for which that holds), so a block of columns is a plain
  ``[block · h_kv, d_head]`` matrix whose rows alternate KV heads;
* there is NO grid. A loop over the slots holds, for each, a loop over the
  blocks its cursor reaches (``ceil(min(row + 1, capacity) / block)``: at
  least one, so the item after (slot, last block) is (slot + 1, 0) and no
  list of items is needed). An item's K and V blocks arrive by DMA into one
  of ``BUFFERS`` buffers, started ``BUFFERS - 1`` items before the item is
  computed, across slot boundaries too: a block nobody sees costs nothing —
  no copy, no grid step (a grid of (slot, block) pays a third of a
  microsecond for every step it skips: 512 steps a call here for about 90
  with work);
* all query heads score all rows of a block, ``[heads, block · h_kv]`` on
  the matrix unit; a row of another KV head than the query head's own is
  masked with the columns past the cursor, so p·V over the block is exact —
  ``h_kv`` times the flops of a step that is bound by its bytes, and no
  widened, split or re-laid K or V (the block-diagonal trick of
  ``ops/kv_attention.py::page_decode_attention``, turned to the page's
  side);
* a slot's running softmax (``m``, ``l``, the ``[heads, d_head]``
  accumulator) rides the block loop's carry; scores never leave VMEM.

The arithmetic is ``_grouped_cache_attention``'s: operands in the page's
dtype, float32 accumulation, float32 scores, mask and ``exp``, probabilities
rounded to the page's dtype before p·V, float32 pages keep float32 products.
The one-shot softmax becomes an online one over blocks, so results agree
within rounding, not bitwise.

The ring: column ``j`` of a slot holds position ``row − ((row − j) mod
capacity)``, so a slot at ``row < capacity`` sees columns ``≤ row`` and one
whose cursor has passed the capacity sees every column: ``min(row + 1,
capacity)`` columns from 0, whatever the order of the positions in them (a
softmax over keys that carry their own rotation does not ask).

:func:`decode_refusal` is the dispatcher's rule: which calls the kernel can
serve, by what the call shows at trace time.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.kv_attention import _exact
from chainermn_tpu.ops.latent_attention import (_NEG, LANES,
                                                _placement_refusal)
from chainermn_tpu.ops.page_write import rows_are_whole_tiles
from chainermn_tpu.utils import on_tpu

__all__ = ["page_decode_fwd", "decode_refusal", "block_columns",
           "BLOCK_ROWS", "BUFFERS"]

#: rows of the flattened page (columns x h_kv) one item reads of K and of V.
#: An item pays a fixed cost whatever its width (its chain of scores,
#: softmax and p.V, each waiting for the matrix unit's result: 0.45 us for
#: 512 rows) and a slot reads its last block whole whatever its fill. On a
#: v5e at the shape served (PERF.md §6, PR 45: 64 slots of 2,048 columns, 24
#: heads over 2 of 128, bfloat16, 34 live slots at the cell's fills and 30
#: parked, 4 buffers, ms a call in a chain of 30): 128 rows 0.098 (8 buffers), 256
#: 0.074, 512 0.060, 1,024 0.072; every slot full: 256 0.412, 512 0.234,
#: 1,024 0.205 (654 GB/s); float32 pages: 256 0.090, 512 0.089, 1,024 0.122
BLOCK_ROWS = 512
#: K blocks (and V blocks) in VMEM at a time: one under the arithmetic and
#: ``BUFFERS - 1`` on their way. With ONE copy ahead an item waited out what
#: was left of its copy's latency after the item before it was done: 0.62 us
#: an item of 512 rows on full slots, 0.46 with three ahead (its own chain).
#: The same readings at 512 rows, the cell's fills: 2 buffers 0.073, 3 0.062,
#: 4 0.060, 8 0.061; in the cell 1,501-1,505 tokens/s with 2 and 1,520-1,529
#: with 4
BUFFERS = 4
# q, the own-head bias and the float32 output whole, the K and V blocks: 3 MB
# at the shape served. What is reserved beyond that is not idle: XLA plans
# its weight prefetch around it, and the cell's decode step read 10.75 ms
# with 4 MB here, 10.74 with 16, 10.22 with 32, 10.21 with 64 and 11.14
# with 96 (PERF.md §7 "From PR 45" (a))
_VMEM_LIMIT = 32 * 1024 * 1024


def block_columns(capacity: int, h_kv: int,
                  block_rows: int = BLOCK_ROWS) -> int:
    """Columns of one item: ``block_rows`` flattened rows, and no more than
    the page has."""
    return min(max(block_rows // h_kv, 1), capacity)


def decode_refusal(q, k_page, window) -> Optional[str]:
    """Why :func:`page_decode_fwd` cannot serve this call, or ``None`` if it
    can: a cache row ``[h_kv, d_head]`` is whole memory tiles one lane tile
    wide (the flattened view is then the same bytes and a block's DMA whole
    tiles), the capacity is whole blocks, no window, pages in bfloat16 or
    float32 that one device holds, on a TPU."""
    cap, h_kv, d = k_page.shape[1:]
    if window is not None:
        # no cell serves a windowed dense model: the kernel has no band
        return f"attention_window {window}"
    if k_page.dtype not in (jnp.bfloat16, jnp.float32):
        return f"pages {k_page.dtype}: not one of bfloat16, float32"
    if d != LANES or not rows_are_whole_tiles(h_kv, d, k_page.dtype):
        return (f"a cache row [{h_kv}, {d}] of {k_page.dtype} is not whole "
                f"tiles of {LANES} lanes")
    bk = block_columns(cap, h_kv)
    if cap % bk or (bk * h_kv) % 16:
        return f"capacity {cap} is not whole blocks of {bk} columns"
    return _placement_refusal()


def _kernel(row_ref, q_ref, own_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            *, scale, bk, h_kv, cap, exact):
    n, h, d = q_ref.shape
    depth = kbuf.shape[0]
    rows = bk * h_kv
    nt = (((1,), (1,)), ((), ()))               # q @ k^T

    def seen(s):
        # columns slot ``s`` sees, from column 0: all once it has wrapped
        return jnp.minimum(row_ref[s] + 1, cap)

    def blocks(s):
        return (seen(s) + bk - 1) // bk

    def first_column(j):
        # the page's last block ends with the page
        return jnp.minimum(j * bk, cap - bk)

    def copies(s, j, buf):
        r0 = first_column(j) * h_kv
        if cap % bk == 0:
            r0 = pl.multiple_of(r0, rows)
        at = pl.ds(r0, rows)
        return (pltpu.make_async_copy(k_hbm.at[s, at], kbuf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[s, at], vbuf.at[buf],
                                      sem.at[1, buf]))

    def fetch(ahead, item):
        """Start the copies of the item ``ahead = (slot, block)`` points at,
        if there is one, into the buffer item number ``item`` takes; return
        the item after it: the slot's next block, or the next slot's first
        (every slot has one)."""
        s, j = ahead

        @pl.when(s < n)
        def _start():
            for dma in copies(s, j, item % depth):
                dma.start()

        last = j + 1 >= blocks(jnp.minimum(s, n - 1))
        return jnp.where(last, s + 1, s), jnp.where(last, 0, j + 1)

    def fold(s, j, buf, carry):
        m, l, acc = carry
        c0 = first_column(j)
        # flattened rows of the block that hold a column the slot sees (and,
        # in a page's last block, that no earlier block held)
        lo, hi = (j * bk - c0) * h_kv, (seen(s) - c0) * h_kv

        def held(shape, axis):
            f = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            return (f >= lo) & (f < hi)

        sc = jax.lax.dot_general(
            q_ref[s], kbuf[buf], nt, precision=exact,
            preferred_element_type=jnp.float32) * scale + own_ref[...]
        sc = jnp.where(held((1, rows), 1), sc, _NEG)
        # what lies past the cursor need hold no number, and a probability
        # of zero would not make it one
        v = vbuf[buf]
        v = jnp.where(held((rows, 1), 0), v, jnp.zeros_like(v))
        # every block holds a column the slot sees, and every query head a
        # KV head in it: ``m`` is finite from the slot's first block on
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v, precision=exact,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    def slot(s, carry):
        def block(j, carry):
            item, ahead, softmax = carry
            # the item ``depth - 1`` ahead into the buffer the item before
            # this one has left, under this one's arithmetic
            ahead = fetch(ahead, item + depth - 1)
            for dma in copies(s, j, item % depth):
                dma.wait()
            return item + 1, ahead, fold(s, j, item % depth, softmax)

        item, ahead, (_, l, acc) = jax.lax.fori_loop(
            0, blocks(s), block, (*carry, (
                jnp.full((h, 1), _NEG, jnp.float32),
                jnp.zeros((h, 1), jnp.float32),
                jnp.zeros((h, d), jnp.float32))))
        o_ref[s] = acc / l
        return item, ahead

    ahead = (jnp.int32(0), jnp.int32(0))
    for item in range(depth - 1):
        ahead = fetch(ahead, item)
    jax.lax.fori_loop(0, n, slot, (0, ahead))


def page_decode_fwd(q, k_page, v_page, row, *,
                    block_rows: int = BLOCK_ROWS, buffers: int = BUFFERS):
    """``q [B, H, d]``: slot ``b``'s query at position ``row[b]`` (``row``
    int ``[B]`` or ``()``), already written into ``k_page``/``v_page [B,
    capacity, h_kv, d]`` (ring pages: column ``j`` holds the newest position
    ``≡ j mod capacity``), ``H`` a multiple of ``h_kv``, query head ``i``
    over KV head ``i // (H / h_kv)``. Scaled by ``d ** -0.5``. Returns ``[B,
    H, d]`` float32. The pages are read only, ``block_rows / h_kv`` columns
    an item with ``buffers - 1`` items' copies in flight, and of a slot only
    the blocks that hold a column it sees."""
    row = jnp.broadcast_to(jnp.asarray(row, jnp.int32), q.shape[:1])
    return _decode(q, k_page, v_page, row, block_rows=block_rows,
                   buffers=buffers, interpret=not on_tpu())


# jitted for its cache, not for speed: a model's layers call it at one
# shape, and the kernel is traced and lowered once a program, not once a
# layer (PR 40: 10 s of a decode program's set-up otherwise)
@functools.partial(jax.jit,
                   static_argnames=("block_rows", "buffers", "interpret"))
def _decode(q, k_page, v_page, row, *, block_rows, buffers, interpret):
    b, h, d = q.shape
    cap, h_kv = k_page.shape[1:3]
    bk = block_columns(cap, h_kv, block_rows)
    rows = bk * h_kv
    # a query head's own KV head among a block's rows: 0 there, else the
    # mask (the rows of a block alternate KV heads, column by column); made
    # here, so a constant of the program and no work of a step
    own = np.where(
        (np.arange(rows) % h_kv)[None] == (np.arange(h) // (h // h_kv)
                                           )[:, None], 0.0, _NEG)
    flat = lambda page: page.reshape(b, cap * h_kv, d)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=d ** -0.5, bk=bk, h_kv=h_kv, cap=cap,
            exact=_exact(k_page)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[vmem, vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[pltpu.VMEM((buffers, rows, d), k_page.dtype),
                            pltpu.VMEM((buffers, rows, d), v_page.dtype),
                            pltpu.SemaphoreType.DMA((2, buffers))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="page_decode_fwd",
    )(row, q.astype(k_page.dtype), jnp.asarray(own, jnp.float32),
      flat(k_page), flat(v_page))
