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
  cursors against the FILLED columns of a page plus the chunk itself: one
  loop over blocks of columns up to the last column a query sees, no score
  array over the page, no float32 copy or head-repeat of the page;
* :func:`ring_chunk_attention` — the same chunk on a window layer: the
  ring's last ``W`` positions laid out in position order before the chunk's
  own keys, query tiles of ``W`` against the ``2W`` keys that can lie inside
  their band (a 2,048-token chunk is four windows: the band is skipped
  INSIDE the chunk too), then the chunk's last ``min(valid, W)`` rows
  written at ``position mod W``;
* :func:`page_decode_attention` — one query a row: ONE loop over the (row,
  block) pairs that hold a column a LIVE row has filled, so a step reads
  what is cached row by row, not the capacity;
* :func:`ring_decode_attention` — one query a row over the whole ring
  (``≤ W`` columns), every row at once.

Grouping without a widened K or V: the chunk paths fold the query heads to
``[n_kv, heads / n_kv]`` and contract against the ``n_kv`` heads the block
has. The one-query paths lay the row's queries out BLOCK-DIAGONALLY,
``[n_kv · d_head, heads]`` with head ``h``'s query in the rows of its KV head
and zeros elsewhere: scores are then ``block @ Q`` and values ``pᵀ @ block``,
the block the left operand of the one and the right of the other as it lies
(a product batched over the KV heads makes the compiler re-lay the block),
at ``n_kv`` times the flops of a step that is bound by its bytes.

Plain ``jax.numpy``: a Pallas kernel that follows each row's fill is the
later step (what ``ops/latent_attention.py`` is to the latent page).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["CHUNK_BLOCK", "DECODE_BLOCK", "column_blocks", "decode_blocks", "decode_columns",
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


def page_chunk_attention(q, k_page, v_page, pos, slots, scale):
    """Causal attention of a chunk of queries over a K/V page, block by
    block. ``q [B, C, H, d]``: the queries at positions ``pos[b] + 0..C-1``;
    row ``slots[b]`` of ``k_page``/``v_page [N, T, n_kv · d]`` holds every
    column up to those positions, the chunk's own included (the caller wrote
    them). Blocks past the last column a query sees are not read; the pages
    ride the loop's carry and the caller keeps what comes out, so that a
    page just written is read where it lies. Returns ``(o [B, C, H, d]``
    float32, ``k_page, v_page)``."""
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


def ring_chunk_attention(q, k, v, k_ring, v_ring, pos, slots, scale):
    """Causal attention of a chunk of queries inside a window of ``W``
    positions, ``W`` the ring's length. ``q [B, C, H, d]`` at positions
    ``pos[b] + 0..C-1``; ``k``, ``v [B, C, n_kv · d]`` the chunk's own;
    row ``slots[b]`` (``b`` itself without ``slots``) of ``k_ring``/``v_ring
    [N, W, n_kv · d]`` holds the positions before ``pos[b]`` (the chunk not
    yet written: it would overwrite what its first queries see). Query ``i``
    sees key position ``p`` where ``pos + i - W < p <= pos + i``: the
    ring's positions are laid in order before the chunk's, and query tile
    ``t`` (``W`` queries) meets keys ``[tW, (t + 2)W)`` of that row alone.
    Returns ``o [B, C, H, d]`` float32."""
    b, c, h, d = q.shape
    window, w = k_ring.shape[1:]
    n_kv = w // d
    n_tiles = -(-c // window)
    pad = n_tiles * window - c
    exact = _exact(k_ring)

    def keys(ring, chunk):
        """``[B, (n_tiles + 1) W, n_kv, d]``: positions ``pos - W ..``."""
        at = (pos[:, None] + jnp.arange(window)[None]) % window
        prev = jnp.take_along_axis(_call_rows(ring, slots), at[..., None],
                                   axis=1)
        chunk = jnp.pad(chunk.astype(ring.dtype), ((0, 0), (0, pad), (0, 0)))
        return jnp.concatenate([prev, chunk], 1).reshape(
            b, (n_tiles + 1) * window, n_kv, d)

    kk, vv = keys(k_ring, k), keys(v_ring, v)
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
