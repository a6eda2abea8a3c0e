"""Operations of grouped-query attention over K/V leaves in prefill, from the
sizes in the configuration's ``as_run``: the scores and the values of every
(query, key) pair that is causal, filled and — on a window layer — inside the
window, 4 x d_head flop a pair and QUERY head (2 d_head for q.k, 2 d_head for
p.v). The work, whatever computes it: a program that scores pairs outside the
band or past a fill does more and reads lower; the writes of the page and the
ring are in the scopes' time and not in the flop, so no implementation reads
above its roofline by this count."""


def chunk_pairs(start, valid):
    """Query-key pairs of one chunk of ``valid`` queries at cursor ``start``
    on a FULL layer: each query sees the ``start`` columns before the chunk
    and the chunk's own columns up to itself."""
    return valid * start + valid * (valid + 1) // 2


def window_pairs(start, valid, window):
    """The same chunk on a WINDOW layer: query ``i`` (position ``start +
    i``) sees ``min(start + i + 1, window)`` keys."""
    # the queries still inside the first window see all that lies before them
    ramp = max(0, min(valid, window - 1 - start))
    return (ramp * start + ramp * (ramp + 1) // 2) + (valid - ramp) * window


def layer_heads(cfg):
    """(query heads over the full layers, over the window layers), summed
    over the layers of each kind."""
    full = sum(1 for m, _ in cfg["pattern"] if m == "gqa")
    ring = sum(1 for m, _ in cfg["pattern"] if m == "swa")
    return (full * (cfg["gqa_heads"] or cfg["n_heads"]),
            ring * (cfg["swa_heads"] or cfg["n_heads"]))


def attention_flops(chunks, cfg):
    """``chunks``: the (start, valid) of every chunk row dispatched."""
    full_heads, ring_heads = layer_heads(cfg)
    per_pair_and_head = 4 * cfg["d_head"]
    full = sum(chunk_pairs(s, v) for s, v in chunks)
    ring = sum(window_pairs(s, v, cfg["window"]) for s, v in chunks)
    return per_pair_and_head * (full * full_heads + ring * ring_heads)
