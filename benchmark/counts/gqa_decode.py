"""Bytes one decode step of a model of K/V pages, K/V rings and held experts
has to move, from the sizes in the configuration's ``as_run``: every weight
outside the experts once (attention, dense feed-forward, routers, shared
experts, norms, the head; the embedding is a gather of one row a slot), the
three kernels of each expert that at least one pair reaches, the FILLED
columns of the live rows' pages on the full layers — not the capacity — and
``min(fill, window)`` columns of their rings on the window layers, and the
row each live row writes on every layer. Decode is memory-bound: at 20 slots
its operations need a few per cent of the time its bytes need."""


def attention_params(cfg, heads):
    d, dh, kv = cfg["d_model"], cfg["d_head"], cfg["n_kv_heads"]
    gate = d * heads if cfg["attn_gate"] else 0
    return d * heads * dh + 2 * d * kv * dh + gate + heads * dh * d


def expert_params(cfg):
    """One expert's three kernels."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def layer_params(cfg, mixer, ffn, with_experts=True):
    d = cfg["d_model"]
    heads = cfg["swa_heads" if mixer == "swa" else "gqa_heads"] \
        or cfg["n_heads"]
    n = attention_params(cfg, heads) + 2 * d
    if ffn == "dense":
        return n + 3 * d * cfg["d_ff"]
    n += d * cfg["n_experts"] + cfg["n_experts"] + 3 * d * cfg["d_shared"]
    held = cfg["held_hi"] - cfg["held_lo"]
    return n + (held * expert_params(cfg) if with_experts else 0)


def model_params(cfg):
    """Everything the chip holds: the layers, the final norm, embedding and
    head."""
    d = cfg["d_model"]
    return (sum(layer_params(cfg, m, f) for m, f in cfg["pattern"])
            + d + 2 * d * cfg["vocab"])


def non_expert_weight_bytes(cfg, itemsize=2):
    d = cfg["d_model"]
    n = sum(layer_params(cfg, m, f, with_experts=False)
            for m, f in cfg["pattern"])
    return itemsize * (n + d + d * cfg["vocab"])


def column_bytes(cfg, itemsize=2):
    """Keys and values of one position on one layer."""
    return 2 * cfg["n_kv_heads"] * cfg["d_head"] * itemsize


def layers_by_kind(cfg):
    """(full layers over a page, window layers over a ring)."""
    mixers = [m for m, _ in cfg["pattern"]]
    return mixers.count("gqa"), mixers.count("swa")


def slot_bytes(cfg, capacity, itemsize=2):
    """One slot's pages and rings."""
    full, ring = layers_by_kind(cfg)
    return column_bytes(cfg, itemsize) * (full * capacity
                                          + ring * cfg["window"])


def decode_step_bytes(cfg, experts_touched, page_columns, ring_columns,
                      rows_live):
    """``experts_touched`` summed over the expert layers of one step;
    ``page_columns``: the live rows' filled columns (ONE full layer's);
    ``ring_columns``: their ``min(fill, window)`` (one window layer's);
    ``rows_live``: the rows that write a column."""
    full, ring = layers_by_kind(cfg)
    return (non_expert_weight_bytes(cfg)
            + experts_touched * 2 * expert_params(cfg)
            + column_bytes(cfg) * (full * page_columns + ring * ring_columns
                                   + (full + ring) * rows_live))
