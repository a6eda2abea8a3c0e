"""Bytes one decode step has to read: every weight of the blocks and the head
once (the batch shares them), and the keys and values cached so far. Decode is
memory-bound: its operations (2 flops a weight a slot) need a few per cent of
the time its bytes need on this chip."""


def weight_bytes(cfg, itemsize=2):
    d, ff, v = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    dkv = cfg["n_kv_heads"] * (d // cfg["n_heads"])
    per_layer = d * d + 2 * d * dkv + d * d + 2 * d * ff
    return itemsize * (cfg["n_layers"] * per_layer + d * v)


def cache_bytes_per_token(cfg, itemsize=2):
    dkv = cfg["n_kv_heads"] * (cfg["d_model"] // cfg["n_heads"])
    return itemsize * cfg["n_layers"] * 2 * dkv
