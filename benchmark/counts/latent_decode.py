"""Bytes one decode step of a latent-attention model with held experts has
to move, from the sizes in the configuration's ``as_run``: every weight
outside the experts once (the batch shares them; the embedding is a gather
of one row a slot), the three kernels of each expert that at least one pair
reaches, and the FILLED columns of the live slots' latent pages, 576 values
a column and latent layer — not the pages' capacity. Decode is memory-bound:
at 12 slots its operations need a few per cent of the time its bytes need."""


def mla_mixer_params(cfg):
    d, h = cfg["d_model"], cfg["n_heads"]
    dn, dr, dv, r, q = (cfg["d_nope"], cfg["d_rope"], cfg["d_head"],
                        cfg["kv_rank"], cfg["q_rank"])
    return (d * q + q + q * h * (dn + dr) + d * (r + dr) + r
            + r * h * (dn + dv) + h * dv * d)


def map_params(cfg):
    """The hyper-connection maps of one sub-layer: Phi, three alphas, the
    biases."""
    n = cfg.get("hc_mult", 1)
    if n == 1:
        return 0
    return n * cfg["d_model"] * (2 * n + n * n) + 3 + 2 * n + n * n


def expert_params(cfg):
    """One expert's three kernels."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def non_expert_weight_bytes(cfg, itemsize=2):
    d = cfg["d_model"]
    n = 0
    for _, ffn in cfg["pattern"]:
        n += mla_mixer_params(cfg) + 2 * map_params(cfg) + 2 * d
        n += (3 * d * cfg["d_ff"] if ffn == "dense" else
              d * cfg["n_experts"] + cfg["n_experts"]
              + 3 * d * cfg["d_shared"])
    return itemsize * (n + d + d * cfg["vocab"])


def all_weight_bytes(cfg, itemsize=2):
    """Everything the chip holds: the above, every held expert, the
    embedding."""
    moe = sum(1 for _, f in cfg["pattern"] if f == "moe")
    held = cfg["held_hi"] - cfg["held_lo"]
    return (non_expert_weight_bytes(cfg, itemsize) + itemsize * (
        moe * held * expert_params(cfg) + cfg["vocab"] * cfg["d_model"]))


def latent_bytes_per_column(cfg, page_itemsize=2):
    layers = sum(1 for m, _ in cfg["pattern"] if m == "mla")
    return layers * (cfg["kv_rank"] + cfg["d_rope"]) * page_itemsize


def decode_step_bytes(cfg, experts_touched, filled_columns):
    """``experts_touched`` summed over the expert layers of one step,
    ``filled_columns`` over the live slots."""
    return (non_expert_weight_bytes(cfg)
            + experts_touched * 2 * expert_params(cfg)
            + filled_columns * latent_bytes_per_column(cfg))
