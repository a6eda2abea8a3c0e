"""What ONE self-drafted round of the latent-attention model with held experts
and a multi-token-prediction module has to do, from the sizes in the
configuration's ``as_run`` — the same work whatever implements it:

* bytes: every weight outside the experts once — the main layers', the
  module's, the head (the embedding is a gather of a row a position) —, the
  three kernels of each held expert that at least one pair reaches (main
  layers and module apart), and the FILLED columns of the live slots' latent
  pages, read once a latent layer (the module's page too) at the width they
  are stored in (576 values padded to whole lane tiles);
* operations: the absorbed attention of the positions run against those
  columns — ``2 (r + d_rope) + 2 r`` a column, query and head: 2,176 —, two
  positions a live slot in the main layers and the accepted ones in the
  module; and the matrices of those positions: the weights outside the
  experts, 8 pairs a position of which the held share is computed, the head
  for every main position and for one module position a slot.

A round's least time is the greater of bytes over the chip's bandwidth and
operations over its bf16 peak: at 128 slots the weights are bandwidth's
(10.98 GB a round) and two queries against one read of a page row are
compute's (435 flop/B against the chip's 240)."""
LANES = 128


def mla_params(cfg):
    d, h = cfg["d_model"], cfg["n_heads"]
    dn, dr, dv, r, q = (cfg["d_nope"], cfg["d_rope"], cfg["d_head"],
                        cfg["kv_rank"], cfg["q_rank"])
    return (d * q + q + q * h * (dn + dr) + d * (r + dr) + r
            + r * h * (dn + dv) + h * dv * d)


def expert_params(cfg):
    """One expert's three kernels."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def outside_experts(cfg, ffn):
    """One layer's parameters outside its routed experts: the mixer, the two
    norms, and the dense feed-forward or the router, its bias and the shared
    expert."""
    d = cfg["d_model"]
    return mla_params(cfg) + 2 * d + (
        3 * d * cfg["d_ff"] if ffn == "dense" else
        d * cfg["n_experts"] + cfg["n_experts"] + 3 * d * cfg["d_shared"])


def module_outside_experts(cfg):
    """The module without its routed experts, embedding and head: one expert
    block, the ``2d -> d`` projection, three norms."""
    d = cfg["d_model"]
    return outside_experts(cfg, "moe") + 2 * d * d + 3 * d


def layers(cfg):
    """(dense layers, expert layers, modules)."""
    ffns = [f for _, f in cfg["pattern"]]
    return ffns.count("dense"), ffns.count("moe"), cfg.get("n_mtp", 0)


def held(cfg):
    return cfg["held_hi"] - cfg["held_lo"]


def head_params(cfg):
    return cfg["d_model"] * cfg["vocab"] + cfg["d_model"]


def non_expert_params(cfg):
    """What every round reads whatever the routing (the embedding is not
    read: a row a position)."""
    return (sum(outside_experts(cfg, f) for _, f in cfg["pattern"])
            + cfg.get("n_mtp", 0) * module_outside_experts(cfg)
            + head_params(cfg))


def all_params(cfg):
    """Everything the chip holds."""
    _, moe, mtp = layers(cfg)
    return (non_expert_params(cfg) + cfg["vocab"] * cfg["d_model"]
            + (moe + mtp) * held(cfg) * expert_params(cfg))


def page_width(cfg):
    """Stored values a token and latent layer: ``r + d_rope`` in whole lane
    tiles where the page is read in blocks."""
    w = cfg["kv_rank"] + cfg["d_rope"]
    return -(-w // LANES) * LANES if cfg.get("mla_block") else w


def latent_layers(cfg):
    return sum(1 for m, _ in cfg["pattern"] if m == "mla") + cfg.get(
        "n_mtp", 0)


def page_bytes_per_column(cfg, itemsize=2):
    return latent_layers(cfg) * page_width(cfg) * itemsize


def round_bytes(cfg, experts_touched, filled_columns, itemsize=2):
    """``experts_touched``: held experts with at least one pair, summed over
    the expert layers of the round, the module's among them;
    ``filled_columns``: summed over the live slots."""
    return (itemsize * (non_expert_params(cfg)
                        + experts_touched * expert_params(cfg))
            + filled_columns * page_bytes_per_column(cfg, itemsize))


def attention_flops_per_column(cfg):
    """One query, one head, one column, absorbed: the score over ``r +
    d_rope`` values and the value sum over ``r``."""
    r, dr = cfg["kv_rank"], cfg["d_rope"]
    return 2 * (r + dr) + 2 * r


def round_flops(cfg, live, emitted, filled_columns, pairs_held):
    """``live`` slots run two main positions each and ``emitted`` module
    positions in all; ``filled_columns`` as above; ``pairs_held``: (position,
    expert) pairs computed by a held expert, main layers and module
    together."""
    main_layers = sum(1 for m, _ in cfg["pattern"] if m == "mla")
    mean_fill = filled_columns / max(live, 1)
    attn = attention_flops_per_column(cfg) * cfg["n_heads"] * mean_fill * (
        2 * live * main_layers + emitted * cfg.get("n_mtp", 0))
    main = sum(outside_experts(cfg, f) for _, f in cfg["pattern"])
    matrices = 2 * (2 * live * (main + head_params(cfg))
                    + cfg.get("n_mtp", 0) * (
                        emitted * module_outside_experts(cfg)
                        + live * head_params(cfg))
                    + pairs_held * expert_params(cfg))
    return attn + matrices


def least_seconds(flops, bytes_, peaks):
    """(seconds, which bound applies)."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
