"""Operations of latent attention over a page in prefill, from the sizes in
the configuration's ``as_run``: the scores and the values of every
query-column pair a causal chunk computes, in the EXPANDED form (a 128 + 64
key and a 128 value a head: 640 flop a pair and head). That is the lesser of
the two forms (the absorbed one takes 2,176), and the re-expansion of the
page's latents through ``W_kvb`` is left out, so no implementation reads
above its roofline by this count."""


def mla_layers(cfg):
    return sum(1 for m, _ in cfg["pattern"] if m == "mla")


def flops_per_pair_and_head(cfg):
    return 2 * (cfg["d_nope"] + cfg["d_rope"] + cfg["d_head"])


def chunk_pairs(start, valid):
    """Query-column pairs of one chunk of ``valid`` queries at cursor
    ``start``: each query sees the ``start`` columns before the chunk and
    the chunk's own columns up to itself."""
    return valid * start + valid * (valid + 1) // 2


def attention_flops(pairs, cfg):
    """``pairs`` summed over the chunks (the engine's ``attended_pairs``),
    over every head and latent layer."""
    return (pairs * cfg["n_heads"] * flops_per_pair_and_head(cfg)
            * mla_layers(cfg))


def matrix_flops_per_token(cfg):
    """What a prompt token costs outside attention: 2 flop a weight it
    meets (the latent projections, the maps' matrix, the dense or the
    routed and shared feed-forwards at ``top_k`` experts, the router)."""
    d, h = cfg["d_model"], cfg["n_heads"]
    dn, dr, dv, r, q = (cfg["d_nope"], cfg["d_rope"], cfg["d_head"],
                        cfg["kv_rank"], cfg["q_rank"])
    n = cfg.get("hc_mult", 1)
    mla = d * q + q * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) \
        + h * dv * d
    maps = 2 * n * d * (2 * n + n * n) if n > 1 else 0
    total = 0
    for _, ffn in cfg["pattern"]:
        total += mla + maps
        if ffn == "dense":
            total += 3 * d * cfg["d_ff"]
        else:
            total += (d * cfg["n_experts"] + 3 * d * cfg["d_shared"]
                      + cfg["top_k"] * 3 * d * cfg["d_expert"])
    return 2 * total
