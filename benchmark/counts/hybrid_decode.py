"""Bytes one decode step of the hybrid model has to move, from the sizes in
the configuration's ``as_run``: every weight outside the experts once (the
batch shares them), the three kernels of each held expert that at least one
pair reaches, each live slot's recurrent state and convolution tail read and
written, and the latent page filled so far. Decode is memory-bound: at 128
slots its operations need a few per cent of the time its bytes need.

Also the grouped expert product's own operations and bytes (one call of
``ops/grouped_swiglu.py``), for its share of the roofline."""


def _kinds(cfg):
    mixers = [m for m, _ in cfg["pattern"]]
    ffns = [f for _, f in cfg["pattern"]]
    return (mixers.count("kda"), mixers.count("mla"), ffns.count("dense"),
            ffns.count("moe"))


def kda_mixer_params(cfg):
    d, h, e = cfg["d_model"], cfg["n_heads"], cfg["d_head"]
    inner = h * e
    # q, k, v, decay, output gate, output; beta; convolutions; biases, norm
    return (6 * d * inner + d * h + 3 * cfg["conv_kernel"] * inner
            + inner + h + e)


def mla_mixer_params(cfg):
    d, h = cfg["d_model"], cfg["n_heads"]
    dn, dr, dv, r = cfg["d_nope"], cfg["d_rope"], cfg["d_head"], cfg["kv_rank"]
    return (d * h * (dn + dr) + d * (r + dr) + r + r * h * (dn + dv)
            + d * h + h * dv * d)


def expert_params(cfg):
    """One expert's three kernels."""
    return 3 * cfg["d_model"] * cfg["d_expert"]


def non_expert_weight_bytes(cfg, itemsize=2):
    """What every step reads whatever the routing: mixers, norms, routers,
    shared experts, dense feed-forwards and the output head (the embedding
    is a gather of one row a slot)."""
    d = cfg["d_model"]
    n_kda, n_mla, n_dense, n_moe = _kinds(cfg)
    n = (n_kda * kda_mixer_params(cfg) + n_mla * mla_mixer_params(cfg)
         + n_dense * 3 * d * cfg["d_ff"]
         + n_moe * (d * cfg["n_experts"] + cfg["n_experts"]
                    + 3 * d * cfg["d_shared"])
         + 2 * d * len(cfg["pattern"]) + d + d * cfg["vocab"])
    return itemsize * n


def expert_bytes(cfg, itemsize=2):
    return itemsize * expert_params(cfg)


def state_bytes_per_slot(cfg, page_itemsize=2):
    """Recurrent state (f32) and convolution tail of the KDA layers."""
    n_kda = _kinds(cfg)[0]
    h, e = cfg["n_heads"], cfg["d_head"]
    tail = (cfg["conv_kernel"] - 1) * 3 * h * e * page_itemsize
    return n_kda * (h * e * e * 4 + tail)


def latent_bytes_per_token(cfg, page_itemsize=2):
    return _kinds(cfg)[1] * (cfg["kv_rank"] + cfg["d_rope"]) * page_itemsize


def slot_bytes(cfg, capacity, page_itemsize=2):
    """Everything one slot holds: state, tail, a whole latent page and the
    cursor — what ``ServingStep.slot_bytes`` counts."""
    return (state_bytes_per_slot(cfg, page_itemsize)
            + capacity * latent_bytes_per_token(cfg, page_itemsize) + 4)


def decode_step_bytes(cfg, experts_touched, live_slots, cached_tokens):
    """``experts_touched`` summed over the expert layers of one step."""
    return (non_expert_weight_bytes(cfg)
            + experts_touched * expert_bytes(cfg)
            + 2 * live_slots * state_bytes_per_slot(cfg)
            + cached_tokens * latent_bytes_per_token(cfg))


def grouped_swiglu(rows, experts_touched, cfg, itemsize=2):
    """(flops, bytes) of one call: ``rows`` real rows through
    ``experts_touched`` experts."""
    d, f = cfg["d_model"], cfg["d_expert"]
    flops = 2 * rows * 3 * d * f
    bytes_ = itemsize * (experts_touched * 3 * d * f + 2 * rows * d)
    return flops, bytes_


def least_seconds(flops, bytes_, peaks):
    """(seconds, which bound applies)."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
