"""Plain reference of the Xing4.0-29B-A4B language model's layers, and the map
from the program's parameter tree to the reference's names.

Written from the equations of ISSUE 31 and the public descriptions the
configuration's keys point at (``benchmark/configs/xing4.0-29b-a4b.json``
lists what was assumed); float32 throughout, matmuls under
``jax.default_matmul_precision("highest")`` (set by the caller around the
jitted call). No kernels, no cache, no absorbed form, no online softmax; it
imports nothing of the program.

The residual state is ``n = hc_mult`` streams a token, ``X [B, L, n, d]``:
after the embedding every stream is the embedding, before the final norm the
streams are summed. Around each sub-layer ``F`` (the mixer, then the
feed-forward, each with its own maps) — manifold-constrained
hyper-connections, arXiv:2512.24880:

    x̃ = RMSNorm(vec(X))                     (no learned scale)
    H̃_pre, H̃_post [n], H̃_res [n, n] = α · (x̃ Φ) + b     (one Φ, three α)
    H_pre = σ(H̃_pre),  H_post = 2σ(H̃_post)
    H_res = ``hc_sinkhorn_iters`` rounds of row-then-column normalisation of
            exp(clamp(H̃_res, ±hc_clamp)), ``hc_eps`` in each divisor
    X' = H_res X + H_postᵀ ⊗ F(RMSNorm_F(H_pre X))

* MLA mixer (DeepSeek-V2/V3), EXPANDED: ``q = W_qb RMSNorm(W_qa x)`` (128
  nope + 64 rope a head), ``[c; k_r] = W_kva x`` with c RMS-normed and k_r
  shared by the heads, ``[k_nope; v]_h = W_kvb c``; rotary on the 64 rope
  dims in adjacent pairs with YaRN's blended frequencies; causal
  ``softmax(scale · (q_nope·k_nope + q_rope·k_r))`` with ``scale = 192^-1/2 ·
  (0.1 ln factor + 1)²``; no output gate. The causal mask is full, computed
  for ``q_block`` queries at a time against every key (a 33k × 33k score
  array a head would not fit): blocks past ``n_real`` are skipped.
* Feed-forward: SwiGLU, dense, or routed: ``s = σ(W_r x̃)`` over all experts,
  the ``top_k`` best of ``s + b``, weights ``s`` over the chosen, normalised,
  times ``routed_scale``; every expert by a dense loop; plus the shared
  expert.

``quant`` is the hook the lower-precision control uses: it is applied to both
operands of every matrix multiplication EXCEPT the router's and the
hyper-connection maps', which the configuration states as float32 on both
sides of the comparison.
"""
import math

import jax
import jax.numpy as jnp


def identity(x):
    return x


def fake_fp8(x):
    """Round to 4 significant bits (e4m3's 1 + 3), exponent range left
    unbounded: it errs on the side of being MORE exact than real fp8."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


# -- hyper-connections ---------------------------------------------------------

def sinkhorn(logits, iters, eps):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hc_maps(x, p, cfg):
    """x [B, L, n, d] -> (H_pre [B, L, n], H_post [B, L, n], H_res
    [B, L, n, n])."""
    b, l, n, d = x.shape
    t = jnp.matmul(rms_norm(x.reshape(b, l, n * d), 1.0, cfg["norm_eps"]),
                   p["phi"])
    pre = jax.nn.sigmoid(p["alpha"][0] * t[..., :n] + p["b_pre"])
    post = 2.0 * jax.nn.sigmoid(p["alpha"][1] * t[..., n:2 * n] + p["b_post"])
    res = p["alpha"][2] * t[..., 2 * n:].reshape(b, l, n, n) + p["b_res"]
    res = sinkhorn(jnp.clip(res, -cfg["hc_clamp"], cfg["hc_clamp"]),
                   cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    return pre, post, res


def around(x, p, cfg, f):
    """One sub-layer ``f`` ([B, L, d] -> [B, L, d]) on the state."""
    pre, post, res = hc_maps(x, p, cfg)
    y = f(jnp.einsum("bln,blnd->bld", pre, x))
    return (jnp.einsum("blij,bljd->blid", res, x)
            + post[..., None] * y[:, :, None])


# -- MLA -----------------------------------------------------------------------

def yarn_inv_freq(d, theta, sc):
    """DeepSeek-V2's ``yarn_find_correction_range`` and linear ramp."""
    def correction_dim(rotations):
        return (d * math.log(sc["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(d // 2, dtype=jnp.float32)
    plain = 1.0 / theta ** (2.0 * i / d)
    mask = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return plain / sc["factor"] * (1.0 - mask) + plain * mask


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_pairs(x, cfg):
    """x [B, L, ..., d]: adjacent pairs as complex numbers, turned by
    position · frequency (plain ``theta^(-2i/d)``, or YaRN's)."""
    d, l = x.shape[-1], x.shape[1]
    sc = cfg.get("rope_scaling")
    if sc:
        freqs = yarn_inv_freq(d, cfg["rope_theta"], sc)
        amp = (yarn_mscale(sc["factor"], sc["mscale"])
               / yarn_mscale(sc["factor"], sc["mscale_all_dim"]))
    else:
        freqs = cfg["rope_theta"] ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        amp = 1.0
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freqs
    turn = amp * jnp.exp(1j * ang).reshape(
        (1, l) + (1,) * (x.ndim - 3) + (d // 2,))
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * turn
    return jnp.stack([z.real, z.imag], -1).reshape(x.shape)


def softmax_scale(cfg):
    scale = (cfg["d_nope"] + cfg["d_rope"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc["mscale_all_dim"]:
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def mla_mixer(y, p, cfg, mm, quant, n_real=None):
    b, l, _ = y.shape
    h, dn, dr, r = (cfg["n_heads"], cfg["d_nope"], cfg["d_rope"],
                    cfg["kv_rank"])
    dv = cfg["d_head"]
    q = mm(rms_norm(mm(y, p["wqa"]), p["q_norm"], cfg["norm_eps"]), p["wqb"])
    q = q.reshape(b, l, h, dn + dr)
    q_nope, q_rope = q[..., :dn], rotary_pairs(q[..., dn:], cfg)
    kva = mm(y, p["wkva"])
    c = rms_norm(kva[..., :r], p["c_norm"], cfg["norm_eps"])
    k_rope = rotary_pairs(kva[..., r:], cfg)
    kv = mm(c, p["wkvb"]).reshape(b, l, h, dn + dv)
    k_nope, v = quant(kv[..., :dn]), quant(kv[..., dn:])
    k_rope, scale = quant(k_rope), softmax_scale(cfg)
    qb = min(cfg.get("q_block", 256), l)
    if l % qb:
        raise ValueError(f"length {l} is no multiple of the query block {qb}")

    def rows(i, out):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, i * qb, qb, 1)
        s = (jnp.einsum("bqhe,bkhe->bhqk", quant(take(q_nope)), k_nope)
             + jnp.einsum("bqhe,bke->bhqk", quant(take(q_rope)), k_rope))
        seen = (jnp.arange(l)[None] <= i * qb + jnp.arange(qb)[:, None])
        s = jnp.where(seen, s * scale, -jnp.inf)
        o = jnp.einsum("bhqk,bkhe->bqhe", quant(jax.nn.softmax(s, -1)), v)
        return jax.lax.dynamic_update_slice_in_dim(out, o, i * qb, 1)

    n_blocks = l // qb if n_real is None else (n_real + qb - 1) // qb
    o = jax.lax.fori_loop(0, n_blocks, rows,
                          jnp.zeros((b, l, h, dv), jnp.float32))
    return mm(o.reshape(b, l, h * dv), p["wo"])


# -- routed feed-forward -------------------------------------------------------

def route(y, p, cfg):
    """y [T, d] -> (chosen expert ids [T, k], weights [T, k], biased scores
    [T, E]). Float32, never quantised. One group: no group limit."""
    scores = jax.nn.sigmoid(jnp.matmul(y, p["router"]))
    biased = scores + p["router_bias"]
    chosen = jnp.argsort(-biased, axis=-1, stable=True)[:, :cfg["top_k"]]
    w = jnp.take_along_axis(scores, chosen, 1)
    w = w / w.sum(-1, keepdims=True) * cfg["routed_scale"]
    return chosen, w, biased


def route_margin(y, p, cfg):
    """y [T, d] -> [T]: how far the last chosen expert's biased score lies
    above the best one not chosen."""
    ranked = -jnp.sort(-route(y, p, cfg)[2], axis=-1)
    k = cfg["top_k"]
    return ranked[:, k - 1] - ranked[:, k]


def balance_bias(y, p, cfg, steps=400, rate=0.02):
    """The choice bias that ``noaux_tc``'s own rule comes to rest at on the
    tokens ``y [T, d]``: from ``p["router_bias"]``, each step every expert
    chosen more often than the mean moves down and every one chosen less
    often up, by ``rate`` falling linearly to 0 (the scores lie in (0, 1)).
    A trained model of this kind arrives with such a bias; seeded routers
    without one send a tenth of the experts most of the pairs."""
    scores = jax.nn.sigmoid(jnp.matmul(y, p["router"]))
    e = scores.shape[-1]

    def one(bias, i):
        chosen = jnp.argsort(-(scores + bias), axis=-1,
                             stable=True)[:, :cfg["top_k"]]
        load = (chosen[..., None] == jnp.arange(e)).sum((0, 1))
        return bias + rate * (1.0 - i / steps) * jnp.sign(
            load.mean() - load), None

    return jax.lax.scan(one, p["router_bias"],
                        jnp.arange(steps, dtype=jnp.float32))[0]


def routed_ffn(y, p, cfg, mm):
    """The routed sum, expert by expert over every token."""
    chosen, w, _ = route(y, p, cfg)

    def one(acc, x):
        wg, wu, wd, e = x
        share = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)          # [T]
        up = lambda a: a.astype(jnp.float32)
        return acc + share[:, None] * swiglu(y, up(wg), up(wu), up(wd),
                                             mm), None

    n = p["w_gate"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        p["w_gate"], p["w_up"], p["w_down"], jnp.arange(n)))
    return out


# -- blocks and model ----------------------------------------------------------

def feed(u, p, kind, cfg, mm):
    y = rms_norm(u, p["norm_ffn"], cfg["norm_eps"])
    if kind[1] == "dense":
        return swiglu(y, p["ffn_gate"], p["ffn_up"], p["ffn_down"], mm)
    b, l, d = y.shape
    flat = y.reshape(b * l, d)
    out = routed_ffn(flat, p, cfg, mm) + swiglu(
        flat, p["shared_gate"], p["shared_up"], p["shared_down"], mm)
    return out.reshape(b, l, d)


def after_mixer(x, p, kind, cfg, quant=identity, n_real=None):
    mm = lambda a, w: jnp.matmul(quant(a), quant(w))
    return around(x, p["hc_mix"], cfg, lambda u: mla_mixer(
        rms_norm(u, p["norm_mix"], cfg["norm_eps"]), p, cfg, mm, quant,
        n_real))


def ffn_input(x, p, kind, cfg):
    """What the layer's feed-forward (and its router) is given."""
    x = after_mixer(x, p, kind, cfg)
    pre, _, _ = hc_maps(x, p["hc_ffn"], cfg)
    return rms_norm(jnp.einsum("bln,blnd->bld", pre, x), p["norm_ffn"],
                    cfg["norm_eps"])


def block(x, p, kind, cfg, quant=identity, n_real=None):
    """One layer on the state [B, L, n, d]; ``kind`` is its (mixer,
    feed-forward) pair, the mixer always ``"mla"``."""
    mm = lambda a, w: jnp.matmul(quant(a), quant(w))
    x = after_mixer(x, p, kind, cfg, quant, n_real)
    return around(x, p["hc_ffn"], cfg, lambda u: feed(u, p, kind, cfg, mm))


def embed(tokens, rest, cfg):
    e = rest["emb"][tokens]
    return jnp.broadcast_to(e[:, :, None],
                            e.shape[:2] + (cfg["hc_mult"], e.shape[-1]))


def head_logits(x, rest, cfg, quant=identity):
    """x: the state summed over its streams, [..., d]."""
    y = rms_norm(x, rest["norm_f"], cfg["norm_eps"])
    return jnp.matmul(quant(y), quant(rest["head"]))


def forward(tokens, layers, rest, cfg, quant=identity):
    """Whole model on ``tokens`` [B, L]: float32 logits [B, L, vocab].
    ``layers`` is a list of canonical layer dicts, one a pattern entry."""
    x = embed(tokens, rest, cfg)
    for p, kind in zip(layers, cfg["pattern"]):
        x = block(x, p, tuple(kind), cfg, quant)
    return head_logits(x.sum(2), rest, cfg, quant)


# -- from the program's tree to these names ------------------------------------

def canonical_layer(blk, upcast_experts=True):
    """One ``block_i`` subtree of the program -> the reference's layer dict,
    float32 (the stacked expert kernels may stay in their stored type:
    ``routed_ffn`` upcasts one expert at a time)."""
    f32 = lambda a: a.astype(jnp.float32)
    m = blk["mla"]
    out = {"norm_mix": f32(blk["norm_mix"]["scale"]),
           "norm_ffn": f32(blk["norm_ffn"]["scale"]),
           "wqa": f32(m["qa_proj"]["kernel"]), "wqb": f32(m["qb_proj"]["kernel"]),
           "q_norm": f32(m["q_norm"]["scale"]),
           "wkva": f32(m["kva_proj"]["kernel"]),
           "c_norm": f32(m["c_norm"]["scale"]), "wkvb": f32(m["kvb_proj"]),
           "wo": f32(m["o_proj"]["kernel"])}
    for name in ("hc_mix", "hc_ffn"):
        out[name] = {k: f32(v) for k, v in blk[name].items()}
    if "ffn" in blk:
        out.update({"ffn_" + k: f32(blk["ffn"][k]["kernel"])
                    for k in ("gate", "up", "down")})
    else:
        e = blk["moe"]
        keep = f32 if upcast_experts else (lambda a: a)
        out.update(router=f32(e["router"]), router_bias=f32(e["router_bias"]),
                   w_gate=keep(e["w_gate"]), w_up=keep(e["w_up"]),
                   w_down=keep(e["w_down"]))
        out.update({"shared_" + k: f32(blk["shared"][k]["kernel"])
                    for k in ("gate", "up", "down")})
    return out


def canonical_rest(tree):
    f32 = lambda a: a.astype(jnp.float32)
    return {"emb": f32(tree["tok_emb"]["embedding"]),
            "norm_f": f32(tree["norm_f"]["scale"]),
            "head": f32(tree["lm_head"]["kernel"])}
