"""Plain reference of DeepSeek-V3's language model layers WITH its
multi-token-prediction module, and the map from the program's parameter tree
to the reference's names.

Written from the equations of ISSUE 33, the DeepSeek-V3 report (§2.1 latent
attention and routing, §2.2 multi-token prediction) and the public
``config.json`` the configuration's keys point at
(``benchmark/configs/deepseek-v3.json`` lists what was assumed); float32
throughout, matmuls under ``jax.default_matmul_precision("highest")`` (set by
the caller around the jitted call). No kernels, no cache, no absorbed form, no
online softmax, no drafting and no acceptance: it returns the main logits and
the module's logits of every position, teacher-forced. It imports nothing of
the program.

* Residual path: the plain sum, pre-norm RMSNorm blocks.
* MLA mixer, EXPANDED: ``q = W_qb RMSNorm(W_qa x)`` (128 nope + 64 rope a
  head), ``[c; k_r] = W_kva x`` with c RMS-normed and k_r shared by the
  heads, ``[k_nope; v]_h = W_kvb c``; rotary on the 64 rope dims in adjacent
  pairs with YaRN's blended frequencies; causal ``softmax(scale ·
  (q_nope·k_nope + q_rope·k_r))`` with ``scale = 192^-1/2 · (0.1 ln factor +
  1)²``; no output gate. The causal mask is full, computed for ``q_block``
  queries at a time against every key.
* Feed-forward: SwiGLU, dense, or routed by the SHARE's rule: ``s = σ(W_r
  x̃)`` over all ``n_routed`` experts in float32, the choice on ``s + b``
  limited to the ``topk_group`` best of ``n_group`` groups (a group's score
  the sum of its two best), the ``top_k`` best among them, weights ``s`` over
  the chosen, normalised, times ``routed_scale``; of the chosen pairs only
  those to the experts held here (``held_lo`` on, as many as the stacked
  kernels hold) are computed, expert by expert in a loop — what the absent
  experts would add is left out, here and in the program alike; plus the
  shared expert on every token.
* The MTP module, for the main hidden state ``h_i`` (the last held layer's
  output BEFORE the final norm) and the token ``t_{i+1}`` that follows:
  ``u_i = W_eh [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]``; ``h'_i`` = one
  block of the expert kind on ``u`` (attention at rotary position ``i``
  over ``u_0 .. u_i``, its own router, bias and share, the shared expert);
  draft logits ``Head(RMSNorm_out(h'_i))``, of ``t_{i+2}``, with the main
  model's ``Emb`` and ``Head``.

``quant`` is the hook the lower-precision control uses: it is applied to both
operands of every matrix multiplication EXCEPT the router's, which the
configuration states as float32 on both sides of the comparison.
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


# -- MLA -----------------------------------------------------------------------

def yarn_inv_freq(d, theta, sc):
    """DeepSeek-V2/V3's ``yarn_find_correction_range`` and linear ramp."""
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


def mla_mixer(y, p, cfg, mm, quant):
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

    o = jax.lax.fori_loop(0, l // qb, rows,
                          jnp.zeros((b, l, h, dv), jnp.float32))
    return mm(o.reshape(b, l, h * dv), p["wo"])


# -- routed feed-forward -------------------------------------------------------

def allowed_scores(y, p, cfg):
    """y [T, d] -> (scores [T, E], the biased scores with every expert of a
    group that is not kept at -inf). Float32, never quantised."""
    scores = jax.nn.sigmoid(jnp.matmul(y, p["router"]))
    t, e = scores.shape
    ng = cfg["n_group"]
    biased = (scores + p["router_bias"]).reshape(t, ng, e // ng)
    best_two = -jnp.sort(-biased, axis=-1)[..., :2]
    group_rank = jnp.argsort(-best_two.sum(-1), axis=-1, stable=True)
    keep = jnp.zeros((t, ng), bool).at[
        jnp.arange(t)[:, None], group_rank[:, :cfg["topk_group"]]].set(True)
    return scores, jnp.where(keep[:, :, None], biased, -jnp.inf).reshape(t, e)


def route(y, p, cfg):
    """y [T, d] -> (chosen expert ids [T, k], weights [T, k])."""
    scores, allowed = allowed_scores(y, p, cfg)
    chosen = jnp.argsort(-allowed, axis=-1, stable=True)[:, :cfg["top_k"]]
    w = jnp.take_along_axis(scores, chosen, 1)
    return chosen, w / w.sum(-1, keepdims=True) * cfg["routed_scale"]


def balance_bias(y, p, cfg, steps=400, rate=0.02):
    """The choice bias that ``noaux_tc``'s own rule comes to rest at on the
    tokens ``y [T, d]``: from ``p["router_bias"]``, each step every expert
    chosen more often than the mean moves down and every one chosen less
    often up, by ``rate`` falling linearly to 0 (the scores lie in (0, 1)).
    A trained model of this kind arrives with such a bias."""
    e = p["router"].shape[-1]

    def one(bias, i):
        chosen, _ = route(y, dict(p, router_bias=bias), cfg)
        load = (chosen[..., None] == jnp.arange(e)).sum((0, 1))
        return bias + rate * (1.0 - i / steps) * jnp.sign(
            load.mean() - load), None

    return jax.lax.scan(one, p["router_bias"],
                        jnp.arange(steps, dtype=jnp.float32))[0]


def routed_ffn(y, p, cfg, mm):
    """The held experts' part of the routed sum, expert by expert."""
    chosen, w = route(y, p, cfg)
    lo = cfg["held_lo"]

    def one(acc, x):
        wg, wu, wd, e = x
        share = jnp.sum(jnp.where(chosen == lo + e, w, 0.0), -1)    # [T]
        up = lambda a: a.astype(jnp.float32)
        return acc + share[:, None] * swiglu(y, up(wg), up(wu), up(wd),
                                             mm), None

    n = p["w_gate"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        p["w_gate"], p["w_up"], p["w_down"], jnp.arange(n)))
    return out


# -- blocks, module and model --------------------------------------------------

def after_mixer(x, p, cfg, quant=identity):
    mm = lambda a, w: jnp.matmul(quant(a), quant(w))
    return x + mla_mixer(rms_norm(x, p["norm_mix"], cfg["norm_eps"]), p, cfg,
                         mm, quant)


def ffn_input(x, p, cfg):
    """What the layer's feed-forward (and its router) is given."""
    return rms_norm(after_mixer(x, p, cfg), p["norm_ffn"], cfg["norm_eps"])


def block(x, p, kind, cfg, quant=identity):
    """One layer; ``kind`` is its (mixer, feed-forward) pair, the mixer
    always ``"mla"``."""
    mm = lambda a, w: jnp.matmul(quant(a), quant(w))
    x = after_mixer(x, p, cfg, quant)
    y = rms_norm(x, p["norm_ffn"], cfg["norm_eps"])
    if kind[1] == "dense":
        return x + swiglu(y, p["ffn_gate"], p["ffn_up"], p["ffn_down"], mm)
    b, l, d = y.shape
    flat = y.reshape(b * l, d)
    out = routed_ffn(flat, p, cfg, mm) + swiglu(
        flat, p["shared_gate"], p["shared_up"], p["shared_down"], mm)
    return x + out.reshape(b, l, d)


def embed(tokens, rest):
    return rest["emb"][tokens]


def head_logits(x, rest, cfg, quant=identity):
    """x: the last layer's output, before the final norm."""
    y = rms_norm(x, rest["norm_f"], cfg["norm_eps"])
    return jnp.matmul(quant(y), quant(rest["head"]))


def mtp_input(hidden, next_emb, p, cfg, quant=identity):
    """``u = W_eh [RMSNorm_h(h) ; RMSNorm_e(Emb(t_next))]``."""
    both = jnp.concatenate(
        [rms_norm(hidden, p["norm_h"], cfg["norm_eps"]),
         rms_norm(next_emb, p["norm_e"], cfg["norm_eps"])], -1)
    return jnp.matmul(quant(both), quant(p["eh"]))


def mtp_hidden(hidden, next_emb, p, cfg, quant=identity):
    """The module's block on ``u``: ``h'`` [B, L, d]."""
    return block(mtp_input(hidden, next_emb, p, cfg, quant), p["block"],
                 ("mla", "moe"), cfg, quant)


def mtp_logits(hp, p, rest, cfg, quant=identity):
    """``Head(RMSNorm_out(h'))`` with the main model's head."""
    y = rms_norm(hp, p["norm_out"], cfg["norm_eps"])
    return jnp.matmul(quant(y), quant(rest["head"]))


def forward(tokens, next_tokens, layers, mtp, rest, cfg, quant=identity):
    """Whole model on ``tokens`` [B, L] and the tokens that follow them
    (``next_tokens[:, i] = t_{i+1}``): float32 (main logits, module logits),
    both [B, L, vocab]. ``layers`` is a list of canonical layer dicts, one a
    pattern entry; ``mtp`` the canonical module."""
    x = embed(tokens, rest)
    for p, kind in zip(layers, cfg["pattern"]):
        x = block(x, p, tuple(kind), cfg, quant)
    hp = mtp_hidden(x, embed(next_tokens, rest), mtp, cfg, quant)
    return (head_logits(x, rest, cfg, quant),
            mtp_logits(hp, mtp, rest, cfg, quant))


# -- from the program's tree to these names ------------------------------------

def canonical_layer(blk, upcast_experts=True):
    """One ``block_i`` subtree of the program -> the reference's layer dict,
    float32 (the stacked expert kernels may stay in their stored type:
    ``routed_ffn`` upcasts one expert at a time)."""
    f32 = lambda a: a.astype(jnp.float32)
    m = blk["mla"]
    out = {"norm_mix": f32(blk["norm_mix"]["scale"]),
           "norm_ffn": f32(blk["norm_ffn"]["scale"]),
           "wqa": f32(m["qa_proj"]["kernel"]),
           "wqb": f32(m["qb_proj"]["kernel"]),
           "q_norm": f32(m["q_norm"]["scale"]),
           "wkva": f32(m["kva_proj"]["kernel"]),
           "c_norm": f32(m["c_norm"]["scale"]), "wkvb": f32(m["kvb_proj"]),
           "wo": f32(m["o_proj"]["kernel"])}
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


def canonical_mtp(tree, upcast_experts=True):
    """The program's ``mtp_0`` subtree -> the reference's module dict."""
    f32 = lambda a: a.astype(jnp.float32)
    return {"norm_h": f32(tree["norm_h"]["scale"]),
            "norm_e": f32(tree["norm_e"]["scale"]),
            "norm_out": f32(tree["norm_out"]["scale"]),
            "eh": f32(tree["eh_proj"]["kernel"]),
            "block": canonical_layer(tree["block"], upcast_experts)}


def canonical_rest(tree):
    f32 = lambda a: a.astype(jnp.float32)
    return {"emb": f32(tree["tok_emb"]["embedding"]),
            "norm_f": f32(tree["norm_f"]["scale"]),
            "head": f32(tree["lm_head"]["kernel"])}
