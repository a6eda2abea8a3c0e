"""Plain reference of the Ling-3.0-flash language model's layers, and the map
from the program's parameter tree to the reference's names.

Written from the equations of ISSUE 27 and the public descriptions its
configuration keys point at; float32 throughout, matmuls under
``jax.default_matmul_precision("highest")`` (set by the caller around the
jitted call). No kernels, no cache, no batching tricks; it imports nothing of
the program.

Pre-norm residual blocks, ``x̃ = RMSNorm(x)`` with epsilon ``norm_eps``:

* KDA mixer (Kimi Linear, arXiv:2510.26692): ``q, k, v = SiLU(conv4(W x̃))``
  with a causal depthwise convolution of 4 taps; q and k L2-normalised per
  head, q scaled by ``d_head^-1/2``; per head the state follows
  ``S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ``,
  ``o_t = S_tᵀ q_t``, run here as a SEQUENTIAL scan over tokens;
  ``β = σ(W_β x̃)`` per head, ``α = exp(g)`` per channel with
  ``g = lower_bound · σ(exp(A_log_h) · (W_f x̃ + b_dt))``; the output is
  ``W_o(RMSNorm_head(o) ⊙ σ(W_g x̃))``.
* MLA mixer (DeepSeek-V2), EXPANDED: ``q = W_q x̃`` (128 nope + 64 rope a
  head), ``[c; k_r] = W_kva x̃`` with c RMS-normed and k_r shared by the
  heads, ``[k_nope; v]_h = W_kvb c``; rotary on the 64 rope dims in adjacent
  pairs; causal ``softmax((q_nope·k_nope + q_rope·k_r)/√192)``; a head-wise
  gate ``σ(w_h·x̃)`` before ``W_o``.
* Feed-forward: SwiGLU ``W_d(SiLU(W_g x̃) ⊙ W_u x̃)``, dense, or routed:
  ``s = σ(W_r x̃)`` over all experts, the choice on ``s + b`` limited to the
  ``topk_group`` best of ``n_group`` groups (a group's score: the sum of its
  two best), the ``top_k`` best experts among them, weights ``s`` over the
  chosen, normalised, times ``routed_scale``; the experts held here
  (``held_lo:held_hi``) by a dense loop, the others' pairs left out of the
  sum; plus the shared expert. The cell's ``b`` is the bias that
  ``noaux_tc``'s balancing comes to rest at, computed with ``route`` below
  by ``references/balance.py``.

``quant`` is the hook the lower-precision control uses: it is applied to both
operands of every matrix multiplication EXCEPT the router's, whose scores are
float32 on both sides of the comparison (a control that re-routes every
token would fail for the routing, not for the precision).
"""
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


# -- KDA ---------------------------------------------------------------------

def causal_conv(u, w):
    """u [B, L, C], w [taps, C]: y_t = Σ_j w[j] u_{t - (taps-1) + j}."""
    taps = w.shape[0]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + u.shape[1]] * w[j] for j in range(taps))


def kda_scan(q, k, v, g, beta, state=None):
    """The recurrence, one token at a time. q, k, g [B, L, H, dk]; v
    [B, L, H, dv]; beta [B, L, H]. Returns (o [B, L, H, dv], final state
    [B, H, dk, dv])."""
    b, l, h, dk = q.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def step(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[..., None] * s
        ks = jnp.einsum("bhk,bhkv->bhv", k, s)
        s = s + jnp.einsum("bhk,bhv->bhkv", beta[..., None] * k, v - ks)
        return s, jnp.einsum("bhk,bhkv->bhv", q, s)

    t = lambda a: jnp.moveaxis(a, 1, 0)
    state, o = jax.lax.scan(step, state, tuple(map(t, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1), state


def kda_mixer(y, p, cfg, mm):
    b, l, _ = y.shape
    h, dk = cfg["n_heads"], cfg["d_head"]
    conv = lambda w, cw: jax.nn.silu(causal_conv(mm(y, w), cw))
    q = conv(p["wq"], p["conv_q"]).reshape(b, l, h, dk)
    k = conv(p["wk"], p["conv_k"]).reshape(b, l, h, dk)
    v = conv(p["wv"], p["conv_v"]).reshape(b, l, h, dk)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True)
                                  + cfg["norm_eps"])
    q, k = unit(q) * dk ** -0.5, unit(k)
    f = (mm(y, p["wf"]) + p["dt_bias"]).reshape(b, l, h, dk)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[:, None] * f)
    beta = jax.nn.sigmoid(mm(y, p["wb"]))
    o, _ = kda_scan(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"], cfg["norm_eps"]).reshape(b, l, h * dk)
    return mm(o * jax.nn.sigmoid(mm(y, p["wg"])), p["wo"])


# -- MLA ---------------------------------------------------------------------

def rotary_pairs(x, theta):
    """x [B, L, ..., d]: adjacent pairs as complex numbers, turned by
    position · theta^(-2i/d)."""
    d, l = x.shape[-1], x.shape[1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freqs
    turn = jnp.exp(1j * ang).reshape((1, l) + (1,) * (x.ndim - 3) + (d // 2,))
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * turn
    return jnp.stack([z.real, z.imag], -1).reshape(x.shape)


def mla_mixer(y, p, cfg, mm, quant):
    b, l, _ = y.shape
    h, dn, dr, r = (cfg["n_heads"], cfg["d_nope"], cfg["d_rope"],
                    cfg["kv_rank"])
    dv = cfg["d_head"]
    q = mm(y, p["wq"]).reshape(b, l, h, dn + dr)
    q_nope, q_rope = q[..., :dn], rotary_pairs(q[..., dn:], cfg["rope_theta"])
    kva = mm(y, p["wkva"])
    c = rms_norm(kva[..., :r], p["c_norm"], cfg["norm_eps"])
    k_rope = rotary_pairs(kva[..., r:], cfg["rope_theta"])
    kv = mm(c, p["wkvb"]).reshape(b, l, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("bqhe,bkhe->bhqk", quant(q_nope), quant(k_nope))
         + jnp.einsum("bqhe,bke->bhqk", quant(q_rope), quant(k_rope)))
    s = s * (dn + dr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhe->bqhe", quant(jax.nn.softmax(s, -1)), quant(v))
    o = o * jax.nn.sigmoid(mm(y, p["wg"]))[..., None]
    return mm(o.reshape(b, l, h * dv), p["wo"])


# -- routed feed-forward -------------------------------------------------------

def route(y, p, cfg):
    """y [T, d] -> (chosen expert ids [T, k], weights [T, k], scores [T, E]).
    Float32, never quantised."""
    scores = jax.nn.sigmoid(jnp.matmul(y, p["router"]))
    t, e = scores.shape
    ng = cfg["n_group"]
    biased = (scores + p["router_bias"]).reshape(t, ng, e // ng)
    best_two = -jnp.sort(-biased, axis=-1)[..., :2]
    group_rank = jnp.argsort(-best_two.sum(-1), axis=-1, stable=True)
    keep = jnp.zeros((t, ng), bool).at[
        jnp.arange(t)[:, None], group_rank[:, :cfg["topk_group"]]].set(True)
    allowed = jnp.where(keep[:, :, None], biased, -jnp.inf).reshape(t, e)
    chosen = jnp.argsort(-allowed, axis=-1, stable=True)[:, :cfg["top_k"]]
    w = jnp.take_along_axis(scores, chosen, 1)
    w = w / w.sum(-1, keepdims=True) * cfg["routed_scale"]
    return chosen, w, scores


def route_margin(y, p, cfg):
    """y [T, d] -> [T]: how far the 8th chosen expert's biased score lies
    above the best one not chosen (among the kept groups). A margin below
    the noise of the hidden state's precision is a choice that a bfloat16
    program and this float32 reference may make differently."""
    scores = jax.nn.sigmoid(jnp.matmul(y, p["router"]))
    t, e = scores.shape
    ng = cfg["n_group"]
    biased = (scores + p["router_bias"]).reshape(t, ng, e // ng)
    best_two = -jnp.sort(-biased, axis=-1)[..., :2]
    group_rank = jnp.argsort(-best_two.sum(-1), axis=-1, stable=True)
    keep = jnp.zeros((t, ng), bool).at[
        jnp.arange(t)[:, None], group_rank[:, :cfg["topk_group"]]].set(True)
    allowed = jnp.where(keep[:, :, None], biased, -jnp.inf).reshape(t, e)
    ranked = -jnp.sort(-allowed, axis=-1)
    k = cfg["top_k"]
    return ranked[:, k - 1] - ranked[:, k]


def routed_ffn(y, p, cfg, mm):
    """The held experts' part of the routed sum, expert by expert."""
    chosen, w, _ = route(y, p, cfg)
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


# -- blocks and model ----------------------------------------------------------

def after_mixer(x, p, kind, cfg, quant=identity):
    mm = lambda a, w: jnp.matmul(quant(a), quant(w))
    y = rms_norm(x, p["norm_mix"], cfg["norm_eps"])
    if kind[0] == "kda":
        return x + kda_mixer(y, p, cfg, mm)
    return x + mla_mixer(y, p, cfg, mm, quant)


def ffn_input(x, p, kind, cfg):
    """What the layer's feed-forward (and its router) is given."""
    return rms_norm(after_mixer(x, p, kind, cfg), p["norm_ffn"],
                    cfg["norm_eps"])


def block(x, p, kind, cfg, quant=identity):
    """One layer; ``kind`` is its (mixer, feed-forward) pair."""
    mm = lambda a, w: jnp.matmul(quant(a), quant(w))
    x = after_mixer(x, p, kind, cfg, quant)
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
    y = rms_norm(x, rest["norm_f"], cfg["norm_eps"])
    return jnp.matmul(quant(y), quant(rest["head"]))


def forward(tokens, layers, rest, cfg, quant=identity):
    """Whole model on ``tokens`` [B, L]: float32 logits [B, L, vocab].
    ``layers`` is a list of canonical layer dicts, one a pattern entry."""
    x = embed(tokens, rest)
    for p, kind in zip(layers, cfg["pattern"]):
        x = block(x, p, tuple(kind), cfg, quant)
    return head_logits(x, rest, cfg, quant)


# -- from the program's tree to these names ------------------------------------

def canonical_layer(blk, upcast_experts=True):
    """One ``block_i`` subtree of the program -> the reference's layer dict,
    float32 (the stacked expert kernels may stay in their stored type:
    ``routed_ffn`` upcasts one expert at a time)."""
    f32 = lambda a: a.astype(jnp.float32)
    out = {"norm_mix": f32(blk["norm_mix"]["scale"]),
           "norm_ffn": f32(blk["norm_ffn"]["scale"])}
    if "kda" in blk:
        m = blk["kda"]
        out.update(
            wq=f32(m["q_proj"]["kernel"]), wk=f32(m["k_proj"]["kernel"]),
            wv=f32(m["v_proj"]["kernel"]), conv_q=f32(m["q_conv"]),
            conv_k=f32(m["k_conv"]), conv_v=f32(m["v_conv"]),
            a_log=f32(m["a_log"]), dt_bias=f32(m["dt_bias"]),
            wf=f32(m["f_proj"]["kernel"]), wb=f32(m["b_proj"]["kernel"]),
            wg=f32(m["g_proj"]["kernel"]), o_norm=f32(m["o_norm"]["scale"]),
            wo=f32(m["o_proj"]["kernel"]))
    else:
        m = blk["mla"]
        out.update(
            wq=f32(m["q_proj"]["kernel"]), wkva=f32(m["kva_proj"]["kernel"]),
            c_norm=f32(m["c_norm"]["scale"]), wkvb=f32(m["kvb_proj"]),
            wg=f32(m["g_proj"]["kernel"]), wo=f32(m["o_proj"]["kernel"]))
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
