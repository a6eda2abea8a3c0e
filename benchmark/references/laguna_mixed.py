"""Plain reference of the Laguna-XS.2 language model's layers, and the map from
the program's parameter tree to the reference's names.

Written from the equations of ISSUE 39 (``benchmark/configs/laguna-xs.2.json``
has them under ``equations`` and lists what was assumed); float32 throughout,
matmuls under ``jax.default_matmul_precision("highest")`` (set by the caller
around the jitted call). No cache, no ring, no kernel, no online softmax, no
batching of rows; it imports nothing of the program.

    h = x + Attn_l(RMSNorm(x)),  y = h + FFN_l(RMSNorm(h));  final RMSNorm, head

* ``Attn_l`` — ``H_l`` query heads over 8 keys and values of 128, query head
  ``h`` reading KV head ``h // (H_l / 8)``; rotary on the first ``rotary``
  share of a head, the first half of those dimensions against the second, as
  complex numbers turned by ``position · frequency``: plain ``theta^(-2i/n)``
  on the window layers, YaRN's blend with cos and sin scaled by
  ``attention_factor`` on the full ones; ``softmax(q·k / sqrt(128))`` under an
  EXPLICIT mask, ``j <= i`` and on the window layers also ``i - j < window``,
  computed for ``q_block`` queries at a time against every key (a 33k x 33k
  score array a head would not fit; blocks past ``n_real`` are skipped);
  one sigmoid gate a head from the layer's normed input on the attended
  values; then ``W_o``. The two kinds differ in ``H_l``, the rotary share,
  theta, the scaling and the window, all read from ``cfg["kinds"][mixer]``.
* ``FFN_l`` — SwiGLU, dense, or routed: ``s = sigmoid(W_r x)`` over all
  experts in float32, the ``top_k`` best of ``s + b``, weights ``s`` over the
  chosen, normalised, times ``routed_scale``, on the experts' OUTPUTS; every
  expert by a dense loop over all tokens with the weight 0 where it was not
  chosen; plus the shared expert.

Departures from the description: the query heads are folded ``[8, H_l / 8]``
against the 8 KV heads instead of repeating K and V ``H_l / 8`` times (the
same sums; a float32 repeat of 32,768 keys to 64 heads is 1 GB); the softmax
runs over a block of queries at a time; an expert is evaluated for every
token and weighted 0 where it was not routed (the same sum as evaluating it
for its own tokens).

``quant`` is the hook the lower-precision control uses: it is applied to both
operands of every matrix multiplication — the cached keys and values among
them — EXCEPT the router's, which the configuration states as float32 on
both sides of the comparison.
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


# -- rotary ----------------------------------------------------------------------

def frequencies(n, kind):
    """The ``n / 2`` frequencies of ``n`` rotated dimensions and the factor
    on cos and sin: plain, or YaRN's (``find_correction_range`` over the pair
    indices, the interpolated frequency above it, the plain one below, a
    linear ramp between)."""
    theta, sc = kind["theta"], kind.get("scaling")
    plain = 1.0 / theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    if not sc:
        return plain, 1.0

    def correction_dim(rotations):
        return (n * math.log(sc["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), n - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(n // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (plain / sc["factor"] * ramp + plain * (1.0 - ramp),
            sc["attention_factor"])


def rotary(x, kind):
    """x [B, L, H, d]: dimension ``i`` of the first ``n = rotary · d`` paired
    with ``i + n/2`` as a complex number and turned by position · frequency;
    the rest of the head passes through."""
    l, d = x.shape[1], x.shape[-1]
    n = int(d * kind["rotary"]) // 2 * 2
    freqs, amp = frequencies(n, kind)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freqs        # [L, n/2]
    turn = (amp * jnp.exp(1j * ang))[None, :, None, :]
    z = jax.lax.complex(x[..., :n // 2], x[..., n // 2:n]) * turn
    return jnp.concatenate([z.real, z.imag, x[..., n:]], -1)


# -- attention -------------------------------------------------------------------

def attention(y, p, kind, cfg, mm, quant, n_real=None):
    b, l, _ = y.shape
    h, n_kv, dh = kind["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    window = kind.get("window") or 0
    q = rotary(mm(y, p["wq"]).reshape(b, l, h, dh), kind)
    k = quant(rotary(mm(y, p["wk"]).reshape(b, l, n_kv, dh), kind))
    v = quant(mm(y, p["wv"]).reshape(b, l, n_kv, dh))
    q = q.reshape(b, l, n_kv, h // n_kv, dh)        # head h -> KV head h // G
    qb = min(cfg.get("q_block", 256), l)
    if l % qb:
        raise ValueError(f"length {l} is no multiple of the query block {qb}")
    j = jnp.arange(l)[None]

    def rows(blk, out):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, blk * qb, qb, 1)
        s = jnp.einsum("bqkgd,btkd->bkgqt", quant(take(q)), k) * dh ** -0.5
        i = blk * qb + jnp.arange(qb)[:, None]
        seen = j <= i
        if window:
            seen &= i - j < window
        s = jnp.where(seen, s, -jnp.inf)
        o = jnp.einsum("bkgqt,btkd->bqkgd", quant(jax.nn.softmax(s, -1)), v)
        return jax.lax.dynamic_update_slice_in_dim(
            out, o.reshape(b, qb, h, dh), blk * qb, 1)

    n_blocks = l // qb if n_real is None else (n_real + qb - 1) // qb
    o = jax.lax.fori_loop(0, n_blocks, rows,
                          jnp.zeros((b, l, h, dh), jnp.float32))
    if "wg" in p:
        o = o * jax.nn.sigmoid(mm(y, p["wg"]))[..., None]
    return mm(o.reshape(b, l, h * dh), p["wo"])


# -- routed feed-forward ---------------------------------------------------------

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
    """The choice bias that a bias-balancing rule comes to rest at on the
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


# -- blocks and model ------------------------------------------------------------

def after_mixer(x, p, kind, cfg, quant=identity, n_real=None):
    mm = lambda a, w: jnp.matmul(quant(a), quant(w))
    return x + attention(rms_norm(x, p["norm_mix"], cfg["norm_eps"]), p,
                         cfg["kinds"][kind[0]], cfg, mm, quant, n_real)


def ffn_input(x, p, kind, cfg):
    """What the layer's feed-forward (and its router) is given."""
    return rms_norm(after_mixer(x, p, kind, cfg), p["norm_ffn"],
                    cfg["norm_eps"])


def block(x, p, kind, cfg, quant=identity, n_real=None):
    """One layer on ``x [B, L, d]``; ``kind`` is its (mixer, feed-forward)
    pair, the mixer one of ``cfg["kinds"]``."""
    mm = lambda a, w: jnp.matmul(quant(a), quant(w))
    h = after_mixer(x, p, kind, cfg, quant, n_real)
    y = rms_norm(h, p["norm_ffn"], cfg["norm_eps"])
    if kind[1] == "dense":
        return h + swiglu(y, p["ffn_gate"], p["ffn_up"], p["ffn_down"], mm)
    b, l, d = y.shape
    flat = y.reshape(b * l, d)
    out = routed_ffn(flat, p, cfg, mm) + swiglu(
        flat, p["shared_gate"], p["shared_up"], p["shared_down"], mm)
    return h + out.reshape(b, l, d)


def embed(tokens, rest, cfg):
    return rest["emb"][tokens]


def head_logits(x, rest, cfg, quant=identity):
    y = rms_norm(x, rest["norm_f"], cfg["norm_eps"])
    return jnp.matmul(quant(y), quant(rest["head"]))


def forward(tokens, layers, rest, cfg, quant=identity):
    """Whole model on ``tokens`` [B, L]: float32 logits [B, L, vocab].
    ``layers`` is a list of canonical layer dicts, one a pattern entry."""
    x = embed(tokens, rest, cfg)
    for p, kind in zip(layers, cfg["pattern"]):
        x = block(x, p, tuple(kind), cfg, quant)
    return head_logits(x, rest, cfg, quant)


# -- from the program's tree to these names --------------------------------------

def canonical_layer(blk, upcast_experts=True):
    """One ``block_i`` subtree of the program -> the reference's layer dict,
    float32 (the stacked expert kernels may stay in their stored type:
    ``routed_ffn`` upcasts one expert at a time)."""
    f32 = lambda a: a.astype(jnp.float32)
    m = blk["gqa"] if "gqa" in blk else blk["swa"]
    out = {"norm_mix": f32(blk["norm_mix"]["scale"]),
           "norm_ffn": f32(blk["norm_ffn"]["scale"]),
           "wq": f32(m["q_proj"]["kernel"]), "wk": f32(m["k_proj"]["kernel"]),
           "wv": f32(m["v_proj"]["kernel"]), "wo": f32(m["o_proj"]["kernel"])}
    if "g_proj" in m:
        out["wg"] = f32(m["g_proj"]["kernel"])
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
