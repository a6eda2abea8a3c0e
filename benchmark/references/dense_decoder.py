"""Plain reference of the dense pre-LayerNorm decoder (GPT-2, StarCoder2).

Written from the published descriptions: token (and, for GPT-2, learned
position) embedding; per layer ``x += Wo . attention(LN(x))`` and
``x += W_out . gelu_tanh(W_in . LN(x) + b_in) + b_out``; a final LayerNorm and
a linear head. Attention is causal softmax(q k^T / sqrt(d_head)) v, with
grouped key/value heads repeated over their query heads and, for StarCoder2,
rotary positions in the split-half convention. float32 throughout, matmuls
under ``jax.default_matmul_precision("highest")`` (set by the caller around
the jitted call). No kernels, no cache, no batching tricks; it imports nothing
of the program.

``quant`` is the hook the lower-precision control uses: it is applied to both
operands of every matrix multiplication. The default is the identity.

Departures from the sources, followed because the program has them: no bias
on the attention projections, an untied head, LayerNorm epsilon as the
configuration file's ``as_run.norm_eps`` gives it.
"""
import jax
import jax.numpy as jnp


def identity(x):
    return x


def fake_fp8(x):
    """Round to 4 significant bits (e4m3's 1 + 3) with a per-tensor scale,
    with a straight-through gradient. It leaves e4m3's narrow exponent range
    out, so it errs on the side of being MORE exact than real fp8."""
    m, e = jnp.frexp(jax.lax.stop_gradient(x))
    rounded = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    return x + jax.lax.stop_gradient(rounded - x)


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def rope(x, positions, theta):
    """x [b, l, h, e]; split-half rotation by position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs       # [l, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(x, p, cfg, quant=identity):
    """One layer. x [b, l, d] float32; p the layer's plain-named leaves."""
    b, l, d = x.shape
    h, hkv = cfg["n_heads"], cfg["n_kv_heads"]
    e = d // h
    mm = lambda a, w: jnp.matmul(quant(a), quant(w))
    y = layer_norm(x, p["ln1_s"], p["ln1_b"], cfg["norm_eps"])
    q = mm(y, p["wq"]).reshape(b, l, h, e)
    k = mm(y, p["wk"]).reshape(b, l, hkv, e)
    v = mm(y, p["wv"]).reshape(b, l, hkv, e)
    if cfg["pos_emb"] == "rope":
        pos = jnp.arange(l)
        q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    s = jnp.einsum("bqhe,bkhe->bhqk", quant(q), quant(k)) * e ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhe->bqhe", quant(jax.nn.softmax(s, -1)), quant(v))
    x = x + mm(a.reshape(b, l, d), p["wo"])
    y = layer_norm(x, p["ln2_s"], p["ln2_b"], cfg["norm_eps"])
    y = gelu_tanh(mm(y, p["w_in"]) + p["b_in"])
    return x + mm(y, p["w_out"]) + p["b_out"]


def embed(tokens, rest, cfg):
    x = rest["emb"][tokens]
    if cfg["pos_emb"] == "learned":
        x = x + rest["pos"][:tokens.shape[1]][None]
    return x


def head_logits(x, rest, cfg, quant=identity):
    y = layer_norm(x, rest["lnf_s"], rest["lnf_b"], cfg["norm_eps"])
    return jnp.matmul(quant(y), quant(rest["head"]))


def loss(params, tokens, targets, cfg, quant=identity):
    """Mean next-token cross-entropy over the rows given. ``params`` is
    {"layers": leaves stacked on a leading layer axis, "rest"}; the layers
    run under a rematerialised scan so a float32 backward pass fits."""
    x = embed(tokens, params["rest"], cfg)

    @jax.checkpoint
    def body(x, p):
        return block(x, p, cfg, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    logits = head_logits(x, params["rest"], cfg, quant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def adamw(params, grads, m, v, t, opt):
    """One AdamW step as Loshchilov & Hutter give it, decay on every leaf."""
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - opt["lr"] * (
            (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
            + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v
